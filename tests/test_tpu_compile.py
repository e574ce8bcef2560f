"""Compiles for a TPU v5e that is described, not attached.

The TPU compiler refuses what the Pallas interpreter accepts (unaligned
blocks, too much fast memory, ops Mosaic cannot lower), so the main-path
kernels and the deployment-shaped epoch scan are compiled here for a
``v5e:2x2`` topology on every run.  Nothing executes.  The topology is
described inside a module fixture, never while a module is imported: only
one process at a time may load the TPU library.  The persistent
compilation cache is off around these compiles (an entry written for a
described chip cannot be read back without one).
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.engine.backends import resolve_backend_for_layout
from repro.engine.data import DSOState, TileData
from repro.engine.driver import run_epochs
from repro.kernels import dso_sparse, dso_update
from repro.sparse.format import ONEHOT_MAX_DB

#: real-sim at p=4: m=72,309 and d=20,958 padded to 72,312 x 20,960; the
#: flat chunk view of its K-bucketed grid (bucket widths 8/16/56, tile-K
#: skew 5.1 at the power-law model of chip_smoke.py, seed 0) holds 13
#: chunks of K_CHUNK=8 columns per processor, 7 for the widest tile
REALSIM_P4 = dict(p=4, mb=18_078, db=5_240, n_chunks=13, n_kc=7)
#: packed width of its uniform block-ELL grid (ids spread over the blocks)
REALSIM_K = 32
#: news20.binary at p=4: m=19,996 and d=1,355,191 padded to 19,996 x
#: 1,355,192; its uniform block-ELL grid (tile-K skew 1.079 at the
#: benchmark's Zipf model, data seed 0) is 160 slots wide
NEWS20_P4 = dict(p=4, mb=4_999, db=338_798, K=160)

LOSS_REG = [("hinge", "l2"), ("logistic", "l2"), ("square", "l1")]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _f32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


@pytest.mark.parametrize("loss,reg", LOSS_REG)
def test_fused_tile_step_compiles_for_v5e(one_chip, loss, reg):
    M = D = 1024
    s = lambda *shape: _f32(shape, one_chip)  # noqa: E731
    compiled = dso_update.dso_tile_step_pallas.lower(
        s(M, D), s(M), s(D), s(M), s(D), s(M), s(M), s(D), s(5),
        loss_name=loss, reg_name=reg, bm=256, bd=512, interpret=False,
        tile_row_nnz=s(M), tile_col_nnz=s(D)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("loss,reg", LOSS_REG)
def test_block_step_compiles_for_v5e(one_chip, loss, reg):
    """The one-launch block kernel at row_batches=4: its per-row-tile
    column counts are a (4, 1, D) array read in squeezed (1, bd) blocks."""
    M = D = 1024
    rb = 4
    s = lambda *shape: _f32(shape, one_chip)  # noqa: E731
    compiled = dso_update.dso_block_step_pallas.lower(
        s(M, D), s(M), s(D), s(M), s(D), s(M), s(M), s(rb, D), s(M), s(D),
        s(5), row_batches=rb, loss_name=loss, reg_name=reg, bd=512,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_run_epochs_bucketed_compiles_at_realsim_p4(one_chip):
    """The default path at the deployment's shape: ``run_epochs`` of
    ``sparse_bucketed_jnp`` (what ``backend="auto"`` picks for real-sim)
    over a 5-epoch chunk, within one v5e chip's 16 GB."""
    p, mb, db = (REALSIM_P4[k] for k in ("p", "mb", "db"))
    n_ch, n_kc = REALSIM_P4["n_chunks"], REALSIM_P4["n_kc"]
    d_pad, n = p * db, 5

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    tile = TileData(
        arrays=(sds((p, n_ch, mb, 8), jnp.int32), sds((p, n_ch, mb, 8)),
                sds((p, p, n_kc), jnp.int32), sds((p, p), jnp.int32)),
        yg=sds((p, mb)), row_nnz_g=sds((p, mb)), col_nnz=sds((d_pad,)),
        row_valid=sds((p, mb)), tile_col_nnz_g=sds((p, 1, d_pad)),
        tile_row_nnz_g=sds((p, p, mb)))
    state = DSOState(sds((p, db)), sds((p, db)), sds((p, mb)), sds((p, mb)),
                     sds((), jnp.int32))
    scalar = sds(())
    compiled = run_epochs.lower(
        tile, state, sds((n, p, p), jnp.int32), sds((n,)), scalar, scalar,
        scalar, scalar, backend="sparse_bucketed_jnp", loss_name="hinge",
        reg_name="l2", use_adagrad=True, row_batches=1, p=p,
        db=db).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < 16e9, total
    assert np.isfinite(total)


def _compile_onehot_kernel(one_chip, db, row_batches, use_adagrad=True):
    p, mb, K = REALSIM_P4["p"], REALSIM_P4["mb"], REALSIM_K

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    step = jax.vmap(lambda *a: dso_sparse.dso_sparse_block_step_pallas(
        *a, row_batches=row_batches, loss_name="hinge", reg_name="l2",
        use_adagrad=use_adagrad, interpret=False))
    return jax.jit(step).lower(
        sds((p, p, mb, K), jnp.int32), sds((p, p, mb, K)),
        sds((p,), jnp.int32), sds((p, mb)), sds((p, db)), sds((p, mb)),
        sds((p, db)), sds((p, mb)), sds((p, mb)),
        sds((p, row_batches, db)), sds((p, mb)), sds((p, db)),
        sds((p, 5))).compile()


def test_onehot_sparse_kernel_compiles_at_realsim_p4(one_chip):
    """The one-hot sparse kernel at real-sim's uniform p=4 shape, vmapped
    over the 4 processors as the grid simulator runs it: each reads its
    active tile from its (4, 18078, 32) payload in place."""
    compiled = _compile_onehot_kernel(one_chip, REALSIM_P4["db"], 1)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("row_batches,use_adagrad", [(1, True), (8, False),
                                                     (4096, True)])
def test_onehot_sparse_kernel_compiles_at_widest_auto_block(
        one_chip, row_batches, use_adagrad):
    """The widest block ``auto`` gives the one-hot kernel on a TPU
    (``ONEHOT_MAX_DB`` columns, 128 lane rows of w), at real-sim's rows
    and K, with one to 4,096 row batches (of four rows each): the tile's
    per-batch column counts stay in HBM, so the fast memory the kernel
    needs does not grow with ``row_batches`` and the compiler accepts
    every count."""
    compiled = _compile_onehot_kernel(one_chip, ONEHOT_MAX_DB, row_batches,
                                      use_adagrad)
    assert "tpu_custom_call" in compiled.as_text()


def _uniform_run_epochs(one_chip, backend, *, p, mb, db, K,
                       loss="hinge", n=5):
    d_pad = p * db

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    tile = TileData(
        arrays=(sds((p, p, mb, K), jnp.int32), sds((p, p, mb, K))),
        yg=sds((p, mb)), row_nnz_g=sds((p, mb)), col_nnz=sds((d_pad,)),
        row_valid=sds((p, mb)), tile_col_nnz_g=sds((p, 1, d_pad)),
        tile_row_nnz_g=sds((p, p, mb)))
    state = DSOState(sds((p, db)), sds((p, db)), sds((p, mb)), sds((p, mb)),
                     sds((), jnp.int32))
    scalar = sds(())
    return run_epochs.lower(
        tile, state, sds((n, p, p), jnp.int32), sds((n,)), scalar, scalar,
        scalar, scalar, backend=backend, loss_name=loss, reg_name="l2",
        use_adagrad=True, row_batches=1, p=p, db=db).compile()


def _realsim_uniform_run_epochs(one_chip, backend):
    return _uniform_run_epochs(
        one_chip, backend, K=REALSIM_K,
        **{k: REALSIM_P4[k] for k in ("p", "mb", "db")})


def test_run_epochs_auto_uniform_compiles_at_realsim_p4(topo, one_chip):
    """``run_epochs`` with the backend ``auto`` resolves on a TPU for
    real-sim's uniform grid: the one-hot kernel, inside the epoch scan,
    with no more temporary memory than ``sparse_jnp`` at the same shape
    (the guard of ``peak_hbm_bytes``: no tile is copied out of the grid)."""
    with jax.default_device(topo.devices[0]):
        backend = resolve_backend_for_layout("auto", "sparse",
                                             REALSIM_P4["db"]).name
        assert backend == "sparse_pallas"
        compiled = _realsim_uniform_run_epochs(one_chip, backend)
        baseline = _realsim_uniform_run_epochs(one_chip, "sparse_jnp")
    assert "tpu_custom_call" in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= baseline.memory_analysis().temp_size_in_bytes, temp


def test_run_epochs_auto_wide_uniform_compiles_at_news20_p4(topo, one_chip):
    """``run_epochs`` with the backend ``auto`` resolves on a TPU for
    news20's uniform grid, whose 338,798-column blocks are wider than
    ``ONEHOT_MAX_DB``: XLA's gather and scatter-add (``sparse_jnp``), with
    the logistic loss, over a one-epoch chunk, within one v5e chip's 16 GB.
    The compiled program's metadata names the gather's ops with the
    ``xw_gather`` scope and the scatter-add's with ``xta_scatter``, both
    inside ``tile_step``: the paths a profile gives ``gather_ms`` and
    ``scatter_ms``."""
    with jax.default_device(topo.devices[0]):
        backend = resolve_backend_for_layout("auto", "sparse",
                                             NEWS20_P4["db"]).name
    assert NEWS20_P4["db"] > ONEHOT_MAX_DB and backend == "sparse_jnp"
    compiled = _uniform_run_epochs(one_chip, backend, loss="logistic", n=1,
                                   **NEWS20_P4)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < 16e9, total
    paths = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    for scope in ("xw_gather", "xta_scatter"):
        assert any(re.search(rf"tile_step\)?/(.+/)?{scope}/", p)
                   for p in paths), scope


def test_sharded_ring_onehot_compiles_on_v5e_2x2(topo):
    """``ShardedDSO`` takes the one-hot kernel on a TPU at real-sim's
    uniform shape: the overlapped cyclic ring over the four chips of a
    v5e:2x2, one processor per chip (the kernel unbatched, inside
    ``shard_map``).  The kernel's (K, M) view of each chip's payload is a
    bitcast of how the chip stores it: the program's temporaries stay
    below the size of one payload array per chip, so no copy of the grid
    is made in any step."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.dso_dist import _epoch_shardmap

    p, mb, db = (REALSIM_P4[k] for k in ("p", "mb", "db"))
    K, d_pad, n = REALSIM_K, p * db, 2
    mesh = Mesh(np.array(topo.devices[:p]), ("dso",))

    def sds(shape, dtype=jnp.float32, spec=P("dso")):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    with jax.default_device(topo.devices[0]):
        fn = _epoch_shardmap(mesh, p, db, "hinge", "l2", True, 1,
                             backend_name="sparse_pallas")
        compiled = fn.lower(
            sds((p, p, mb, K), jnp.int32), sds((p, p, mb, K)),
            sds((p, mb)), sds((p, mb)), sds((p, 1, d_pad)),
            sds((p, p, mb)), sds((d_pad,), spec=P(None)),
            sds((p, db)), sds((p, db)), sds((p, mb)), sds((p, mb)),
            sds((n,), spec=P()), sds((n, p, p), jnp.int32, spec=P()),
            *(sds((), spec=P()) for _ in range(4))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    payload_per_chip = p * mb * K * 4          # one of cols, vals
    assert compiled.memory_analysis().temp_size_in_bytes < payload_per_chip
