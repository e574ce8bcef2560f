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

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.engine.data import DSOState, TileData
from repro.engine.driver import run_epochs
from repro.kernels import dso_update

#: real-sim at p=4: m=72,309 and d=20,958 padded to 72,312 x 20,960; the
#: flat chunk view of its K-bucketed grid (bucket widths 8/16/56, tile-K
#: skew 5.1 at the power-law model of chip_smoke.py, seed 0) holds 13
#: chunks of K_CHUNK=8 columns per processor, 7 for the widest tile
REALSIM_P4 = dict(p=4, mb=18_078, db=5_240, n_chunks=13, n_kc=7)

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
