"""K-bucketed ragged sparse backend + load-balanced schedule coverage.

Six groups, mirroring the PR 4 / PR 8 acceptance gates:

  1. packing    — bucket-width assignment invariants, and the round-trip
                  property: every tile of a ``BucketedGridData`` densifies
                  to exactly the same tile as the uniform
                  ``SparseGridData`` (deterministic + hypothesis forms),
                  with identical scaling statistics; the flat chunk view's
                  offset table reassembles every tile's exact (mb, K_k)
                  rectangle (``flat_tile`` == ``tile``).
  2. trajectory — ``sparse_bucketed_jnp`` / ``sparse_bucketed_pallas``
                  equal ``sparse_jnp`` to <= 1e-5 on every loss/reg pair
                  on a power-law-skewed problem (the PR 4 acceptance
                  gate).
  3. one-kernel — the scalar-prefetch one-kernel Pallas backend is
                  BIT-identical to ``sparse_bucketed_jnp`` (same staged
                  math by construction) across loss x reg, {cyclic, lpt},
                  and bucket counts 1-4, and within 1e-5 of the legacy
                  ``lax.switch`` backends; the ops wrapper matches the
                  independent ``dso_bucketed_block_step_ref`` oracle, and
                  the platform-only interpret resolution / the
                  per-platform Mosaic probe cache behave.
  4. schedules  — the LPT schedule is a valid (n_epochs, p, p) permutation
                  array (never two workers on one block), covers every
                  (worker, block) pair per epoch, balances a skewed cost
                  matrix better than cyclic, and drives the grid runner.
  5. auto       — ``impl="auto"`` upgrades to the bucketed layout exactly
                  when the tile-K skew crosses the threshold in the sparse
                  regime; the ingester's pass-1 ``k_per_tile`` matches the
                  tiler's, so the decision needs no extra data pass.
  6. sharded    — grid == sharded for both bucketed backends under both
                  the cyclic and the LPT schedule (subprocess, 4 host
                  devices); plus the ``dso_sparse_block_step`` interpret
                  default now auto-detects the backend like the dense ops.
"""

import os
import subprocess
import sys
import tempfile
import textwrap

import numpy as np
import jax.numpy as jnp
import pytest

from _hypothesis_compat import given, settings, st
from repro.core.dso import run_dso_grid
from repro.data.synthetic import make_skewed_classification
from repro.engine import fixed_schedule, get_schedule, lpt_latin_square, solve
from repro.engine.backends import resolve_backend, resolve_backend_for_layout
from repro.kernels import ops
from repro.sparse import (BUCKET_SKEW_THRESHOLD, MAX_K_BUCKETS, SparseTile,
                          assign_k_buckets, choose_k, grid_nbytes,
                          ingest_libsvm, make_bucketed_grid_data,
                          make_sparse_grid_data, packed_bytes_per_step,
                          problem_k_per_tile, scan_libsvm,
                          sparse_grid_from_csr, tile_k_skew)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LOSS_REG_PAIRS = [("hinge", "l2"), ("hinge", "l1"), ("logistic", "l2"),
                  ("logistic", "l1"), ("square", "l2"), ("square", "l1")]


def _skewed(m=120, d=64, density=0.15, alpha=1.3, loss="hinge", reg="l2",
            seed=0):
    return make_skewed_classification(m=m, d=d, density=density, alpha=alpha,
                                      loss=loss, lam=1e-3, seed=seed,
                                      reg=reg)


# ---------------------------------------------------------------- packing --


def test_assign_k_buckets_invariants():
    rng = np.random.default_rng(0)
    k_raw = rng.integers(1, 300, size=(6, 6))
    widths, bucket_id = assign_k_buckets(k_raw)
    assert len(widths) <= MAX_K_BUCKETS
    assert list(widths) == sorted(set(widths))       # ascending, distinct
    for q in range(6):
        for b in range(6):
            w = widths[bucket_id[q, b]]
            assert w % 8 == 0                        # sublane-aligned
            assert w >= choose_k(int(k_raw[q, b]))   # covers the tile
    # the widest bucket is the tightest alignment of the widest tile, not
    # a pow2 blow-up (that padding is what the layout exists to remove)
    assert widths[-1] == choose_k(int(k_raw.max()))


def _check_roundtrip(prob, p, row_batches=1):
    uni = make_sparse_grid_data(prob, p, row_batches)
    buck = make_bucketed_grid_data(prob, p, row_batches)
    assert (buck.p, buck.mb, buck.db) == (uni.p, uni.mb, uni.db)
    for field in ("yg", "row_nnz_g", "col_nnz", "row_valid",
                  "tile_col_nnz_g", "tile_row_nnz_g"):
        np.testing.assert_allclose(np.asarray(getattr(buck, field)),
                                   np.asarray(getattr(uni, field)),
                                   err_msg=field)
    np.testing.assert_array_equal(buck.k_per_tile, uni.k_per_tile)
    for q in range(p):
        for b in range(p):
            t = buck.tile(q, b)
            t_u = SparseTile(uni.cols_g[q, b], uni.vals_g[q, b], None,
                             uni.db).toarray()
            np.testing.assert_allclose(t.toarray(), t_u,
                                       err_msg=f"tile ({q}, {b})")
            # flat chunk view round-trip: the offset table reassembles the
            # tile's exact (mb, K_bucket) rectangle, chunk for chunk
            fc, fv = buck.flat_tile(q, b)
            np.testing.assert_array_equal(fc, np.asarray(t.cols),
                                          err_msg=f"flat cols ({q}, {b})")
            np.testing.assert_array_equal(fv, np.asarray(t.vals),
                                          err_msg=f"flat vals ({q}, {b})")
    # the ragged grid never exceeds the uniform one's packed-byte budget
    # (device payload = flat view + index maps + chunk tables)
    maps = buck.bucket_id.nbytes + buck.bucket_pos.nbytes \
        + buck.chunk_lut.nbytes + buck.chunk_cnt.nbytes
    assert grid_nbytes(buck) <= grid_nbytes(uni) + maps
    assert packed_bytes_per_step(buck) <= packed_bytes_per_step(uni)


@pytest.mark.parametrize("p,row_batches", [(2, 1), (4, 2), (3, 3)])
def test_bucketed_roundtrips_deterministic(p, row_batches):
    _check_roundtrip(_skewed(m=75, d=41, seed=p), p, row_batches)


@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=0, max_value=2**31 - 1))
def test_bucketed_roundtrip_property(seed):
    """Hypothesis form: bucketed -> dense == uniform -> dense for random
    shapes/densities/skews, including shards that are pure padding."""
    rng = np.random.default_rng(seed)
    m = int(rng.integers(3, 80))
    d = int(rng.integers(8, 70))
    p = int(rng.integers(2, 5))
    density = float(rng.uniform(0.02, 0.5))
    alpha = float(rng.uniform(0.0, 2.0))
    prob = _skewed(m=m, d=d, density=density, alpha=alpha, seed=seed % 997)
    _check_roundtrip(prob, p)


# ------------------------------------------------------------- trajectory --


@pytest.mark.parametrize("loss,reg", LOSS_REG_PAIRS)
def test_bucketed_matches_sparse_trajectory(loss, reg):
    """PR acceptance gate: the bucketed backend's trajectory equals
    sparse_jnp to <= 1e-5 on every loss/regularizer pair (skewed data, so
    several K-buckets really exist)."""
    prob = _skewed(m=120, d=60, loss=loss, reg=reg, seed=1)
    w1, a1, h1 = run_dso_grid(prob, p=2, epochs=4, eta0=0.5, impl="sparse")
    w2, a2, h2 = run_dso_grid(prob, p=2, epochs=4, eta0=0.5,
                              impl="sparse_bucketed_jnp")
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), atol=1e-5,
                               err_msg=f"{loss}/{reg} w")
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a2), atol=1e-5,
                               err_msg=f"{loss}/{reg} alpha")
    assert abs(h1[-1]["primal"] - h2[-1]["primal"]) < 1e-4


def test_bucketed_pallas_matches_jnp_with_row_batches():
    prob = _skewed(m=120, d=90, density=0.2, seed=2)
    w1, a1, _ = run_dso_grid(prob, p=2, epochs=2, eta0=0.5, row_batches=3,
                             impl="sparse_bucketed_jnp")
    w2, a2, _ = run_dso_grid(prob, p=2, epochs=2, eta0=0.5, row_batches=3,
                             impl="sparse_bucketed_pallas")
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a2), atol=1e-5)


# -------------------------------------------------------------- one-kernel --

# problem shapes whose bucketed tiling lands on exactly 1..4 K-buckets
# (verified by the assert in _bucket_problem)
_N_BUCKET_PROBLEMS = {
    1: dict(p=2, m=64, d=32, density=0.3, alpha=0.0),
    2: dict(p=2, m=96, d=64, density=0.15, alpha=1.0),
    3: dict(p=4, m=96, d=128, density=0.3, alpha=2.0),
    4: dict(p=4, m=96, d=128, density=0.4, alpha=2.5),
}


def _bucket_problem(n_buckets, loss="hinge", reg="l2", row_batches=1):
    cfg = dict(_N_BUCKET_PROBLEMS[n_buckets])
    p = cfg.pop("p")
    prob = make_skewed_classification(loss=loss, reg=reg, lam=1e-3, seed=0,
                                      **cfg)
    data = make_bucketed_grid_data(prob, p, row_batches)
    assert len(data.bucket_ks) == n_buckets, data.bucket_ks
    return prob, p


def _run_backend(prob, backend, p, schedule="cyclic", row_batches=1):
    res = solve(prob, backend=backend, schedule=schedule, p=p, epochs=2,
                eta0=0.5, row_batches=row_batches, seed=2)
    return np.asarray(res.w), np.asarray(res.alpha)


def _assert_onekernel_identity(prob, p, schedule="cyclic", row_batches=1):
    """The trajectory gate: one-kernel Pallas == flat jnp BITWISE (shared
    staged math), and both within 1e-5 of the uniform-K layout
    (``sparse_jnp``: the same math at the grid's max width — f32
    reduction order may differ)."""
    kw = dict(p=p, schedule=schedule, row_batches=row_batches)
    w_jnp, a_jnp = _run_backend(prob, "sparse_bucketed_jnp", **kw)
    w_pal, a_pal = _run_backend(prob, "sparse_bucketed_pallas", **kw)
    np.testing.assert_array_equal(w_pal, w_jnp)
    np.testing.assert_array_equal(a_pal, a_jnp)
    w_uni, a_uni = _run_backend(prob, "sparse_jnp", **kw)
    np.testing.assert_allclose(w_pal, w_uni, atol=1e-5)
    np.testing.assert_allclose(a_pal, a_uni, atol=1e-5)


@pytest.mark.parametrize("loss,reg", LOSS_REG_PAIRS)
def test_onekernel_bit_identity_every_loss_reg(loss, reg):
    prob, p = _bucket_problem(3, loss=loss, reg=reg)
    _assert_onekernel_identity(prob, p)


@pytest.mark.parametrize("n_buckets", [1, 2, 3, 4])
@pytest.mark.parametrize("schedule", ["cyclic", "lpt"])
def test_onekernel_bit_identity_buckets_and_schedules(n_buckets, schedule):
    prob, p = _bucket_problem(n_buckets, row_batches=2)
    _assert_onekernel_identity(prob, p, schedule=schedule, row_batches=2)


def test_bucketed_block_step_matches_ref_oracle():
    """ops.dso_bucketed_block_step (one-kernel launch) and its jnp twin
    against the *independent* ref oracle, which reassembles the tile at
    its exact bucket width from the offset table and runs the plain
    uniform-K sparse scan — no staging, no max-width padding."""
    from repro.kernels import dso_sparse, ref
    from repro.sparse import make_bucketed_grid_data as _mk
    prob, p = _bucket_problem(3)
    data = _mk(prob, p, 2)
    q, b = 1, 2
    mb, db = data.mb, data.db
    rng = np.random.default_rng(3)
    args = (jnp.asarray(data.cols_fl[q]), jnp.asarray(data.vals_fl[q]),
            jnp.asarray(data.chunk_lut[q, b]),
            jnp.asarray(data.chunk_cnt[q, b]),
            jnp.asarray(data.yg[q]),
            jnp.asarray(rng.normal(0, 0.1, db).astype(np.float32)),
            jnp.asarray(rng.random(mb).astype(np.float32)),
            jnp.asarray(rng.random(db).astype(np.float32)),
            jnp.asarray(rng.random(mb).astype(np.float32)))
    stats = (jnp.asarray(data.tile_row_nnz_g[q, b]),
             jnp.asarray(data.tile_col_nnz_g[q, :, b * db:(b + 1) * db]),
             jnp.asarray(data.row_nnz_g[q]),
             jnp.asarray(data.col_nnz[b * db:(b + 1) * db]))
    scalars = jnp.asarray([0.5, 1e-3, prob.m, -10.0, 10.0], jnp.float32)
    kw = dict(row_batches=2, loss_name="hinge", reg_name="l2")
    got = ops.dso_bucketed_block_step(*args, *stats, scalars, **kw)
    twin = dso_sparse.dso_bucketed_block_step_jnp(*args, *stats, scalars,
                                                  **kw)
    want = ref.dso_bucketed_block_step_ref(
        *args, stats[2], stats[3], scalars, **kw)
    for g, t, r in zip(got, twin, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(t))
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=1e-5)


def test_interpret_follows_platform_only(monkeypatch):
    """``interpret=None`` resolves from the platform the computation runs
    on and from nothing else: the environment cannot force the interpreter
    onto a TPU, an explicit ``interpret=`` argument always wins, and a
    ``jax.default_device`` block counts as the platform."""
    import jax
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    assert ops._resolve_interpret(None) is False        # compiled on TPU
    assert ops._resolve_interpret(True) is True         # explicit arg wins
    monkeypatch.setattr(ops, "_on_tpu", lambda: False)
    assert ops._resolve_interpret(None) is True
    assert ops._resolve_interpret(False) is False
    monkeypatch.undo()
    with jax.default_device(jax.devices("cpu")[0]):
        assert ops._platform() == "cpu"
        assert ops._resolve_interpret(None) is True
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    assert ops._platform() == "tpu"
    assert ops._resolve_interpret(None) is False
    with jax.default_device("cpu"):                     # platform string
        assert ops._resolve_interpret(None) is True


def test_mosaic_probe_cached_per_platform(monkeypatch):
    """The Mosaic scatter/gather probe verdict is cached per *platform
    name*: switching the default backend re-probes instead of serving the
    other platform's verdict."""
    ops._mosaic_sparse_gather_error.cache_clear()
    r1 = ops.mosaic_sparse_gather_error()
    assert ops._mosaic_sparse_gather_error.cache_info().currsize == 1
    assert ops.mosaic_sparse_gather_error() == r1       # cache hit
    assert ops._mosaic_sparse_gather_error.cache_info().hits >= 1
    calls = []
    monkeypatch.setattr(
        ops, "_mosaic_sparse_gather_error",
        lambda platform: calls.append(platform) or f"probed:{platform}")
    monkeypatch.setattr(ops.jax, "default_backend",
                        lambda: "other-platform")
    assert ops.mosaic_sparse_gather_error() == "probed:other-platform"
    assert calls == ["other-platform"]                  # keyed on platform
    monkeypatch.undo()
    ops._mosaic_sparse_gather_error.cache_clear()


# -------------------------------------------------------------- schedules --


def _assert_valid_epoch_schedule(perms, p):
    perms = np.asarray(perms)
    assert perms.shape[1:] == (p, p)
    want = np.arange(p)
    for e in range(perms.shape[0]):
        for r in range(p):
            # a permutation per inner iteration: never two workers on the
            # same block (Lemma 2's only requirement)
            np.testing.assert_array_equal(np.sort(perms[e, r]), want,
                                          err_msg=f"epoch {e} iter {r}")
        for q in range(p):
            # full coverage: every worker sees every block once per epoch
            np.testing.assert_array_equal(np.sort(perms[e, :, q]), want,
                                          err_msg=f"epoch {e} worker {q}")


@pytest.mark.parametrize("p", [2, 3, 4, 7])
def test_lpt_schedule_is_valid_permutation_array(p):
    rng = np.random.default_rng(p)
    cost = rng.pareto(1.0, size=(p, p)) * 100 + 1
    sched = get_schedule("lpt")
    key = jnp.zeros(2, jnp.uint32)
    _, perms = sched.draw(key, 0, 3, p, tile_nnz=cost)
    assert perms.shape == (3, p, p)
    _assert_valid_epoch_schedule(perms, p)


def test_lpt_balances_skewed_costs_better_than_cyclic():
    """Hot tiles in distinct rows AND distinct block columns whose
    (block - worker) offsets differ: cyclic's fixed diagonal spreads them
    over three rounds (each round inherits one straggler), while LPT
    co-schedules all four in ONE inner iteration — the summed per-round
    max, what a bulk-synchronous epoch actually waits on, drops toward
    one hot round plus mean-cost rounds."""
    p = 4
    cost = np.ones((p, p))
    hot = {0: 0, 1: 2, 2: 3, 3: 1}     # worker -> its hot block
    for q, b in hot.items():
        cost[q, b] = 100.0             # offsets (b - q) % p = 0, 1, 1, 2
    lpt = lpt_latin_square(cost)
    _assert_valid_epoch_schedule(lpt[None], p)
    cyc = (np.arange(p)[:, None] + np.arange(p)[None, :]) % p

    def epoch_cost(perm):
        return sum(max(cost[q, perm[r, q]] for q in range(p))
                   for r in range(p))

    # all four hot tiles in ONE inner iteration: one 100-round + (p-1)
    # 1-rounds; cyclic pays a straggler in every round whose offset class
    # holds a hot tile (three of them here)
    assert epoch_cost(lpt) == 100 + (p - 1)
    assert epoch_cost(cyc) == 3 * 100 + 1
    assert epoch_cost(lpt) < epoch_cost(cyc)


def test_lpt_without_costs_raises():
    sched = get_schedule("lpt")
    with pytest.raises(ValueError, match="tile_nnz"):
        sched.draw(jnp.zeros(2, jnp.uint32), 0, 1, 4)


def test_lpt_through_driver_matches_fixed_replay():
    """The driver feeds the per-tile nnz into the balanced schedule; the
    same Latin square replayed through fixed_schedule is bit-identical."""
    prob = _skewed(m=64, d=48, seed=5)
    res = solve(prob, backend="sparse_jnp", schedule="lpt", p=4, epochs=3,
                eta0=0.5)
    data = make_sparse_grid_data(prob, 4)
    sq = lpt_latin_square(np.asarray(data.tile_row_nnz_g).sum(-1))
    ref = solve(prob, backend="sparse_jnp",
                schedule=fixed_schedule(np.broadcast_to(sq, (3, 4, 4))),
                p=4, epochs=3, eta0=0.5)
    np.testing.assert_array_equal(np.asarray(res.w), np.asarray(ref.w))
    np.testing.assert_array_equal(np.asarray(res.alpha),
                                  np.asarray(ref.alpha))


# ------------------------------------------------------------------- auto --


def test_auto_upgrades_to_bucketed_on_skew():
    assert resolve_backend("auto", 0.01).name == "sparse_jnp"
    assert resolve_backend("auto", 0.01, k_skew=1.0).name == "sparse_jnp"
    assert resolve_backend(
        "auto", 0.01, k_skew=BUCKET_SKEW_THRESHOLD).name \
        == "sparse_bucketed_jnp"
    # skew never flips the dense side of the density threshold
    assert resolve_backend("auto", 0.5, k_skew=100.0).name == "dense_jnp"
    # pre-built bucketed grids resolve kernel selectors to their layout
    assert resolve_backend_for_layout("auto", "bucketed", 512).name \
        == "sparse_bucketed_jnp"
    assert resolve_backend_for_layout("pallas", "bucketed", 512).name \
        == "sparse_bucketed_pallas"


def test_auto_skew_probe_end_to_end():
    """A power-law problem in the sparse regime really crosses the
    threshold, and solve(impl='auto') runs the bucketed layout on it (its
    trajectory equals the explicit bucketed backend's bit-for-bit)."""
    prob = _skewed(m=96, d=256, density=0.02, alpha=1.6, seed=7)
    skew = tile_k_skew(problem_k_per_tile(prob, 4))
    assert skew >= BUCKET_SKEW_THRESHOLD
    res = solve(prob, backend="auto", p=4, epochs=2, eta0=0.5)
    ref = solve(prob, backend="sparse_bucketed_jnp", p=4, epochs=2,
                eta0=0.5)
    np.testing.assert_array_equal(np.asarray(res.w), np.asarray(ref.w))
    np.testing.assert_array_equal(np.asarray(res.alpha),
                                  np.asarray(ref.alpha))


def _write_libsvm(path, X):
    with open(path, "w") as f:
        for i in range(X.shape[0]):
            cols = np.nonzero(X[i])[0]
            feats = " ".join(f"{j + 1}:{X[i, j]:.6g}" for j in cols)
            f.write(f"+1 {feats}\n" if i % 2 else f"-1 {feats}\n")


def test_ingest_records_k_per_tile_in_pass_one():
    """Pass 1 of the streaming ingester records the same (p, p) per-tile
    widths as the grid tiler, so impl='auto' can run the skew decision
    without a third pass over the data."""
    prob = _skewed(m=60, d=40, density=0.2, alpha=1.4, seed=9)
    X = np.asarray(prob.X)
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "skewed.libsvm")
        _write_libsvm(path, X)
        stats = scan_libsvm(path, n_features=40, p=4)
        csr, y, stats2 = ingest_libsvm(path, n_features=40, p=4,
                                       return_stats=True)
    grid = sparse_grid_from_csr(csr, y, 4)
    np.testing.assert_array_equal(stats.k_per_tile, grid.k_per_tile)
    np.testing.assert_array_equal(stats2.k_per_tile, grid.k_per_tile)
    assert tile_k_skew(stats.k_per_tile) == tile_k_skew(grid.k_per_tile)


def test_scan_k_per_tile_requires_n_features():
    with pytest.raises(ValueError, match="n_features"):
        scan_libsvm(["+1 1:1.0"], p=2)
    # out-of-range index must fail loudly, not fold into the wrong tile
    with pytest.raises(ValueError, match="exceeds"):
        scan_libsvm(["+1 7:1.0"], n_features=3, p=2)


# ---------------------------------------------- kernels: interpret default --


def test_sparse_block_step_interpret_default_pins_to_backend(monkeypatch):
    """The sparse block step resolves interpret=None through the same
    backend auto-detection as the dense ops (ROADMAP Mosaic-native seam,
    step 1): interpreter on this CPU container, compiled on a real TPU."""
    assert ops._on_tpu() is False          # this container is CPU
    assert ops._resolve_interpret(None) is True
    assert ops._resolve_interpret(False) is False
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    assert ops._resolve_interpret(None) is False
    monkeypatch.undo()

    M, db, rbs = 32, 24, 2
    rng = np.random.default_rng(0)
    X = (rng.random((M, db)) < 0.3) * rng.normal(0, 1, (M, db))
    tile = SparseTile.from_dense(X.astype(np.float32))
    y = np.where(rng.random(M) < 0.5, 1.0, -1.0).astype(np.float32)
    args = (tile.cols, tile.vals, jnp.asarray(y),
            jnp.zeros(db), jnp.asarray(y * 0.3), jnp.zeros(db),
            jnp.zeros(M), jnp.asarray((X != 0).sum(1).astype(np.float32)),
            jnp.asarray(np.stack([(X[s * (M // rbs):(s + 1) * (M // rbs)]
                                   != 0).sum(0) for s in range(rbs)])
                        .astype(np.float32)),
            jnp.maximum(jnp.asarray((X != 0).sum(1).astype(np.float32)), 1),
            jnp.ones(db),
            jnp.asarray([0.5, 1e-3, M, -31.6, 31.6], jnp.float32))
    kw = dict(row_batches=rbs, loss_name="hinge", reg_name="l2")
    default = ops.dso_sparse_block_step(*args, **kw)          # None
    explicit = ops.dso_sparse_block_step(*args, interpret=True, **kw)
    for a, b in zip(default, explicit):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------- sharded --


SHARD_SCRIPT = textwrap.dedent("""
    import numpy as np
    from repro.data.synthetic import make_skewed_classification
    from repro.engine import solve
    from repro.core.dso_dist import run_dso_sharded
    prob = make_skewed_classification(m=96, d=48, density=0.2, alpha=1.3,
                                      loss='hinge', lam=1e-3, seed=0)
    for backend in ('sparse_bucketed_jnp', 'sparse_bucketed_pallas'):
        for schedule in ('cyclic', 'lpt'):
            res = solve(prob, backend=backend, schedule=schedule, p=4,
                        epochs=2, eta0=0.5, seed=3)
            w2, a2, _ = run_dso_sharded(prob, epochs=2, eta0=0.5,
                                        impl=backend, schedule=schedule,
                                        seed=3)
            assert np.abs(np.asarray(res.w) - np.asarray(w2)).max() < 1e-5, \\
                (backend, schedule)
            assert np.abs(np.asarray(res.alpha) - np.asarray(a2)).max() \\
                < 1e-5, (backend, schedule)
    print('BUCKETED_MATCH')
""")


def test_bucketed_sharded_matches_grid_cyclic_and_lpt():
    """grid == sharded for both bucketed backends under the ring (cyclic)
    and the load-balanced (lpt, all-gather) schedule — inside shard_map
    the bucket lax.switch runs ONE branch per device, so this also pins
    that the per-device dispatch stays correct.  Subprocess with 4 host
    devices like the other shard_map tests."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", SHARD_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "BUCKETED_MATCH" in out.stdout
