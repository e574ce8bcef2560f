"""Block-sparse subsystem coverage.

Four layers, each pinned against the dense path that the rest of the suite
already trusts:

  1. format     — CSR round-trips, ELL tile packing, and the grid tiler
                  reproducing ``make_grid_data``'s layout + statistics.
  2. kernels    — the one-hot sparse Pallas kernel == the jnp sparse
                  oracle == the dense block-step oracle.
  3. trajectory — ``run_dso_grid(impl='sparse')`` equals the dense
                  trajectory to <= 1e-5 across every loss/regularizer pair
                  (the PR acceptance gate), and sharded == grid on the
                  sparse path (subprocess with 4 host devices).
  4. ingest     — the streaming two-pass libsvm ingester at paper scale
                  (1e5 rows, density 0.005) with no dense materialization.
"""

import os
import subprocess
import sys
import tempfile
import textwrap

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.dso import (make_grid_data, resolve_impl, run_dso_grid,
                            run_dso_grid_from_data)
from repro.data.synthetic import make_classification, make_regression
from repro.kernels import ops
from repro.kernels.ref import dso_block_step_ref, dso_sparse_block_step_ref
from repro.sparse import (CSRMatrix, SPARSE_DENSITY_THRESHOLD, SparseTile,
                          choose_k, csr_primal_objective, grid_nbytes,
                          ingest_libsvm, make_sparse_grid_data, scan_libsvm,
                          sparse_grid_from_csr)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LOSS_REG_PAIRS = [("hinge", "l2"), ("hinge", "l1"), ("logistic", "l2"),
                  ("logistic", "l1"), ("square", "l2"), ("square", "l1")]


def _problem(loss, reg, seed=0):
    if loss == "square":
        return make_regression(m=120, d=60, density=0.15, seed=seed,
                               reg=reg)
    return make_classification(m=120, d=60, density=0.15, loss=loss,
                               lam=1e-3, seed=seed, reg=reg)


# ---------------------------------------------------------------- format --


def test_csr_roundtrip_and_matvecs():
    prob = make_classification(m=50, d=33, density=0.2, seed=3)
    X = np.asarray(prob.X)
    csr = CSRMatrix.from_dense(X)
    np.testing.assert_allclose(csr.toarray(), X)
    w = np.random.default_rng(0).normal(size=33).astype(np.float32)
    a = np.random.default_rng(1).normal(size=50).astype(np.float32)
    np.testing.assert_allclose(csr.matvec(w), X @ w, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(csr.rmatvec(a), X.T @ a, rtol=1e-5,
                               atol=1e-5)
    assert csr.nnz == int((X != 0).sum())


def test_choose_k_alignment():
    assert choose_k(1) == 8 and choose_k(8) == 8 and choose_k(9) == 16
    assert choose_k(51) == 56                  # sublane multiple, not 128
    assert choose_k(51, pow2=True) == 64
    assert choose_k(0) == 8                    # empty tile still addressable


def test_sparse_tile_roundtrip_including_column_zero():
    # a real entry at column 0 must survive the pads-point-at-col-0 scheme
    X = np.zeros((8, 16), np.float32)
    X[0, 0] = 3.0
    X[0, 5] = -1.0
    X[3, 0] = 2.0
    tile = SparseTile.from_dense(X)
    np.testing.assert_allclose(tile.toarray(), X)
    assert tile.K == 8


@pytest.mark.parametrize("p,row_batches", [(2, 1), (4, 2), (3, 3)])
def test_grid_tiler_matches_dense_grid(p, row_batches):
    """The CSR tiler must reproduce make_grid_data's layout and every
    scaling statistic — this is what makes the trajectories identical."""
    prob = make_classification(m=75, d=41, density=0.18, seed=p)
    dense = make_grid_data(prob, p, row_batches)
    sp = make_sparse_grid_data(prob, p, row_batches)
    assert (sp.p, sp.mb, sp.db) == (dense.p, dense.mb, dense.db)
    for field in ("yg", "row_nnz_g", "col_nnz", "row_valid",
                  "tile_col_nnz_g", "tile_row_nnz_g"):
        np.testing.assert_allclose(np.asarray(getattr(sp, field)),
                                   np.asarray(getattr(dense, field)),
                                   err_msg=field)
    Xg = np.asarray(dense.Xg)
    for q in range(p):
        for b in range(p):
            tile = SparseTile(sp.cols_g[q, b], sp.vals_g[q, b], None,
                              sp.db).toarray()
            np.testing.assert_allclose(
                tile, Xg[q][:, b * sp.db:(b + 1) * sp.db],
                err_msg=f"tile ({q}, {b})")


def test_csr_from_shards_counts_all_rows():
    X = np.arange(20, dtype=np.float32).reshape(5, 4)
    full = CSRMatrix.from_dense(X)
    shards = [CSRMatrix.from_dense(X[:3]), CSRMatrix.from_dense(X[3:])]
    joined = CSRMatrix.from_shards(shards, d=4)
    assert joined.shape == (5, 4)
    np.testing.assert_array_equal(joined.indptr, full.indptr)
    np.testing.assert_allclose(joined.toarray(), full.toarray())


def test_tiler_handles_shard_entirely_in_padding():
    """m so small that a trailing processor shard is pure padding: the
    tiler must not index indptr past the last real row, and the sparse
    trajectory must still match the dense one."""
    prob = make_classification(m=5, d=12, density=0.4, seed=0)
    dense = make_grid_data(prob, 4)
    sp = make_sparse_grid_data(prob, 4)     # mb=2: shard q=3 starts at row 6
    Xg = np.asarray(dense.Xg)
    for q in range(4):
        for b in range(4):
            tile = SparseTile(sp.cols_g[q, b], sp.vals_g[q, b], None,
                              sp.db).toarray()
            np.testing.assert_allclose(
                tile, Xg[q][:, b * sp.db:(b + 1) * sp.db],
                err_msg=f"tile ({q}, {b})")
    w1, a1, _ = run_dso_grid(prob, p=4, epochs=2, eta0=0.5, impl="jnp")
    w2, a2, _ = run_dso_grid(prob, p=4, epochs=2, eta0=0.5, impl="sparse")
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a2), atol=1e-5)


def test_grid_memory_is_nnz_proportional():
    prob = make_classification(m=256, d=512, density=0.02, seed=0)
    sp = make_sparse_grid_data(prob, 4)
    dense_bytes = 4 * 256 * 512
    assert grid_nbytes(sp) < dense_bytes / 4


# --------------------------------------------------------------- kernels --


def _block_inputs(M, D, density, seed=0):
    rng = np.random.default_rng(seed)
    X = (rng.random((M, D)) < density).astype(np.float32) * \
        rng.normal(0, 1, (M, D)).astype(np.float32)
    y = np.where(rng.random(M) < 0.5, 1.0, -1.0).astype(np.float32)
    w = rng.normal(0, 0.1, D).astype(np.float32)
    alpha = (y * rng.random(M)).astype(np.float32)
    gw = np.abs(rng.normal(0, 0.01, D)).astype(np.float32)
    ga = np.abs(rng.normal(0, 0.01, M)).astype(np.float32)
    rn = np.maximum((X != 0).sum(1), 1).astype(np.float32)
    cn = np.maximum((X != 0).sum(0), 1).astype(np.float32)
    sc = np.array([0.5, 1e-3, M, -31.6, 31.6], np.float32)
    return X, tuple(jnp.asarray(a) for a in (y, w, alpha, gw, ga, rn, cn,
                                             sc))


def _tile_stats(X, row_batches):
    rb = X.shape[0] // row_batches
    trn = (X != 0).sum(1).astype(np.float32)
    tcn = np.stack([(X[s * rb:(s + 1) * rb] != 0).sum(0)
                    for s in range(row_batches)]).astype(np.float32)
    return jnp.asarray(trn), jnp.asarray(tcn)


@pytest.mark.parametrize("loss,reg", LOSS_REG_PAIRS)
def test_sparse_kernel_matches_oracles(loss, reg):
    """Gather kernel == jnp sparse oracle == dense block-step oracle."""
    M, D, rbs = 96, 80, 4
    X, (y, w, alpha, gw, ga, rn, cn, sc) = _block_inputs(M, D, 0.15, seed=7)
    tile = SparseTile.from_dense(X)
    trn, tcn = _tile_stats(X, rbs)
    kernel = ops.dso_sparse_block_step(
        tile.cols, tile.vals, y, w, alpha, gw, ga, trn, tcn, rn, cn, sc,
        row_batches=rbs, loss_name=loss, reg_name=reg, interpret=True)
    sparse_ref = dso_sparse_block_step_ref(
        tile.cols, tile.vals, y, w, alpha, gw, ga, rn, cn, sc,
        row_batches=rbs, loss_name=loss, reg_name=reg)
    dense_ref = dso_block_step_ref(
        jnp.asarray(X), y, w, alpha, gw, ga, rn, cn, sc, row_batches=rbs,
        loss_name=loss, reg_name=reg)
    for name, a, b, c in zip("w alpha gw ga".split(), kernel, sparse_ref,
                             dense_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-5, atol=3e-6,
                                   err_msg=f"{loss}/{reg} {name} vs sparse")
        np.testing.assert_allclose(np.asarray(b), np.asarray(c),
                                   rtol=3e-5, atol=3e-6,
                                   err_msg=f"{loss}/{reg} {name} vs dense")


def test_sparse_kernel_truncates_trailing_rows():
    M, D, rbs = 100, 64, 4       # rb = 25 -> last 0 rows... use 102
    M = 102                      # rb = 25, Mk = 100: 2 trailing rows
    X, (y, w, alpha, gw, ga, rn, cn, sc) = _block_inputs(M, D, 0.2, seed=9)
    tile = SparseTile.from_dense(X)
    trn, tcn = _tile_stats(X[: (M // rbs) * rbs], rbs)
    out = ops.dso_sparse_block_step(
        tile.cols, tile.vals, y, w, alpha, gw, ga,
        jnp.asarray((X != 0).sum(1).astype(np.float32)), tcn, rn, cn, sc,
        row_batches=rbs, loss_name="hinge", reg_name="l2", interpret=True)
    np.testing.assert_array_equal(np.asarray(out[1])[100:],
                                  np.asarray(alpha)[100:])
    np.testing.assert_array_equal(np.asarray(out[3])[100:],
                                  np.asarray(ga)[100:])


def test_all_padding_tile_is_noop_on_alpha():
    """A tile with no nonzeros (all ELL pads) must leave the dual gradient
    at zero: alpha only gets projected, w only gets its regularizer pull."""
    M, db = 16, 24
    cols = jnp.zeros((M, 8), jnp.int32)
    vals = jnp.zeros((M, 8), jnp.float32)
    y = jnp.ones(M)
    alpha = y * 0.3
    out = ops.dso_sparse_block_step(
        cols, vals, y, jnp.zeros(db), alpha, jnp.zeros(db),
        jnp.zeros(M), jnp.zeros(M), jnp.zeros((1, db)), jnp.ones(M),
        jnp.ones(db), jnp.asarray([0.5, 1e-3, M, -31.6, 31.6],
                                  jnp.float32),
        row_batches=1, loss_name="hinge", reg_name="l2", interpret=True)
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(alpha))
    np.testing.assert_allclose(np.asarray(out[0]), 0.0)


# ------------------------------------------------------------ trajectory --


@pytest.mark.parametrize("loss,reg", LOSS_REG_PAIRS)
def test_sparse_grid_matches_dense_trajectory(loss, reg):
    """PR acceptance gate: the sparse path's trajectory equals the dense
    one to <= 1e-5 on every loss/regularizer pair."""
    prob = _problem(loss, reg, seed=1)
    w1, a1, h1 = run_dso_grid(prob, p=2, epochs=4, eta0=0.5, impl="jnp")
    w2, a2, h2 = run_dso_grid(prob, p=2, epochs=4, eta0=0.5, impl="sparse")
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), atol=1e-5,
                               err_msg=f"{loss}/{reg} w")
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a2), atol=1e-5,
                               err_msg=f"{loss}/{reg} alpha")
    assert abs(h1[-1]["primal"] - h2[-1]["primal"]) < 1e-4
    if np.isfinite(h1[-1]["gap"]):   # hinge+l1 has no finite dual here
        assert abs(h1[-1]["gap"] - h2[-1]["gap"]) < 1e-4


def _gathered_terms(cols, vals, w):
    """Per-slot ``vals * w[cols]`` terms of ``X w``, gathered by the
    one-hot kernel's own slot-row code (interpreter)."""
    import jax
    from jax.experimental import pallas as pl
    from repro.kernels import dso_sparse

    M, K = cols.shape
    db = w.shape[0]
    h = -(-(-(-db // 128)) // 16) * 16
    w2 = jnp.pad(w, (0, h * 128 - db)).reshape(h, 128)
    w3 = jnp.concatenate(dso_sparse._split3(w2), axis=0).astype(jnp.bfloat16)
    mp = -(-M // 128) * 128
    ct = jnp.pad(cols.T, ((0, 0), (0, mp - M)))
    vt = jnp.pad(vals.T, ((0, 0), (0, mp - M)))

    def kernel(w3_ref, c_ref, v_ref, t_ref):
        for k in range(K):
            val = v_ref[k:k + 1]
            g, _ = dso_sparse._slot_row(w3_ref[...], c_ref[k:k + 1], val,
                                        jnp.zeros_like(val), h)
            t_ref[k:k + 1] = val * g

    terms = pl.pallas_call(
        kernel, grid=(mp // 128,),
        in_specs=[pl.BlockSpec((3 * h, 128), lambda i: (0, 0)),
                  pl.BlockSpec((K, 128), lambda i: (0, i)),
                  pl.BlockSpec((K, 128), lambda i: (0, i))],
        out_specs=pl.BlockSpec((K, 128), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((K, mp), jnp.float32),
        interpret=True)(w3, ct, vt)
    return np.asarray(terms)[:, :M].T


@pytest.mark.parametrize("use_adagrad", [True, False])
@pytest.mark.parametrize("row_batches", [1, 2])
@pytest.mark.parametrize("loss,reg", [("hinge", "l2"), ("logistic", "l2"),
                                      ("square", "l1")])
def test_onehot_kernel_matches_sparse_jnp(loss, reg, row_batches,
                                          use_adagrad):
    """The one-hot kernel against ``sparse_jnp`` on two processors (vmap)
    reading tile 1 of a two-tile payload: 1,100 rows (a partial last row
    chunk), db = 300 (not a multiple of 128), rows with padding slots
    (val 0 at col 0) and a last group of eight slot rows that is padding
    only, under the AdaGrad step and the plain step.  The gathered ``X w``
    terms are bit-exact; w, alpha, gw, ga agree to 1e-6 relative (the sums
    run in another order)."""
    import jax
    from repro.engine.backends import get_backend

    P, M, db, K = 2, 1100, 300, 24
    rng = np.random.default_rng(3)
    nnz = rng.integers(0, 17, (P, 2, M))        # slots past nnz: padding
    cols = np.zeros((P, 2, M, K), np.int32)
    vals = np.zeros((P, 2, M, K), np.float32)
    for idx in np.ndindex(P, 2, M):
        n = nnz[idx]
        cols[idx][:n] = np.sort(rng.choice(db, n, replace=False))
        vals[idx][:n] = rng.normal(0, 0.3, n)
    y = np.where(rng.random((P, M)) < 0.5, 1.0, -1.0).astype(np.float32)
    if loss == "square":
        y = rng.normal(0, 1, (P, M)).astype(np.float32)
    alpha = (y * rng.random((P, M)) * 0.9).astype(np.float32)
    w = rng.normal(0, 0.1, (P, db)).astype(np.float32)
    gw = np.abs(rng.normal(0, 0.01, (P, db))).astype(np.float32)
    ga = np.abs(rng.normal(0, 0.01, (P, M))).astype(np.float32)
    rn = rng.integers(1, 40, (P, M)).astype(np.float32)
    cn = rng.integers(1, 90, (P, db)).astype(np.float32)
    trn = (vals[:, 1] != 0).sum(-1).astype(np.float32)
    rb = M // row_batches
    tcn = np.stack([np.stack([np.bincount(
        cols[q, 1, s * rb:(s + 1) * rb][vals[q, 1, s * rb:(s + 1) * rb]
                                         != 0], minlength=db)
        for s in range(row_batches)]) for q in range(P)]).astype(np.float32)
    meta = (jnp.float32(1e-3), jnp.float32(M * P), loss, reg, use_adagrad,
            jnp.float32(-31.6), jnp.float32(31.6))
    eta = jnp.float32(0.5)
    blk = jnp.ones(P, jnp.int32)
    common = [jnp.asarray(a) for a in (y, w, alpha, gw, ga, rn, cn, trn,
                                       tcn)]

    def run(name, block):
        step = get_backend(name).block_step

        def per_q(block, y, w, a, gw, ga, rn, cn, trn, tcn):
            return step(meta, block, y, w, a, gw, ga, rn, cn, trn, tcn, eta,
                        row_batches)
        return [np.asarray(o) for o in jax.vmap(per_q)(block, *common)]

    got = run("sparse_pallas", (jnp.asarray(cols), jnp.asarray(vals), blk))
    want = run("sparse_jnp", (jnp.asarray(cols[:, 1]),
                              jnp.asarray(vals[:, 1])))
    for name, a, b in zip("w alpha gw ga".split(), got, want):
        assert np.isfinite(a).all(), name
        assert np.abs(a - b).max() <= 1e-6 * np.abs(b).max(), \
            (name, np.abs(a - b).max(), np.abs(b).max())

    terms = _gathered_terms(jnp.asarray(cols[0, 1]),
                            jnp.asarray(vals[0, 1]), jnp.asarray(w[0]))
    np.testing.assert_array_equal(terms, vals[0, 1] * w[0][cols[0, 1]])


def test_sparse_pallas_matches_sparse_jnp_with_row_batches():
    prob = make_classification(m=120, d=90, density=0.2, loss="hinge",
                               lam=1e-3, seed=1)
    w1, a1, _ = run_dso_grid(prob, p=2, epochs=2, eta0=0.5, row_batches=3,
                             impl="sparse")
    w2, a2, _ = run_dso_grid(prob, p=2, epochs=2, eta0=0.5, row_batches=3,
                             impl="sparse_pallas")
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a2), atol=1e-5)


def test_resolve_impl_auto_threshold():
    assert resolve_impl("auto", 0.01) == ("sparse", "jnp")
    assert resolve_impl("auto", SPARSE_DENSITY_THRESHOLD + 0.1) \
        == ("dense", "jnp")
    assert resolve_impl("sparse_pallas", 0.5) == ("sparse", "pallas")
    assert resolve_impl("pallas", 0.001) == ("dense", "pallas")
    with pytest.raises(ValueError, match="registered backends"):
        resolve_impl("nope", 0.1)


SHARD_SCRIPT = textwrap.dedent("""
    import numpy as np
    from repro.data.synthetic import make_classification
    from repro.core.dso import run_dso_grid
    from repro.core.dso_dist import run_dso_sharded
    prob = make_classification(m=300, d=100, density=0.1, loss='hinge',
                               lam=1e-3, seed=0)
    w1, a1, _ = run_dso_grid(prob, p=4, epochs=4, eta0=0.5, impl='sparse')
    w2, a2, _ = run_dso_sharded(prob, epochs=4, eta0=0.5, impl='sparse')
    assert np.abs(np.asarray(w1) - np.asarray(w2)).max() < 1e-5
    assert np.abs(np.asarray(a1) - np.asarray(a2)).max() < 1e-5
    print('MATCH')
""")


def test_sparse_sharded_matches_sparse_grid():
    """grid == sharded equality holds on the sparse path too (Lemma 2
    serializability with the block-ELL resident shards; only w travels).
    Subprocess with 4 host devices, like the dense equivalent."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", SHARD_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MATCH" in out.stdout


# ---------------------------------------------------------------- ingest --


def _write_sparse_libsvm(path, m, d, nnz_per_row, seed=0):
    """Paper-shaped file writer: fixed nnz/row, ascending indices."""
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for _ in range(m):
            cols = np.sort(rng.choice(d, size=nnz_per_row, replace=False))
            lab = 1 if rng.random() < 0.5 else -1
            feats = " ".join(f"{j + 1}:{v:.4g}" for j, v in
                             zip(cols, rng.normal(0, 1, nnz_per_row)))
            f.write(f"{lab} {feats}\n")


def test_ingest_matches_dense_parser():
    from repro.data.libsvm import parse_libsvm
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "small.libsvm")
        _write_sparse_libsvm(path, m=200, d=50, nnz_per_row=5, seed=2)
        with open(path) as f:
            X, y = parse_libsvm(f, n_features=50)
        csr, y2 = ingest_libsvm(path, n_features=50, shard_rows=64)
        np.testing.assert_allclose(csr.toarray(), X, rtol=1e-4, atol=1e-6)
        np.testing.assert_array_equal(y2, y)


def test_ingest_rejects_oversized_index_and_unsorted_rows():
    from repro.sparse.ingest import iter_csr_shards
    with pytest.raises(ValueError, match="exceeds"):
        list(iter_csr_shards(["+1 7:1.0"], n_features=3))
    with pytest.raises(ValueError, match="non-ascending"):
        list(iter_csr_shards(["+1 5:1.0 2:1.0"], n_features=8))


def test_paper_scale_ingest_never_densifies():
    """Acceptance gate: >= 1e5 rows at density <= 0.01, end to end —
    two-pass streaming ingest -> CSR -> block-ELL grid -> one DSO epoch —
    with every allocation nnz-proportional (the dense matrix would be
    m*d*4 = 800 MB; we assert the resident structures stay ~1000x under
    that)."""
    m, d, k = 100_000, 2000, 10          # density 0.005
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "big.libsvm")
        _write_sparse_libsvm(path, m, d, k, seed=5)
        stats = scan_libsvm(path)
        assert stats.n_rows == m and stats.nnz == m * k
        csr, y = ingest_libsvm(path, n_features=d)
    assert csr.shape == (m, d) and csr.nnz == m * k
    dense_bytes = 4 * m * d
    csr_bytes = (csr.indices.nbytes + csr.values.nbytes
                 + csr.indptr.nbytes)
    assert csr_bytes < dense_bytes / 50
    data = sparse_grid_from_csr(csr, y, p=4)
    # ELL pads each tile row to K (max-nnz skew), so the grid is laxer
    # than raw CSR but still an order of magnitude under dense
    assert grid_nbytes(data) < dense_bytes / 10
    w, alpha = run_dso_grid_from_data(
        data, loss_name="hinge", reg_name="l2", lam=1e-4, m=m, d=d,
        epochs=1, eta0=0.5, impl="jnp")
    assert np.all(np.isfinite(np.asarray(w)))
    # one epoch from w=0 must already beat the trivial objective P(0) = 1
    assert csr_primal_objective(csr, y, np.asarray(w), 1e-4) < 1.0
