"""Engine-layer coverage: backend registry, schedule layer, driver.

Five groups:

  1. registry — ValueError (listing the registry) for unknown backends /
     schedules at every public entry point; ``impl="auto"`` in ShardedDSO.
  2. backend x schedule matrix — every registered backend matches the
     dense_jnp trajectory to <= 1e-5 under both the cyclic and the random
     schedule (the engine acceptance gate).
  3. Lemma 2 — the vmapped (parallel) epoch under an ARBITRARY
     per-inner-iteration permutation schedule equals an equivalent serial
     sequence of updates, replayed one processor at a time in any order
     (deterministic + hypothesis property forms); and the cyclic schedule
     expressed as explicit permutations through the Schedule layer
     reproduces the native cyclic trajectory exactly.
  4. evaluation — the jitted chunked CSR matvec hook equals the dense
     objective, and threads through ``run_dso_grid_from_data``.
  5. driver ergonomics — the ragged ``epochs % eval_every`` warning fires
     once with a divisor suggestion.

Note (recorded in EXPERIMENTS.md / dso_async docstring): trajectories of
DIFFERENT schedules do not coincide — random permutations lack the cyclic
schedule's per-epoch coverage guarantee — so Lemma 2 is tested as
serializability of a FIXED schedule, not cross-schedule equality.
"""

import os
import subprocess
import sys
import textwrap
import warnings

import numpy as np
import jax.numpy as jnp
import pytest

from _hypothesis_compat import given, settings, st
from repro.core.dso import run_dso_grid, run_dso_grid_from_data
from repro.core.dso_async import run_dso_random
from repro.core.dso_dist import ShardedDSO
from repro.data.synthetic import make_classification
from repro.engine import (DSOState, cyclic_perms, fixed_schedule,
                          gather_alpha, gather_w, get_backend, get_schedule,
                          init_state_data, inner_iteration,
                          make_csr_primal_eval, make_grid_data, prob_meta,
                          registered_backends, run_epochs, solve)
from repro.engine.data import as_tile_data
from repro.sparse.format import CSRMatrix

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ALL_BACKENDS = ("dense_jnp", "dense_pallas_fused", "dense_pallas_block",
                "sparse_jnp", "sparse_pallas", "sparse_bucketed_jnp",
                "sparse_bucketed_pallas")


def _prob(m=64, d=40, density=0.2, seed=0, loss="hinge"):
    return make_classification(m=m, d=d, density=density, loss=loss,
                               lam=1e-3, seed=seed)


# ---------------------------------------------------------------- registry --


def test_backend_registry_names():
    assert registered_backends() == ALL_BACKENDS
    for name in ALL_BACKENDS:
        assert get_backend(name).name == name


def test_unknown_backend_raises_valueerror_everywhere():
    prob = _prob(m=12, d=8)
    with pytest.raises(ValueError, match="dense_jnp"):
        get_backend("nope")
    with pytest.raises(ValueError, match="registered backends"):
        run_dso_grid(prob, p=2, epochs=1, impl="bogus")
    with pytest.raises(ValueError, match="registered backends"):
        solve(prob, backend="bogus", p=2, epochs=1)
    with pytest.raises(ValueError, match="registered backends"):
        run_dso_random(prob, p=2, epochs=1, impl="bogus")
    with pytest.raises(ValueError, match="registered backends"):
        ShardedDSO(prob, impl="bogus")
    with pytest.raises(ValueError, match="registered schedules"):
        solve(prob, schedule="bogus", p=2, epochs=1)
    with pytest.raises(ValueError, match="registered schedules"):
        get_schedule("bogus")


def test_layout_mismatch_raises():
    prob = _prob(m=12, d=8)
    data = make_grid_data(prob, 2)
    with pytest.raises(ValueError, match="layout"):
        run_dso_grid_from_data(
            data, loss_name="hinge", reg_name="l2", lam=1e-3, m=12, d=8,
            epochs=1, impl="sparse_jnp")   # dense grid, sparse backend


def test_sharded_accepts_auto_with_density_threshold():
    """impl='auto' picks the layout with the same threshold as
    run_dso_grid (p=1 ring on the single CPU device)."""
    sparse_prob = _prob(m=16, d=128, density=0.02)
    dense_prob = _prob(m=16, d=16, density=0.5)
    assert ShardedDSO(sparse_prob, impl="auto").backend.name == "sparse_jnp"
    assert ShardedDSO(dense_prob, impl="auto").backend.name == "dense_jnp"


# ----------------------------------------------- backend x schedule matrix --


@pytest.mark.parametrize("schedule", ["cyclic", "random"])
@pytest.mark.parametrize("name", ALL_BACKENDS[1:])
def test_backend_schedule_equivalence_matrix(name, schedule):
    """Every registered backend follows the reference backend's trajectory
    (<= 1e-5) under every schedule — layouts and kernels only change the
    arithmetic order, never the update sequence."""
    prob = _prob(m=64, d=48, density=0.2, seed=3)
    kw = dict(schedule=schedule, p=2, epochs=2, eta0=0.5, row_batches=2,
              seed=5)
    ref = solve(prob, backend="dense_jnp", **kw)
    res = solve(prob, backend=name, **kw)
    np.testing.assert_allclose(np.asarray(res.w), np.asarray(ref.w),
                               atol=1e-5, err_msg=f"{name}/{schedule} w")
    np.testing.assert_allclose(np.asarray(res.alpha), np.asarray(ref.alpha),
                               atol=1e-5, err_msg=f"{name}/{schedule} alpha")


def test_random_wrapper_matches_engine_stream():
    """run_dso_random is a thin wrapper: identical trajectory AND RNG
    stream to engine.solve(schedule='random')."""
    prob = _prob(m=48, d=32, seed=1)
    w1, a1, h1 = run_dso_random(prob, p=4, epochs=3, eta0=0.5, seed=9)
    res = solve(prob, backend="dense_jnp", schedule="random", p=4,
                epochs=3, eta0=0.5, seed=9)
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(res.w))
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(res.alpha))
    assert [h["epoch"] for h in h1] == [1, 2, 3]
    assert "saddle" not in h1[-1]   # legacy random history shape


# ------------------------------------------------------ Lemma 2 (schedule) --


def _random_latin_free_perms(rng, n_epochs, p):
    """Arbitrary (n_epochs, p, p) schedule: each inner iteration an
    independent uniform permutation (NO per-processor coverage guarantee)."""
    return np.stack([np.stack([rng.permutation(p) for _ in range(p)])
                     for _ in range(n_epochs)]).astype(np.int32)


def _serial_replay(prob, data, state, perms, eta_t, row_batches=1,
                   reverse=False):
    """The 'equivalent serial sequence of updates' of Lemma 2: the same
    schedule applied one processor at a time (in either order) instead of
    vmapped simultaneously."""
    be = get_backend("dense_jnp")
    meta = prob_meta(prob)
    p = data.p
    w_grid, gw_grid = state.w_grid, state.gw_grid
    alpha, ga = state.alpha, state.ga
    for perm in np.asarray(perms).reshape(-1, p, p):
        for r in range(p):
            order = range(p - 1, -1, -1) if reverse else range(p)
            for q in order:
                b = int(perm[r, q])
                w_b, a_q, gw_b, ga_q = inner_iteration(
                    be, meta, data.col_nnz, b, w_grid[b], gw_grid[b],
                    alpha[q], ga[q], (data.Xg[q],), data.yg[q],
                    data.row_nnz_g[q], data.tile_col_nnz_g[q],
                    data.tile_row_nnz_g[q], eta_t, row_batches)
                w_grid = w_grid.at[b].set(w_b)
                gw_grid = gw_grid.at[b].set(gw_b)
                alpha = alpha.at[q].set(a_q)
                ga = ga.at[q].set(ga_q)
    return w_grid, alpha


def _check_lemma2(seed, p, n_epochs=1):
    prob = _prob(m=8 * p, d=4 * p, density=0.3, seed=seed % 7)
    rng = np.random.default_rng(seed)
    perms = _random_latin_free_perms(rng, n_epochs, p)
    data = make_grid_data(prob, p)
    state = init_state_data(prob.loss_name, data)
    lam, m_f, _, _, _, w_lo, w_hi = prob_meta(prob)
    etas = jnp.full((n_epochs,), jnp.float32(0.5))
    out = run_epochs(
        as_tile_data(data), state, jnp.asarray(perms), etas, lam, m_f,
        w_lo, w_hi, backend="dense_jnp", loss_name=prob.loss_name,
        reg_name=prob.reg_name, use_adagrad=True, row_batches=1, p=p,
        db=data.db)
    state2 = init_state_data(prob.loss_name, data)
    for reverse in (False, True):
        w_ser, a_ser = _serial_replay(prob, data, state2, perms,
                                      jnp.float32(0.5), reverse=reverse)
        np.testing.assert_allclose(np.asarray(out.w_grid),
                                   np.asarray(w_ser), atol=1e-5,
                                   err_msg=f"w reverse={reverse}")
        np.testing.assert_allclose(np.asarray(out.alpha),
                                   np.asarray(a_ser), atol=1e-5,
                                   err_msg=f"alpha reverse={reverse}")


@pytest.mark.parametrize("p", [2, 3])
def test_lemma2_arbitrary_schedule_serializes(p):
    """Deterministic form: one arbitrary-permutation epoch, parallel ==
    both serial replay orders."""
    _check_lemma2(seed=42 + p, p=p, n_epochs=1)


@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=0, max_value=2**31 - 1))
def test_lemma2_property_arbitrary_schedules(seed):
    """Property form (hypothesis): ANY per-inner-iteration permutation
    schedule through the Schedule layer is serializable to <= 1e-5 —
    the exact hypothesis of Lemma 2 at tile granularity."""
    _check_lemma2(seed=seed, p=2 + seed % 2, n_epochs=1)


def test_cyclic_via_schedule_layer_matches_native():
    """sigma_r expressed as an explicit fixed permutation array reproduces
    the native cyclic driver bit-for-bit — the generic schedule path IS
    the cyclic path."""
    prob = _prob(m=48, d=32, seed=2)
    epochs, p = 3, 4
    w1, a1, _ = run_dso_grid(prob, p=p, epochs=epochs, eta0=0.5)
    res = solve(prob, backend="dense_jnp",
                schedule=fixed_schedule(cyclic_perms(epochs, p)),
                p=p, epochs=epochs, eta0=0.5)
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(res.w))
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(res.alpha))


SHARD_SCRIPT = textwrap.dedent("""
    import numpy as np
    from repro.data.synthetic import make_classification
    from repro.engine import solve
    from repro.core.dso_dist import run_dso_sharded
    prob = make_classification(m=96, d=48, density=0.15, loss='hinge',
                               lam=1e-3, seed=0)
    for backend in ('dense_jnp', 'sparse_jnp', 'dense_pallas_block'):
        for schedule in ('cyclic', 'random'):
            res = solve(prob, backend=backend, schedule=schedule, p=4,
                        epochs=2, eta0=0.5, seed=3)
            w2, a2, _ = run_dso_sharded(prob, epochs=2, eta0=0.5,
                                        impl=backend, schedule=schedule,
                                        seed=3)
            assert np.abs(np.asarray(res.w) - np.asarray(w2)).max() < 1e-5, \\
                (backend, schedule)
            assert np.abs(np.asarray(res.alpha) - np.asarray(a2)).max() \\
                < 1e-5, (backend, schedule)
    print('MATRIX_MATCH')
""")


def test_sharded_matches_grid_backend_schedule_matrix():
    """grid == sharded holds for backends x schedules, including the
    NOMAD-style shuffle (all-gather + select instead of the ring).
    Subprocess with 4 host devices, like the other shard_map tests."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", SHARD_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MATRIX_MATCH" in out.stdout


# -------------------------------------------------------------- evaluation --


def test_chunked_csr_eval_matches_dense_objective():
    from repro.core.losses import get_loss
    from repro.core.regularizers import get_regularizer
    prob = _prob(m=50, d=33, density=0.25, seed=4)
    X = np.asarray(prob.X)
    csr = CSRMatrix.from_dense(X)
    rng = np.random.default_rng(0)
    w = rng.normal(0, 0.3, 33).astype(np.float32)
    # chunk far smaller than nnz: exercises the multi-chunk scan + padding
    hook = make_csr_primal_eval(csr, prob.y, prob.lam, "hinge", "l2",
                                chunk_nnz=64)
    got = float(hook.primal(w))
    want = float(prob.lam * np.sum(get_regularizer("l2").value(w))
                 + np.mean(np.asarray(get_loss("hinge").value(
                     jnp.asarray(X @ w), prob.y))))
    assert abs(got - want) < 1e-5
    h = hook(3, w, None)
    assert h["epoch"] == 3 and abs(h["primal"] - want) < 1e-5


def test_out_of_core_eval_loop_through_grid_from_data():
    """run_dso_grid_from_data grows a device-side eval loop: the chunked
    CSR hook records a history without any host-numpy objective."""
    prob = _prob(m=60, d=40, density=0.15, seed=6)
    csr = CSRMatrix.from_dense(np.asarray(prob.X))
    from repro.sparse.format import sparse_grid_from_csr
    data = sparse_grid_from_csr(csr, np.asarray(prob.y), p=2)
    hook = make_csr_primal_eval(csr, prob.y, prob.lam)
    w, alpha, hist = run_dso_grid_from_data(
        data, loss_name="hinge", reg_name="l2", lam=prob.lam, m=60, d=40,
        epochs=4, eta0=0.5, eval_every=2, eval_hook=hook)
    assert [h["epoch"] for h in hist] == [2, 4]
    assert all(np.isfinite(h["primal"]) for h in hist)
    assert hist[-1]["primal"] < 1.0     # beat the trivial P(0) = 1
    # without a hook the legacy (w, alpha) contract is unchanged
    w2, a2 = run_dso_grid_from_data(
        data, loss_name="hinge", reg_name="l2", lam=prob.lam, m=60, d=40,
        epochs=4, eta0=0.5)
    np.testing.assert_allclose(np.asarray(w), np.asarray(w2), atol=1e-6)


# ----------------------------------------------------- ragged-eval warning --


def test_ragged_eval_chunk_warns_once_with_suggestion():
    prob = _prob(m=24, d=16, seed=8)
    # 7 % 3 != 0 -> ragged tail; largest divisor of 7 below 3 is 1
    with pytest.warns(RuntimeWarning,
                      match=r"eval_every=3.*e\.g\. eval_every=1"):
        run_dso_grid(prob, p=2, epochs=7, eta0=0.5, eval_every=3)
    # identical shape again: warned once per (epochs, eval_every)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        run_dso_grid(prob, p=2, epochs=7, eta0=0.5, eval_every=3)
    assert not [w for w in rec if issubclass(w.category, RuntimeWarning)
                and "eval_every" in str(w.message)]
    # divides evenly: never warns
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        run_dso_grid(prob, p=2, epochs=6, eta0=0.5, eval_every=3)
    assert not [w for w in rec if issubclass(w.category, RuntimeWarning)
                and "eval_every" in str(w.message)]
