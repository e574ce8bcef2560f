import numpy as np
import pytest

from bench import gen, objective


@pytest.mark.parametrize("loss", ["hinge", "logistic"])
def test_gap_check_matches_saddle(loss):
    import jax.numpy as jnp

    from repro.core.saddle import duality_gap, make_problem, primal_objective
    from repro.sparse.format import CSRMatrix

    csr = gen.powerlaw_csr(120, 60, 7, 1.1, 11)
    X = CSRMatrix(csr.indptr, csr.indices, csr.values,
                  (csr.m, csr.d)).toarray()
    prob = make_problem(X, csr.y, 1e-3, loss=loss)
    rng = np.random.default_rng(0)
    w = rng.normal(0, 0.3, csr.d).astype(np.float32)
    alpha = objective.project_alpha(
        loss, rng.uniform(-1, 1, csr.m), csr.y).astype(np.float32)
    want = float(duality_gap(prob, jnp.asarray(w), jnp.asarray(alpha))
                 / primal_objective(prob, jnp.asarray(w)))
    host = objective.HostGap(csr, loss, 1e-3)(w, alpha)
    assert host == pytest.approx(want, rel=1e-5)
