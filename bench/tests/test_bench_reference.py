"""The plain reference against the program, and the control against the
limits, at sizes a test run holds."""

import glob
import json
import os

import numpy as np
import pytest

from bench import check, gen
from bench.reference import Reference

HERE = os.path.dirname(os.path.abspath(__file__))
LIMITS = sorted(glob.glob(os.path.join(HERE, "..", "limits", "*.json")))
TRAFFIC = {"eta0": 0.5, "eval_every": 1}


def _program(csr, loss, alpha0, epochs, layout):
    import jax

    from repro.engine import solve
    from repro.sparse.format import (CSRMatrix, bucketed_grid_from_csr,
                                     sparse_grid_from_csr)

    build = {"sparse": sparse_grid_from_csr,
             "bucketed": bucketed_grid_from_csr}[layout]
    data = build(CSRMatrix(csr.indptr, csr.indices, csr.values,
                           (csr.m, csr.d)), csr.y, 4)
    out = []
    solve(data, backend="auto", p=4, epochs=epochs, eta0=0.5, alpha0=alpha0,
          eval_every=1, loss_name=loss, reg_name="l2", lam=1e-3, m=csr.m,
          d=csr.d, eval_hook=lambda t, w, a: out.append(
              (np.asarray(jax.block_until_ready(w)), np.asarray(a))))
    return out


@pytest.mark.parametrize("loss,alpha0,layout", [
    ("hinge", 0.0, "bucketed"), ("logistic", 0.0005, "sparse")])
def test_reference_follows_program(loss, alpha0, layout):
    csr = gen.powerlaw_csr(403, 257, 9, 1.1, 21)
    got = _program(csr, loss, alpha0, 6, layout)
    ref = Reference(csr, loss=loss, lam=1e-3, p=4, eta0=0.5, alpha0=alpha0)
    for w, a in got:
        ref.epoch()
        assert check.rel_err(w, ref.w) < 1e-5
        assert check.rel_err(a, ref.alpha) < 1e-5


@pytest.mark.parametrize("limits", LIMITS, ids=os.path.basename)
@pytest.mark.parametrize("loss,alpha0", [("hinge", 0.0),
                                         ("logistic", 0.0005)])
def test_control_fails_the_limits(limits, loss, alpha0):
    with open(limits) as f:
        lim = json.load(f)
    csr = gen.powerlaw_csr(2000, 1500, 20, 1.1, 5)
    cfg = dict(loss=loss, lam=1e-4, p=4, alpha0=alpha0)
    got = check.control_readings(csr, cfg, TRAFFIC, 8)
    assert not all(ok for *_, ok in check.judge(got, lim)), got


@pytest.mark.parametrize("loss,alpha0", [("hinge", 0.0),
                                         ("logistic", 0.0005)])
def test_reference_threads_match_one_tile_at_a_time(loss, alpha0):
    csr = gen.powerlaw_csr(203, 150, 9, 1.0, 3)
    kw = dict(loss=loss, lam=1e-3, p=4, eta0=0.5, alpha0=alpha0)
    threaded, serial = Reference(csr, **kw), Reference(csr, **kw)
    threaded.run_to(3)
    for _ in range(3):
        for step in range(4):
            for q in range(4):
                serial._tile_step(serial.tiles[q, (q + step) % 4])
    assert np.array_equal(threaded.w, serial.w)
    assert np.array_equal(threaded.alpha, serial.alpha)
