"""The comparison that decides ``correct``.

Three numbers, each held to its cell's limit (``bench/limits/<cell>.json``):

* ``w_rel_err``, ``alpha_rel_err``: the largest ||x - x_ref|| / ||x_ref||
  over every gap check of the window's first solve and the last check of
  every whole solve, where x is what the timed path produced and x_ref
  the float64 reference (``bench.reference``) after as many epochs.
* ``gap_rel_err``: the largest |g - g_ref| / g_ref over the same checks,
  where g is the relative gap the stopping rule read (the benchmark's
  float64 check of the program's iterates) and g_ref the same check of the
  reference's iterates after as many epochs: the objective values that
  decide when a solve ends.

The control puts the reference computed in bfloat16 in the program's
place, read by the same float64 check, and is compared the same way.
"""

from __future__ import annotations

import numpy as np

from bench import objective
from bench.reference import Reference

NAMES = ("w_rel_err", "alpha_rel_err", "gap_rel_err")


def rel_err(x, ref) -> float:
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(np.asarray(x, np.float64) - ref)
                 / max(np.linalg.norm(ref), 1e-30))


def _reference(csr, cfg, traffic, precision: str) -> Reference:
    return Reference(csr, loss=cfg["loss"], lam=cfg["lam"], p=int(cfg["p"]),
                     eta0=traffic["eta0"], alpha0=cfg["alpha0"],
                     precision=precision)


def readings(csr, cfg, traffic, checks) -> dict:
    """``checks``: (solve, epoch, gap, w, alpha) as the window kept them:
    every check of the first solve and the last check of every whole
    solve, its answer.  All of them are compared, in the order of their
    epochs, with one run of the reference."""
    ref = _reference(csr, cfg, traffic, "float64")
    gap = objective.HostGap(csr, cfg["loss"], cfg["lam"])
    out = dict.fromkeys(NAMES, 0.0)
    if not any(c[0] == 0 for c in checks):
        return {k: float("inf") for k in NAMES}
    for _, epoch, g, w, alpha in sorted(checks, key=lambda c: c[1]):
        ref.run_to(epoch)
        _compare(out, w, alpha, g, ref, gap)
    return out


def _compare(out: dict, w, alpha, g: float, ref: Reference, gap) -> None:
    g_ref = gap(ref.w, ref.alpha)
    out["w_rel_err"] = max(out["w_rel_err"], rel_err(w, ref.w))
    out["alpha_rel_err"] = max(out["alpha_rel_err"], rel_err(alpha, ref.alpha))
    out["gap_rel_err"] = max(out["gap_rel_err"], abs(g - g_ref) / abs(g_ref))


def control_readings(csr, cfg, traffic, epochs: int) -> dict:
    """The bfloat16 reference in the program's place, read at every gap
    check up to ``epochs``."""
    k = int(traffic["eval_every"])
    ref = _reference(csr, cfg, traffic, "float64")
    ctl = _reference(csr, cfg, traffic, "bfloat16")
    gap = objective.HostGap(csr, cfg["loss"], cfg["lam"])
    out = dict.fromkeys(NAMES, 0.0)
    for epoch in range(k, epochs + 1, k):
        ref.run_to(epoch)
        ctl.run_to(epoch)
        _compare(out, ctl.w, ctl.alpha, gap(ctl.w, ctl.alpha), ref, gap)
    return out


def judge(values: dict, limits: dict) -> list:
    """[(name, value, limit, ok)] for every limited number."""
    return [(k, values[k], limits[k], bool(values[k] <= limits[k]))
            for k in NAMES]
