"""The regularized-risk objectives, written for the benchmark alone.

    P(w)     = lam * sum_j w_j^2 + (1/m) sum_i l(<w, x_i>, y_i)
    D(alpha) = sum_j min_w (lam w^2 - c_j w) + (1/m) sum_i -l*(-alpha_i, y_i)
             = -sum_j c_j^2 / (4 lam) + (1/m) sum_i -l*(-alpha_i, y_i),
               c = X^T alpha / m

The L2 regularizer is phi(w) = w^2 (lambda absorbs constants).  The
reference's update and the gap check share one statement of the loss
conjugates.  ``ALPHA_EPS`` is the float32 analogue of App. B's 1e-14:
logistic alphas live in y * [eps, 1 - eps].
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ALPHA_EPS = 1e-6
LOSSES = ("hinge", "logistic")


def check_loss(loss: str, reg: str) -> None:
    if loss not in LOSSES or reg != "l2":
        raise ValueError(f"the benchmark's objectives cover {LOSSES} with "
                         f"l2, not {loss}/{reg}")


def loss_value(loss: str, u, y):
    z = -y * u
    if loss == "hinge":
        return np.maximum(1.0 + z, 0.0)
    return np.logaddexp(0.0, z)


def neg_conjugate(loss: str, a, y):
    """-l*(-a): y*a for hinge, the binary entropy of y*a for logistic."""
    if loss == "hinge":
        return y * a
    b = np.clip(y * a, ALPHA_EPS, 1.0 - ALPHA_EPS)
    return -(b * np.log(b) + (1.0 - b) * np.log1p(-b))


def dual_grad(loss: str, a, y):
    """d/da of l*(-a)."""
    if loss == "hinge":
        return -y
    b = np.clip(y * a, ALPHA_EPS, 1.0 - ALPHA_EPS)
    return y * (np.log(b) - np.log1p(-b))


def project_alpha(loss: str, a, y):
    lo = 0.0 if loss == "hinge" else ALPHA_EPS
    return y * np.clip(y * a, lo, 1.0 - lo)


def w_box(loss: str, lam: float) -> float:
    """Half-width of App. B's box on w."""
    if loss == "hinge":
        return float(1.0 / np.sqrt(lam))
    return float(np.sqrt(np.log(2.0) / lam))


def primal_dual(loss: str, lam, m: int, u, c, w, alpha, y):
    """(P, D) from the margins ``u = X w`` and ``c = X^T alpha`` (not yet
    divided by m)."""
    pv = lam * np.sum(w * w) + np.mean(loss_value(loss, u, y))
    c = c / m
    dv = -np.sum(c * c) / (4.0 * lam) \
        + np.sum(neg_conjugate(loss, alpha, y)) / m
    return pv, dv


class HostGap:
    """The benchmark's gap check: ``gap(w, alpha)`` is the relative duality
    gap (P - D) / P in float64 on the host, exact to rounding for the
    iterates it is given.

    The nonzeros are kept in column order, widened to float64 once, here,
    and cut into up to 8 runs of whole columns: each run gathers w in
    order, adds its share of ``u = X w`` and sums its columns of
    ``c = X^T alpha`` on a thread of its own."""

    def __init__(self, csr, loss: str, lam: float):
        check_loss(loss, "l2")
        self.loss, self.lam, self.m, self.d = loss, float(lam), csr.m, csr.d
        order = np.argsort(csr.indices, kind="stable")
        self.cols = csr.indices[order]
        self.rows = csr.row_ids()[order]
        self.vals = csr.values[order].astype(np.float64)
        self.y = csr.y.astype(np.float64)
        starts = np.flatnonzero(np.r_[True, self.cols[1:] != self.cols[:-1]])
        self.ucols = self.cols[starts]
        ends = np.r_[starts[1:], self.cols.size]
        n = min(8, os.cpu_count() or 1)
        self.runs = [(starts[g[0]], ends[g[-1]], starts[g] - starts[g[0]])
                     for g in np.array_split(np.arange(starts.size), n)
                     if g.size]

    def _run(self, lo, hi, seg, w, alpha):
        rows = self.rows[lo:hi]
        vals = self.vals[lo:hi]
        u = np.bincount(rows, weights=vals * w[self.cols[lo:hi]],
                        minlength=self.m)
        return u, np.add.reduceat(vals * alpha[rows], seg)

    def __call__(self, w, alpha) -> float:
        w = np.asarray(w, np.float64)
        alpha = np.asarray(alpha, np.float64)
        with ThreadPoolExecutor(max_workers=len(self.runs)) as pool:
            parts = list(pool.map(lambda r: self._run(*r, w, alpha),
                                  self.runs))
        u = np.sum([p[0] for p in parts], axis=0)
        c = np.zeros(self.d)
        c[self.ucols] = np.concatenate([p[1] for p in parts])
        pv, dv = primal_dual(self.loss, self.lam, self.m, u, c, w, alpha,
                             self.y)
        return float((pv - dv) / pv)

