"""The saddle-point reformulation of the regularized risk (paper Sec. 2).

    P(w)       = lam * sum_j phi_j(w_j) + (1/m) sum_i l_i(<w, x_i>)
    f(w,alpha) = lam * sum_j phi_j(w_j) - (1/m) sum_i alpha_i <w, x_i>
                 - (1/m) sum_i l*_i(-alpha_i)
    D(alpha)   = min_w f(w, alpha)      (closed form for separable phi)

    max_alpha' f(w, alpha') = P(w)      (biconjugacy)
    gap(w, alpha) = P(w) - D(alpha)  >= 0, -> 0 at the saddle point.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.losses import Loss, get_loss
from repro.core.regularizers import Regularizer, get_regularizer

Array = jax.Array


def matvec(A: Array, x: Array) -> Array:
    """``A @ x`` at full float32 precision.  At the default precision a
    TPU may run an f32 dot as reduced-precision (bf16) passes; the
    objectives and the dense tile step need the float32 result."""
    return jnp.matmul(A, x, precision=jax.lax.Precision.HIGHEST)


class Problem(NamedTuple):
    """A regularized-risk instance, stored block-dense.

    ``X`` is the (m, d) design matrix (zeros mark absent entries for sparse
    data); ``row_nnz[i] = |Omega_i|`` and ``col_nnz[j] = |Omega-bar_j|`` are the
    paper's per-row / per-column nonzero counts used in the f_ij scalings.
    """

    X: Array  # (m, d) float
    y: Array  # (m,) float, labels (+-1 for classification)
    lam: float
    row_nnz: Array  # (m,)  int->float, clamped >= 1
    col_nnz: Array  # (d,)  clamped >= 1
    nnz: float  # |Omega|
    loss_name: str = "hinge"
    reg_name: str = "l2"

    @property
    def m(self) -> int:
        return self.X.shape[0]

    @property
    def d(self) -> int:
        return self.X.shape[1]

    @property
    def loss(self) -> Loss:
        return get_loss(self.loss_name)

    @property
    def reg(self) -> Regularizer:
        return get_regularizer(self.reg_name)


def make_problem(X, y, lam: float, loss: str = "hinge", reg: str = "l2") -> Problem:
    X = jnp.asarray(X, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    nz = (X != 0).astype(jnp.float32)
    row_nnz = jnp.maximum(nz.sum(axis=1), 1.0)
    col_nnz = jnp.maximum(nz.sum(axis=0), 1.0)
    return Problem(
        X=X, y=y, lam=float(lam), row_nnz=row_nnz, col_nnz=col_nnz,
        nnz=float(nz.sum()), loss_name=loss, reg_name=reg,
    )


def primal_objective(prob: Problem, w: Array) -> Array:
    """P(w) of Eq. (1)."""
    u = matvec(prob.X, w)
    risk = jnp.mean(prob.loss.value(u, prob.y))
    return prob.lam * jnp.sum(prob.reg.value(w)) + risk


def saddle_objective(prob: Problem, w: Array, alpha: Array) -> Array:
    """f(w, alpha) of Sec. 2."""
    m = prob.m
    reg = prob.lam * jnp.sum(prob.reg.value(w))
    coupling = -jnp.dot(alpha, matvec(prob.X, w),
                        precision=jax.lax.Precision.HIGHEST) / m
    dual_payoff = jnp.sum(prob.loss.neg_conjugate(alpha, prob.y)) / m
    return reg + coupling + dual_payoff


def dual_objective(prob: Problem, alpha: Array) -> Array:
    """D(alpha) = min_w f(w, alpha), closed form via the separable phi."""
    m = prob.m
    c = matvec(prob.X.T, alpha) / m  # (d,)
    wmin = jnp.sum(prob.reg.conjugate_min(c, prob.lam))
    dual_payoff = jnp.sum(prob.loss.neg_conjugate(alpha, prob.y)) / m
    return wmin + dual_payoff


def duality_gap(prob: Problem, w: Array, alpha: Array) -> Array:
    """epsilon(w, alpha) = max_a' f(w,a') - min_w' f(w',a) = P(w) - D(alpha)."""
    return primal_objective(prob, w) - dual_objective(prob, alpha)


def argmin_w(prob: Problem, alpha: Array) -> Array:
    """Closed-form minimizer of f(., alpha) for the L2 regularizer."""
    if prob.reg_name != "l2":
        raise ValueError("closed-form argmin_w only for l2")
    return matvec(prob.X.T, alpha) / (2.0 * prob.lam * prob.m)


def project_w(prob: Problem, w: Array) -> Array:
    """App. B box projection on w (loss-dependent)."""
    box = prob.loss.w_box
    if box is None:
        return w
    b = box(prob.lam)
    return jnp.clip(w, -b, b)


def project_alpha(prob: Problem, alpha: Array) -> Array:
    return prob.loss.project_alpha(alpha, prob.y)


def stochastic_grads(prob: Problem, w_j: Array, alpha_i: Array, y_i: Array,
                     x_ij: Array, row_nnz_i: Array, col_nnz_j: Array):
    """The per-(i,j) primal/dual stochastic (sub)gradients of Eq. (8).

    Returns (g_w, g_alpha) such that the update is
        w_j     <- w_j     - eta * g_w
        alpha_i <- alpha_i + eta * g_alpha
    Broadcasts over any leading shape.
    """
    m = prob.m
    g_w = prob.lam * prob.reg.grad(w_j) / col_nnz_j - alpha_i * x_ij / m
    g_a = (-prob.loss.dual_grad(alpha_i, y_i) / (m * row_nnz_i)
           - w_j * x_ij / m)
    return g_w, g_a


def grads_tile(prob: Problem, X_tile: Array, y_tile: Array, w_blk: Array,
               alpha_blk: Array, row_nnz_tile: Array, col_nnz_blk: Array,
               tile_col_nnz: Array, tile_row_nnz: Array):
    """Aggregated Eq.-(8) gradients for a dense tile (TPU-native block step).

    Summing the pointwise gradients over every nonzero of the tile:
      g_w[j]  = lam phi'(w_j) * n_j / |Omega-bar_j| - (X^T alpha)_j / m
      g_a[i]  = -l*'(-alpha_i) * n_i / (m |Omega_i|) - (X w)_i / m
    where n_j / n_i count the tile's nonzeros in column j / row i.
    """
    m = prob.m
    g_w = (prob.lam * prob.reg.grad(w_blk) * tile_col_nnz / col_nnz_blk
           - matvec(X_tile.T, alpha_blk) / m)
    g_a = (-prob.loss.dual_grad(alpha_blk, y_tile) * tile_row_nnz
           / (m * row_nnz_tile)
           - matvec(X_tile, w_blk) / m)
    return g_w, g_a
