"""The Eq.-(8) saddle-point tile update — the one piece of math every
backend shares.

TPU adaptation (DESIGN.md §3): instead of the paper's one-nonzero-at-a-time
updates (pointer chasing, hostile to the MXU), each inner iteration performs
``row_batches`` *tile steps* on the active block — dense mat-vecs
X_tile^T alpha and X_tile w on the MXU, with the paper's 1/|Omega-bar_j| and
1/(m |Omega_i|) scalings carried by count vectors.  Block-disjointness (the
paper's key observation) is unchanged, so the serializability argument of
Lemma 2 holds at tile granularity.

``block_tile_step`` is the dense form; ``sparse_tile_step`` the gather form
on a packed block-ELL tile.  Both funnel into ``eq8_apply`` so every op
after the mat-vecs (AdaGrad scaling, step, App. B projections) is shared.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.losses import get_loss
from repro.core.regularizers import get_regularizer
from repro.core.saddle import matvec


def block_tile_step(*, X_tile, y_tile, w_blk, alpha_blk, gw_blk, ga_blk,
                    row_nnz_tile, col_nnz_blk, eta_t, lam, m,
                    loss_name: str, reg_name: str, use_adagrad: bool,
                    w_lo, w_hi, tile_row_nnz=None, tile_col_nnz=None):
    """One TPU-native tile step on an active block (DESIGN.md §3).

    Aggregates Eq. (8) over every nonzero of the tile; simultaneous
    (Jacobi) read of (w, alpha) as in Lemma 2.  Returns updated
    (w_blk, alpha_blk, gw_blk, ga_blk), with App. B projections applied.

    ``tile_row_nnz``/``tile_col_nnz`` are the tile's per-row/per-column
    nonzero counts; pass the precomputed statistics (``GridData``) to keep
    this recomputation off the hot path — they are derived from X here only
    when absent.
    """
    loss = get_loss(loss_name)
    reg = get_regularizer(reg_name)
    if tile_row_nnz is None or tile_col_nnz is None:
        nz = (X_tile != 0).astype(X_tile.dtype)
        tile_col_nnz = nz.sum(axis=0)      # n_j within this tile
        tile_row_nnz = nz.sum(axis=1)      # n_i within this tile
    g_w = (lam * reg.grad(w_blk) * tile_col_nnz / col_nnz_blk
           - matvec(X_tile.T, alpha_blk) / m)
    g_a = (-loss.dual_grad(alpha_blk, y_tile) * tile_row_nnz
           / (m * row_nnz_tile)
           - matvec(X_tile, w_blk) / m)
    # rows with no nonzero in this tile have g_a = 0 automatically
    # (tile_row_nnz = 0 and the X_tile @ w term vanishes).
    return eq8_apply(loss, w_blk, alpha_blk, gw_blk, ga_blk, y_tile,
                     g_w, g_a, eta_t, use_adagrad, w_lo, w_hi)


def eq8_apply(loss, w_blk, alpha_blk, gw_blk, ga_blk, y_tile, g_w, g_a,
              eta_t, use_adagrad, w_lo, w_hi):
    """Shared Eq.-(8) update tail: AdaGrad scaling, step, App. B projection.
    Used by both the dense and the sparse (gather) tile steps so the two
    layouts share every op after the mat-vecs."""
    if use_adagrad:
        gw_blk = gw_blk + g_w * g_w
        ga_blk = ga_blk + g_a * g_a
        dw = eta_t * g_w * jax.lax.rsqrt(gw_blk + 1e-8)
        da = eta_t * g_a * jax.lax.rsqrt(ga_blk + 1e-8)
    else:
        dw, da = eta_t * g_w, eta_t * g_a
    w_blk = jnp.clip(w_blk - dw, w_lo, w_hi)
    alpha_blk = loss.project_alpha(alpha_blk + da, y_tile)
    return w_blk, alpha_blk, gw_blk, ga_blk


def sparse_tile_step(*, cols, vals, y_tile, w_blk, alpha_blk, gw_blk, ga_blk,
                     row_nnz_tile, col_nnz_blk, eta_t, lam, m,
                     loss_name: str, reg_name: str, use_adagrad: bool,
                     w_lo, w_hi, tile_row_nnz=None, tile_col_nnz=None):
    """``block_tile_step`` on a packed block-ELL tile (sparse.format).

    ``cols``/``vals`` are (rows, K) with *block-local* column indices, so
    both Eq.-(8) mat-vecs become nnz-proportional index ops on the
    travelling w block:

        X w       -> sum_k vals[i, k] * w[cols[i, k]]          (gather)
        X^T alpha -> scatter-add of vals[i, k] * alpha[i]      (segment sum)

    Padding slots carry val 0 at col 0 — their gather term is exactly 0 and
    their scatter-add is a no-op, so the result equals the dense tile step
    up to float32 reduction order.  The tile sparsity statistics default to
    being derived from ``vals != 0`` (oracle use); runners pass the
    precomputed ``SparseGridData`` fields.
    """
    loss = get_loss(loss_name)
    reg = get_regularizer(reg_name)
    if tile_row_nnz is None:
        tile_row_nnz = (vals != 0).astype(vals.dtype).sum(axis=1)
    if tile_col_nnz is None:
        tile_col_nnz = jnp.zeros_like(w_blk).at[cols.reshape(-1)] \
            .add((vals != 0).astype(vals.dtype).reshape(-1))
    # the scopes name the two index ops in the compiled program's metadata,
    # where a profile tells the gather's time from the scatter-add's
    with jax.named_scope("xw_gather"):
        xw = jnp.sum(vals * jnp.take(w_blk, cols, axis=0), axis=1)
    with jax.named_scope("xta_scatter"):
        xta = jnp.zeros_like(w_blk) \
            .at[cols.reshape(-1)].add((vals * alpha_blk[:, None]).reshape(-1))
    g_w = lam * reg.grad(w_blk) * tile_col_nnz / col_nnz_blk - xta / m
    g_a = (-loss.dual_grad(alpha_blk, y_tile) * tile_row_nnz
           / (m * row_nnz_tile)
           - xw / m)
    return eq8_apply(loss, w_blk, alpha_blk, gw_blk, ga_blk, y_tile,
                     g_w, g_a, eta_t, use_adagrad, w_lo, w_hi)
