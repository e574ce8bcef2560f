"""Grid state and the common ``TileData`` pytree consumed by every backend.

The p x p DSO grid exists in three layouts — dense row shards
(``GridData``), uniform-K packed block-ELL tiles
(``sparse.format.SparseGridData``), and K-bucketed ragged tiles
(``sparse.format.BucketedGridData``).  The engine does not care which:
``as_tile_data`` converts any of them into a ``TileData`` whose ``arrays``
field carries the layout payload (``(Xg,)`` dense, ``(cols_g, vals_g)``
sparse, and for bucketed the flat chunk view + its chunk tables — see
``TileData``) next to the layout-independent labels, scaling statistics,
and padding masks.  Every backend's block step and the single epoch driver
consume only ``TileData``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.losses import get_loss
from repro.sparse.format import (BucketedGridData, SparseGridData,
                                 pad_to_multiple)

Array = jax.Array


class GridData(NamedTuple):
    """Problem data laid out on the p x p DSO grid (row-major padding).

    The ``tile_*_nnz_g`` fields are the *static sparsity statistics* of the
    grid: per-tile nonzero counts precomputed once here instead of being
    re-derived from X with ``(x != 0).sum(...)`` on every tile step of every
    epoch (they never change — X is immutable during optimization).
    """

    Xg: Array        # (p, mb, d_pad)  row shard per processor, all columns
    yg: Array        # (p, mb)
    row_nnz_g: Array  # (p, mb)   |Omega_i|, >= 1
    col_nnz: Array   # (d_pad,)   |Omega-bar_j|, >= 1
    row_valid: Array  # (p, mb)  1.0 for real rows, 0.0 padding
    p: int
    mb: int          # rows per processor
    db: int          # cols per block
    # [q, s, j]: nnz of column j within row batch s of processor q's shard
    tile_col_nnz_g: Array = None   # (p, row_batches, d_pad)
    # [q, b, i]: nnz of row i of processor q within block b's columns
    tile_row_nnz_g: Array = None   # (p, p, mb)


class TileData(NamedTuple):
    """Layout-agnostic view of the grid: the one pytree every backend sees.

    ``arrays`` is the layout payload — ``(Xg,)`` for the dense backends,
    ``(cols_g, vals_g)`` for the block-ELL sparse backends, and for the
    K-bucketed ragged backends the flat chunk view
    ``(cols_fl, vals_fl, chunk_lut, chunk_cnt)`` they stream; everything
    else is identical between layouts
    (and identical in VALUE too: all tilers reproduce ``make_grid_data``'s
    statistics exactly, which is what makes the trajectories match across
    backends).
    """

    arrays: tuple          # (Xg,) | (cols_g, vals_g) | flat chunk view
    yg: Array              # (p, mb)
    row_nnz_g: Array       # (p, mb)
    col_nnz: Array         # (d_pad,)
    row_valid: Array       # (p, mb)
    tile_col_nnz_g: Array  # (p, row_batches, d_pad)
    tile_row_nnz_g: Array  # (p, p, mb)

    @property
    def layout(self) -> str:
        if len(self.arrays) == 1:
            return "dense"
        if len(self.arrays) == 2:
            return "sparse"
        return "bucketed"      # the flat chunk view


class DSOState(NamedTuple):
    w_grid: Array    # (p, db)   w block *by block id* (not by owner)
    gw_grid: Array   # (p, db)   AdaGrad accumulator travelling with the block
    alpha: Array     # (p, mb)
    ga: Array        # (p, mb)
    epoch: Array     # scalar int32


def as_tile_data(data) -> TileData:
    """``GridData`` | ``SparseGridData`` | ``BucketedGridData`` |
    ``TileData`` -> ``TileData``; the bucketed layout contributes its
    device-resident flat chunk view."""
    if isinstance(data, TileData):
        return data
    if isinstance(data, BucketedGridData):
        arrays = (data.cols_fl, data.vals_fl, data.chunk_lut, data.chunk_cnt)
    elif isinstance(data, SparseGridData):
        arrays = (data.cols_g, data.vals_g)
    else:
        arrays = (data.Xg,)
    return TileData(arrays=arrays, yg=data.yg, row_nnz_g=data.row_nnz_g,
                    col_nnz=data.col_nnz, row_valid=data.row_valid,
                    tile_col_nnz_g=data.tile_col_nnz_g,
                    tile_row_nnz_g=data.tile_row_nnz_g)


def tile_dims(data) -> tuple[int, int, int]:
    """(p, mb, db) of any grid container, from shapes alone."""
    if isinstance(data, TileData):
        p, mb = data.yg.shape
        return p, mb, data.col_nnz.shape[0] // p
    return data.p, data.mb, data.db


def make_grid_data(prob, p: int, row_batches: int = 1) -> GridData:
    """Dense-layout grid builder (row-major padding to multiples of p)."""
    m_pad, d_pad = pad_to_multiple(prob.m, p), pad_to_multiple(prob.d, p)
    mb, db = m_pad // p, d_pad // p
    X = np.zeros((m_pad, d_pad), np.float32)
    X[: prob.m, : prob.d] = np.asarray(prob.X)
    y = np.zeros((m_pad,), np.float32)
    y[: prob.m] = np.asarray(prob.y)
    row_nnz = np.ones((m_pad,), np.float32)
    row_nnz[: prob.m] = np.asarray(prob.row_nnz)
    col_nnz = np.ones((d_pad,), np.float32)
    col_nnz[: prob.d] = np.asarray(prob.col_nnz)
    row_valid = np.zeros((m_pad,), np.float32)
    row_valid[: prob.m] = 1.0
    # static per-tile sparsity statistics, computed once per run (X never
    # changes): per-row-batch column counts and per-block row counts
    Xr = X.reshape(p, mb, d_pad)
    nz = Xr != 0
    rb = max(1, mb // row_batches)
    n_rb = mb // rb
    tile_col_nnz = nz[:, : n_rb * rb].reshape(p, n_rb, rb, d_pad) \
        .sum(axis=2).astype(np.float32)
    tile_row_nnz = nz.reshape(p, mb, p, db).sum(axis=3) \
        .transpose(0, 2, 1).astype(np.float32)
    return GridData(
        Xg=jnp.asarray(Xr),
        yg=jnp.asarray(y.reshape(p, mb)),
        row_nnz_g=jnp.asarray(row_nnz.reshape(p, mb)),
        col_nnz=jnp.asarray(col_nnz),
        row_valid=jnp.asarray(row_valid.reshape(p, mb)),
        p=p, mb=mb, db=db,
        tile_col_nnz_g=jnp.asarray(tile_col_nnz),
        tile_row_nnz_g=jnp.asarray(tile_row_nnz),
    )


def init_state(prob, data, alpha0: float = 0.0) -> DSOState:
    return init_state_data(prob.loss_name, data, alpha0)


def init_state_data(loss_name: str, data, alpha0: float = 0.0) -> DSOState:
    """State init from grid data alone (``GridData``, ``SparseGridData`` or
    ``TileData``) — no ``Problem`` needed, so the out-of-core path can start
    from an ingested grid directly."""
    p, mb, db = tile_dims(data)
    alpha = jnp.full((p, mb), alpha0, jnp.float32)
    alpha = get_loss(loss_name).project_alpha(alpha, data.yg)
    alpha = alpha * data.row_valid
    return DSOState(
        w_grid=jnp.zeros((p, db), jnp.float32),
        gw_grid=jnp.zeros((p, db), jnp.float32),
        alpha=alpha,
        ga=jnp.zeros((p, mb), jnp.float32),
        epoch=jnp.int32(0),
    )


_LAYOUT_BUILDERS = {"dense": "make_grid_data",
                    "sparse": "sparse_grid_from_csr",
                    "bucketed": "bucketed_grid_from_csr"}


def check_tile_stats(data, row_batches: int):
    """The stats' tile height must equal the epoch's tile height, or the
    per-tile counts silently describe the wrong row grouping."""
    if isinstance(data, TileData):
        layout = data.layout
    elif isinstance(data, BucketedGridData):
        layout = "bucketed"
    elif isinstance(data, SparseGridData):
        layout = "sparse"
    else:
        layout = "dense"
    builder = _LAYOUT_BUILDERS[layout]
    mb = data.yg.shape[1]
    assert data.tile_col_nnz_g is not None, \
        f"grid data lacks tile stats: build it with {builder}"
    assert mb // data.tile_col_nnz_g.shape[1] == mb // row_batches, \
        (f"grid stats built for a different row grouping: "
         f"{builder}(..., row_batches={row_batches}) required")


def gather_w(state: DSOState, d: int) -> Array:
    return state.w_grid.reshape(-1)[:d]


def gather_alpha(state: DSOState, m: int) -> Array:
    return state.alpha.reshape(-1)[:m]


def eta_schedule(eta0: float, t0: int, n: int, use_adagrad: bool):
    """Per-epoch step sizes for epochs t0+1 .. t0+n (1/sqrt(t) when the
    AdaGrad scaling is off — Theorem 1's schedule)."""
    return jnp.asarray([eta0 if use_adagrad else eta0 / np.sqrt(t)
                        for t in range(t0 + 1, t0 + n + 1)], jnp.float32)


def prob_meta(prob):
    """(lam, m, loss_name, reg_name, use_adagrad, w_lo, w_hi) of a Problem."""
    loss = get_loss(prob.loss_name)
    box = loss.w_box(prob.lam) if loss.w_box is not None else np.inf
    return (jnp.float32(prob.lam), jnp.float32(prob.m), prob.loss_name,
            prob.reg_name, True, jnp.float32(-box), jnp.float32(box))
