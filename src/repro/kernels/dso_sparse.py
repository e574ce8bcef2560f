"""Gather-based Pallas kernel for the sparse (block-ELL) DSO tile step.

Mirrors the dense ``_fused_block_kernel`` of ``dso_update.py`` on the packed
tile format of ``repro.sparse.format``: one launch covers the whole active
block, with the ``row_batches`` sub-scan folded into the kernel grid and the
travelling w block + its AdaGrad accumulator living in VMEM scratch across
the launch.  The difference is what streams from HBM: instead of the dense
(mb, db) X block (4*mb*db bytes), the kernel reads the packed (mb, K)
column-index + value arrays — 8*mb*K bytes, nnz-proportional (K is the
padded max row nnz of the tile, sublane-aligned; sparse.format.choose_k).

Data flow per grid step ``mi`` (row tiles = sequential minibatch steps):

    cols (rb, K) i32 ──┐          packed tile: the ONLY HBM matrix read
    vals (rb, K) f32 ──┤          (8*rb*K bytes vs dense 4*rb*db)
                       ├─> gather   sum_k vals*w_st[cols]  -> X w    (rb, 1)
    w_st (1, db) VMEM ─┤               └ dual update of this alpha slice
                       └─> scatter  add   vals*alpha at cols -> X^T a (1, db)
    alpha (rb, 1) ─────┘               └ primal update, w_st advances

Both mat-vecs read the *pre-update* (w_st, alpha) of the step — the same
Jacobi/Lemma-2 form as the dense kernels — so a ``row_batches=1`` launch is
exactly the fused tile step and the general case equals scanning
``core.dso.sparse_tile_step`` (which in turn equals the dense
``block_tile_step`` to float32 reduction order).

The in-kernel gather (``jnp.take`` of the w block at a 2-D index array) and
scatter-add (``.at[].add``) run only under ``interpret=True``: the TPU
compiler (Mosaic) refuses both ("Only 2D gather is supported"), so on a TPU
the ``ops`` wrappers raise ``ValueError`` after the
``ops.mosaic_sparse_gather_error`` probe instead of compiling these kernels.
The XLA backends (``sparse_jnp`` / ``sparse_bucketed_jnp``) run the same
nnz-proportional math through XLA's native gather and scatter.

The per-tile nonzero counts are precomputed (``SparseGridData``) and passed
in, exactly like the dense kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dso_update import _dual_update, _primal_update
from repro.sparse.format import K_CHUNK


def _sparse_block_kernel(cols_ref, vals_ref, y_ref, w_ref, alpha_ref,
                         gw_ref, ga_ref, trn_ref, tcn_ref, rn_ref, cn_ref,
                         scal_ref, w_out_ref, a_out_ref, gw_out_ref,
                         ga_out_ref, w_st_ref, gw_st_ref,
                         *, loss_name: str, reg_name: str):
    """One active block: each grid step is one sequential minibatch step on
    a packed (rb, K) row tile; the whole block width db sits in VMEM."""
    mi = pl.program_id(0)   # row tiles = sequential minibatch steps

    @pl.when(mi == 0)
    def _load_state():
        w_st_ref[...] = w_ref[...]
        gw_st_ref[...] = gw_ref[...]

    cols = cols_ref[...]                # (rb, K) int32 — packed tile read
    vals = vals_ref[...]                # (rb, K), 0.0 in padding slots
    a = alpha_ref[...]                  # (rb, 1), pre-update
    w = w_st_ref[...]                   # (1, db), state BEFORE this step

    # dual mat-vec: gather the travelling w at the packed column indices
    # (padding gathers w[0] * 0 = 0 exactly)
    xw = jnp.sum(vals * jnp.take(w[0], cols, axis=0), axis=1,
                 keepdims=True)         # (rb, 1) partial X w
    a_new, ga_new = _dual_update(
        loss_name, a, ga_ref[...], y_ref[...], xw, trn_ref[...],
        rn_ref[...], scal_ref[...])
    a_out_ref[...] = a_new
    ga_out_ref[...] = ga_new

    # primal mat-vec: scatter-add vals * alpha into the w-block accumulator
    # (padding adds 0 at column 0 — a no-op)
    acc = jnp.zeros_like(w).at[0, cols.reshape(-1)] \
        .add((vals * a).reshape(-1))    # (1, db) X^T alpha of this tile
    w_new, gw_new = _primal_update(
        reg_name, w, gw_st_ref[...], acc, tcn_ref[...], cn_ref[...],
        scal_ref[...])
    w_st_ref[...] = w_new
    gw_st_ref[...] = gw_new
    w_out_ref[...] = w_new              # last row tile's flush is the result
    gw_out_ref[...] = gw_new


@functools.partial(
    jax.jit,
    static_argnames=("row_batches", "loss_name", "reg_name", "interpret"))
def dso_sparse_block_step_pallas(cols, vals, y, w, alpha, gw, ga,
                                 tile_row_nnz, tile_col_nnz, row_nnz,
                                 col_nnz, scalars, *, row_batches: int,
                                 loss_name: str, reg_name: str,
                                 interpret: bool):
    """All ``row_batches`` sequential tile steps of one active block from
    its packed block-ELL tile.  cols/vals (M, K) with block-local column
    indices; w/gw/col_nnz (db,); alpha/ga/y/row_nnz/tile_row_nnz (M,);
    ``tile_col_nnz`` (row_batches, db); scalars = [eta, lam, m, w_lo, w_hi].

    M % row_batches == 0 (the ops wrapper truncates like the dense path).
    Equivalent to scanning ``core.dso.sparse_tile_step`` over the row tiles.
    """
    M, K = cols.shape
    db = w.shape[0]
    assert M % row_batches == 0, (M, row_batches)
    bm = M // row_batches
    n_mt = row_batches

    import jax.experimental.pallas.tpu as pltpu
    scratch = [pltpu.VMEM((1, db), jnp.float32),   # travelling w state
               pltpu.VMEM((1, db), jnp.float32)]   # its AdaGrad acc
    w2, a2, gw2, ga2 = pl.pallas_call(
        functools.partial(_sparse_block_kernel, loss_name=loss_name,
                          reg_name=reg_name),
        grid=(n_mt,),
        in_specs=[
            pl.BlockSpec((bm, K), lambda mi: (mi, 0)),    # cols
            pl.BlockSpec((bm, K), lambda mi: (mi, 0)),    # vals
            pl.BlockSpec((bm, 1), lambda mi: (mi, 0)),    # y
            pl.BlockSpec((1, db), lambda mi: (0, 0)),     # w
            pl.BlockSpec((bm, 1), lambda mi: (mi, 0)),    # alpha
            pl.BlockSpec((1, db), lambda mi: (0, 0)),     # gw
            pl.BlockSpec((bm, 1), lambda mi: (mi, 0)),    # ga
            pl.BlockSpec((bm, 1), lambda mi: (mi, 0)),    # tile row nnz
            pl.BlockSpec((None, 1, db), lambda mi: (mi, 0, 0)),  # t col nnz
            pl.BlockSpec((bm, 1), lambda mi: (mi, 0)),    # |Omega_i|
            pl.BlockSpec((1, db), lambda mi: (0, 0)),     # |Omega-bar_j|
            pl.BlockSpec((1, 5), lambda mi: (0, 0)),      # scalars
        ],
        out_specs=[
            pl.BlockSpec((1, db), lambda mi: (0, 0)),     # w
            pl.BlockSpec((bm, 1), lambda mi: (mi, 0)),    # alpha
            pl.BlockSpec((1, db), lambda mi: (0, 0)),     # gw
            pl.BlockSpec((bm, 1), lambda mi: (mi, 0)),    # ga
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, db), jnp.float32),
            jax.ShapeDtypeStruct((M, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, db), jnp.float32),
            jax.ShapeDtypeStruct((M, 1), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(cols, vals, y.reshape(M, 1), w.reshape(1, db), alpha.reshape(M, 1),
      gw.reshape(1, db), ga.reshape(M, 1),
      tile_row_nnz.reshape(M, 1).astype(jnp.float32),
      tile_col_nnz.reshape(n_mt, 1, db).astype(jnp.float32),
      row_nnz.reshape(M, 1), col_nnz.reshape(1, db), scalars.reshape(1, 5))
    return (w2.reshape(db), a2.reshape(M), gw2.reshape(db), ga2.reshape(M))


# --------------------------------------------------------------------------
# One-kernel K-bucketed tile step: scalar-prefetch chunk dispatch
# --------------------------------------------------------------------------
#
# The bucketed layout stores every tile as consecutive (mb, K_CHUNK) chunks
# of ONE flat ragged buffer (``sparse.format.BucketedGridData`` flat chunk
# view).  Instead of a ``lax.switch`` over per-bucket kernels, a single
# launch walks grid = (row_batches, n_kc) with the chunk axis innermost:
#
#     info (n_kc+1,) SMEM  = [chunk_lut row | chunk count]  (scalar prefetch)
#        │
#        ▼  index map: block kc of cols/vals = flat[info[kc]]
#     cols_fl (1, rb, Kc) ──> cols_st (rb, n_kc*Kc) VMEM   staging: chunk kc
#     vals_fl (1, rb, Kc) ──> vals_st (rb, n_kc*Kc) VMEM   lands at column
#                                   │                      kc*Kc, dead slots
#          kc == n_kc-1:            ▼                      zeroed
#     gather/dual/scatter/primal on the staged (rb, Kmax) tile — the exact
#     ``_sparse_block_kernel`` math — with w/gw travelling in VMEM scratch
#     across all row batches (the ``row_batches`` sub-scan IS the grid).
#
# ``chunk_lut`` values are pre-clamped (dead slots repeat the tile's last
# chunk), so the index map is just ``info[kc]`` — no branching anywhere.
# Tiles of every K-bucket run through this one kernel; the bucket only
# changes *which* chunks stream in and how many are live.
#
# ``dso_bucketed_block_step_jnp`` below is the same staging + the same math
# expressed in plain jnp — the two are bit-identical by construction.


def _staged_step_math(cols, vals, y, w, a, gw, ga, trn, tcn, rn, cn, scal,
                      *, loss_name: str, reg_name: str):
    """Eq.-8 step on one staged (rb, Kmax) row batch.

    Shared by the Pallas kernel body and the jnp twin so the one-kernel
    backend and ``sparse_bucketed_jnp`` produce bit-identical trajectories:
    both run exactly these ops at exactly these shapes.  Dead chunk slots
    hold col 0 / val 0.0, so they gather ``w[0] * 0`` and scatter ``0`` at
    column 0 — exact no-ops.
    """
    xw = jnp.sum(vals * jnp.take(w[0], cols, axis=0), axis=1,
                 keepdims=True)                      # (rb, 1) partial X w
    a_new, ga_new = _dual_update(loss_name, a, ga, y, xw, trn, rn, scal)
    acc = jnp.zeros_like(w).at[0, cols.reshape(-1)] \
        .add((vals * a).reshape(-1))                 # (1, db), pre-update a
    w_new, gw_new = _primal_update(reg_name, w, gw, acc, tcn, cn, scal)
    return w_new, a_new, gw_new, ga_new


def _bucketed_block_kernel(info_ref, cols_ref, vals_ref, y_ref, w_ref,
                           alpha_ref, gw_ref, ga_ref, trn_ref, tcn_ref,
                           rn_ref, cn_ref, scal_ref, w_out_ref, a_out_ref,
                           gw_out_ref, ga_out_ref, w_st_ref, gw_st_ref,
                           cols_st_ref, vals_st_ref,
                           *, n_kc: int, loss_name: str, reg_name: str):
    """grid = (row_batches, n_kc), chunk slot innermost.  Steps kc < n_kc-1
    only stage their chunk; the last slot runs the tile step on the staged
    rectangle and flushes the outputs."""
    mi = pl.program_id(0)   # row tiles = sequential minibatch steps
    kc = pl.program_id(1)   # chunk slots of the current row tile

    @pl.when((mi == 0) & (kc == 0))
    def _load_state():
        w_st_ref[...] = w_ref[...]
        gw_st_ref[...] = gw_ref[...]

    # stage chunk kc: live slots copy their (rb, Kc) chunk, dead slots (the
    # lut repeats the last live chunk there) are zeroed so the math below
    # sees exact no-op padding
    live = kc < info_ref[n_kc]
    sl = pl.dslice(kc * K_CHUNK, K_CHUNK)
    cols_st_ref[:, sl] = jnp.where(live, cols_ref[0], 0)
    vals_st_ref[:, sl] = jnp.where(live, vals_ref[0], 0.0)

    @pl.when(kc == n_kc - 1)
    def _tile_step():
        w_new, a_new, gw_new, ga_new = _staged_step_math(
            cols_st_ref[...], vals_st_ref[...], y_ref[...], w_st_ref[...],
            alpha_ref[...], gw_st_ref[...], ga_ref[...], trn_ref[...],
            tcn_ref[...], rn_ref[...], cn_ref[...], scal_ref[...],
            loss_name=loss_name, reg_name=reg_name)
        w_st_ref[...] = w_new
        gw_st_ref[...] = gw_new
        w_out_ref[...] = w_new          # last row tile's flush is the result
        gw_out_ref[...] = gw_new
        a_out_ref[...] = a_new
        ga_out_ref[...] = ga_new


@functools.partial(
    jax.jit,
    static_argnames=("row_batches", "loss_name", "reg_name", "interpret"))
def dso_bucketed_block_step_pallas(cols_fl, vals_fl, lut, cnt, y, w, alpha,
                                   gw, ga, tile_row_nnz, tile_col_nnz,
                                   row_nnz, col_nnz, scalars, *,
                                   row_batches: int, loss_name: str,
                                   reg_name: str, interpret: bool):
    """All ``row_batches`` sequential tile steps of one active block from
    the flat chunk view.  cols_fl/vals_fl (n_chunks, M, K_CHUNK) with
    block-local column indices; ``lut`` (n_kc,) clamped chunk indices of
    this tile, ``cnt`` () its live-chunk count; the rest as in
    ``dso_sparse_block_step_pallas``.  M % row_batches == 0 (the ops
    wrapper truncates like the dense path).
    """
    M = y.shape[0]
    db = w.shape[0]
    n_kc = lut.shape[0]
    assert M % row_batches == 0, (M, row_batches)
    bm = M // row_batches
    n_mt = row_batches
    k_max = n_kc * K_CHUNK

    import jax.experimental.pallas.tpu as pltpu
    info = jnp.concatenate([lut.reshape(n_kc).astype(jnp.int32),
                            cnt.reshape(1).astype(jnp.int32)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_mt, n_kc),
        in_specs=[
            # the scalar-prefetched lut IS the dispatch: block kc of the
            # flat buffer streams chunk info[kc] of this tile
            pl.BlockSpec((1, bm, K_CHUNK),
                         lambda mi, kc, info: (info[kc], mi, 0)),   # cols_fl
            pl.BlockSpec((1, bm, K_CHUNK),
                         lambda mi, kc, info: (info[kc], mi, 0)),   # vals_fl
            pl.BlockSpec((bm, 1), lambda mi, kc, info: (mi, 0)),    # y
            pl.BlockSpec((1, db), lambda mi, kc, info: (0, 0)),     # w
            pl.BlockSpec((bm, 1), lambda mi, kc, info: (mi, 0)),    # alpha
            pl.BlockSpec((1, db), lambda mi, kc, info: (0, 0)),     # gw
            pl.BlockSpec((bm, 1), lambda mi, kc, info: (mi, 0)),    # ga
            pl.BlockSpec((bm, 1), lambda mi, kc, info: (mi, 0)),    # t row nnz
            pl.BlockSpec((None, 1, db),
                         lambda mi, kc, info: (mi, 0, 0)),          # t col nnz
            pl.BlockSpec((bm, 1), lambda mi, kc, info: (mi, 0)),    # |Omega_i|
            pl.BlockSpec((1, db), lambda mi, kc, info: (0, 0)),     # |O-bar_j|
            pl.BlockSpec((1, 5), lambda mi, kc, info: (0, 0)),      # scalars
        ],
        out_specs=[
            pl.BlockSpec((1, db), lambda mi, kc, info: (0, 0)),     # w
            pl.BlockSpec((bm, 1), lambda mi, kc, info: (mi, 0)),    # alpha
            pl.BlockSpec((1, db), lambda mi, kc, info: (0, 0)),     # gw
            pl.BlockSpec((bm, 1), lambda mi, kc, info: (mi, 0)),    # ga
        ],
        scratch_shapes=[
            pltpu.VMEM((1, db), jnp.float32),        # travelling w state
            pltpu.VMEM((1, db), jnp.float32),        # its AdaGrad acc
            pltpu.VMEM((bm, k_max), jnp.int32),      # staged tile cols
            pltpu.VMEM((bm, k_max), jnp.float32),    # staged tile vals
        ],
    )
    w2, a2, gw2, ga2 = pl.pallas_call(
        functools.partial(_bucketed_block_kernel, n_kc=n_kc,
                          loss_name=loss_name, reg_name=reg_name),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((1, db), jnp.float32),
            jax.ShapeDtypeStruct((M, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, db), jnp.float32),
            jax.ShapeDtypeStruct((M, 1), jnp.float32),
        ],
        interpret=interpret,
    )(info, cols_fl, vals_fl, y.reshape(M, 1), w.reshape(1, db),
      alpha.reshape(M, 1), gw.reshape(1, db), ga.reshape(M, 1),
      tile_row_nnz.reshape(M, 1).astype(jnp.float32),
      tile_col_nnz.reshape(n_mt, 1, db).astype(jnp.float32),
      row_nnz.reshape(M, 1), col_nnz.reshape(1, db), scalars.reshape(1, 5))
    return (w2.reshape(db), a2.reshape(M), gw2.reshape(db), ga2.reshape(M))


@functools.partial(
    jax.jit, static_argnames=("row_batches", "loss_name", "reg_name"))
def dso_bucketed_block_step_jnp(cols_fl, vals_fl, lut, cnt, y, w, alpha, gw,
                                ga, tile_row_nnz, tile_col_nnz, row_nnz,
                                col_nnz, scalars, *, row_batches: int,
                                loss_name: str, reg_name: str):
    """jnp twin of ``dso_bucketed_block_step_pallas``: the same chunk
    staging (dynamic-slice per lut entry, dead slots zeroed) and the same
    ``_staged_step_math`` at the same shapes, scanned over the row tiles —
    bit-identical to the one-kernel launch by construction.  Rows past
    ``(M // row_batches) * row_batches`` pass through untouched, matching
    the ops-wrapper truncation.
    """
    M = y.shape[0]
    db = w.shape[0]
    n_kc = lut.shape[0]
    bm = M // row_batches
    lut = lut.astype(jnp.int32)
    n_live = cnt.astype(jnp.int32)
    y2 = y.reshape(M, 1)
    trn2 = tile_row_nnz.reshape(M, 1).astype(jnp.float32)
    tcn2 = tile_col_nnz.reshape(row_batches, db).astype(jnp.float32)
    rn2 = row_nnz.reshape(M, 1)
    cn2 = col_nnz.reshape(1, db)
    scal = scalars.reshape(1, 5)

    def stage(r0):
        cols_p, vals_p = [], []
        for kc in range(n_kc):
            c = jax.lax.dynamic_slice(
                cols_fl, (lut[kc], r0, 0), (1, bm, K_CHUNK))[0]
            v = jax.lax.dynamic_slice(
                vals_fl, (lut[kc], r0, 0), (1, bm, K_CHUNK))[0]
            live = kc < n_live
            cols_p.append(jnp.where(live, c, 0))
            vals_p.append(jnp.where(live, v, 0.0))
        return (jnp.concatenate(cols_p, axis=1),
                jnp.concatenate(vals_p, axis=1))     # (bm, n_kc * K_CHUNK)

    def sub_step(carry, mi):
        w_c, a_c, gw_c, ga_c = carry
        r0 = mi * bm
        cols, vals = stage(r0)
        a_t = jax.lax.dynamic_slice(a_c, (r0, 0), (bm, 1))
        ga_t = jax.lax.dynamic_slice(ga_c, (r0, 0), (bm, 1))
        y_t = jax.lax.dynamic_slice(y2, (r0, 0), (bm, 1))
        trn_t = jax.lax.dynamic_slice(trn2, (r0, 0), (bm, 1))
        rn_t = jax.lax.dynamic_slice(rn2, (r0, 0), (bm, 1))
        tcn_t = jax.lax.dynamic_slice(tcn2, (mi, 0), (1, db))
        w_c, a_t, gw_c, ga_t = _staged_step_math(
            cols, vals, y_t, w_c, a_t, gw_c, ga_t, trn_t, tcn_t, rn_t, cn2,
            scal, loss_name=loss_name, reg_name=reg_name)
        a_c = jax.lax.dynamic_update_slice(a_c, a_t, (r0, 0))
        ga_c = jax.lax.dynamic_update_slice(ga_c, ga_t, (r0, 0))
        return (w_c, a_c, gw_c, ga_c), None

    carry0 = (w.reshape(1, db), alpha.reshape(M, 1), gw.reshape(1, db),
              ga.reshape(M, 1))
    (w2, a2, gw2, ga2), _ = jax.lax.scan(
        sub_step, carry0, jnp.arange(row_batches, dtype=jnp.int32))
    return (w2.reshape(db), a2.reshape(M), gw2.reshape(db), ga2.reshape(M))
