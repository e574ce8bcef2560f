"""Pallas kernels for the sparse (block-ELL) DSO tile step.

Uniform layout (``sparse_pallas``): the one-hot tile step
----------------------------------------------------------

One launch runs every ``row_batches`` sequential Eq.-(8) step of the active
block of each processor, reading the packed (M, K) ``cols``/``vals`` tile
straight from the grid (the block id is a prefetched scalar of the index
map, so no tile is sliced out in HBM).  No index op is involved: the gather
of the travelling w block and the scatter-add of its gradient are one-hot
matmuls on the MXU.  A block-local column id ``c`` is factored as
``hi = c >> 7``, ``lo = c & 127``, and w is viewed as ``W2 = (H, 128)``
(H = ceil(db / 128), padded to a multiple of 16):

    gather   w[c]         = sum_h [hi == h] * (W2 @ onehot_lo)[h]
    scatter  (X^T v)[h, l] = sum_s [hi_s == h] * v_s * [lo_s == l]
                           = ((onehot_hi * v) @ onehot_lo^T)[h, l]

Exact in float32: the float32 operand (w for the gather, ``vals * alpha``
for the scatter) is split into three bfloat16 parts whose float32 sum is
the operand exactly, stacked as 3H rows of ONE default-precision bfloat16
matmul with a float32 accumulator, and the three parts are summed after
it.  A one-hot is exact in bfloat16, so every gathered value is ``w[c]``
bit for bit; the scatter sums in float32 like XLA's scatter-add, in
another order.

Layout.  On a TPU the grid's (..., M, K) arrays are stored with the row
axis minor (K = 32 would waste 3/4 of every 128-lane row otherwise), so the
kernel reads their free (K, M) transpose: slots and rows are lane-dense,
one 128-lane row holds one slot of 128 rows, and a row's sum over its K
slots is a sublane sum.  Per-row vectors are (1, M) lane-dense rows.

    cols/vals (K, C) ──> per 128 rows, per slot row k (1, 128):
       onehot_lo (128, 128) bf16, [hi == h] (H, 128)
       gather:  W3 (3H, 128) bf16 @ onehot_lo -> sum parts, select hi
                xw += vals_k * w[c_k]                        (1, 128)
       scatter: acc (3H, 128) += ([hi == h] * split3(vals_k * alpha))
                                 @ onehot_lo^T
    dual update of the 128 rows (pre-update w and alpha)
    primal update at the end of each row batch (acc summed over parts)

grid = (processors, row chunks of C lanes); w, gw, the split w and the
accumulator stay in VMEM for the whole launch, and each row batch's column
counts are copied in from HBM when the batch ends, so the kernel's VMEM
does not grow with ``row_batches``.  Rows are walked in order
and every row of a row batch reads the batch's pre-update (w, alpha), the
Jacobi / Lemma-2 form of ``core.dso.sparse_tile_step``, so the kernel
equals scanning that step over the row batches.  Rows past
``row_batches * (M // row_batches)`` pass through unchanged.  Under
``vmap`` (the grid simulator's processors) the kernel takes the batch as
its leading grid axis (``custom_vmap``), so the processors' tiles are
still read in place.

K-bucketed layout (``sparse_bucketed_pallas``) — further below — still
gathers with ``jnp.take`` and scatter-adds with ``.at[].add``; the TPU
compiler (Mosaic) refuses both ("Only 2D gather is supported"), so on a
TPU the ``ops`` wrapper raises ``ValueError`` after the
``ops.mosaic_sparse_gather_error`` probe instead of compiling it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dso_update import _dual_update, _primal_update
from repro.sparse.format import K_CHUNK

_LANES = 128
#: rows per grid step of the one-hot kernel (a multiple of 128 lanes), at
#: most: fewer for wide tiles, so that the double-buffered cols/vals blocks
#: stay within ``_SLOT_BLOCK_BYTES`` of VMEM
_ROW_CHUNK = 1024
_SLOT_BLOCK_BYTES = 4 << 20


def _split3(x):
    """float32 ``x`` as the float32 values of three bfloat16 parts whose
    sum ``hi + (mid + lo)`` is ``x`` exactly (8 + 8 + 8 significant
    bits)."""
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    r = x - hi
    mid = r.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (r - mid).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, mid, lo


def _sum3(x, h: int):
    """(3h, 128) stacked parts -> (h, 128) float32 sum, hi + (mid + lo)."""
    return x[:h] + (x[h:2 * h] + x[2 * h:])


def _slot_row(w3, col, val, a_m, h: int):
    """One slot row of 128 rows: the gathered ``w[col]`` (1, 128) and the
    stacked (3h, 128) scatter-add of ``val * a_m`` at ``col``."""
    iota_l = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
    iota_h = jax.lax.broadcasted_iota(jnp.int32, (h, _LANES), 0)
    onehot = jnp.where(iota_l == (col & (_LANES - 1)), 1.0, 0.0) \
        .astype(jnp.bfloat16)                              # [lo, slot]
    sel = jnp.where(iota_h == (col >> 7), 1.0, 0.0)        # [hi, slot]
    g = jax.lax.dot(w3, onehot, preferred_element_type=jnp.float32)
    gathered = jnp.sum(sel * _sum3(g, h), axis=0, keepdims=True)
    parts = jnp.concatenate([sel * p for p in _split3(val * a_m)],
                            axis=0).astype(jnp.bfloat16)
    scattered = jax.lax.dot_general(parts, onehot, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    return gathered, scattered


def _onehot_kernel(blk_ref, cols_ref, vals_ref, y_ref, a_ref, ga_ref,
                   trn_ref, rn_ref, w_ref, gw_ref, tcn_ref, cn_ref, scal_ref,
                   w_out_ref, gw_out_ref, a_out_ref, ga_out_ref,
                   w_st, gw_st, w3_st, acc_st, tcn_st,
                   *, rb: int, n_rb: int, loss_name: str, reg_name: str,
                   use_adagrad: bool):
    """One chunk of C rows of one processor's active tile; the processor's
    w state lives in the scratch refs from its first chunk on.  ``tcn_ref``
    stays in HBM: each row batch's (h, 128) row is copied in when the batch
    ends, so fast memory does not grow with ``row_batches``."""
    import jax.experimental.pallas.tpu as pltpu

    del blk_ref                        # consumed by the index maps
    q, c = pl.program_id(0), pl.program_id(1)
    h = w_st.shape[0]
    K, C = cols_ref.shape

    def load_w(w):
        w_st[...] = w
        w3_st[...] = jnp.concatenate(_split3(w), axis=0) \
            .astype(jnp.bfloat16)

    @pl.when(c == 0)
    def _load_state():
        load_w(w_ref[...])
        gw_st[...] = gw_ref[...]
        acc_st[...] = jnp.zeros_like(acc_st)

    scal = scal_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

    def slot_rows(k0, sl, in_b, a_m, xw):
        """Eight slot rows k0..k0+7 of 128 rows: gather into ``xw``,
        scatter-add ``vals * a_m`` into the accumulator."""
        cols8 = jnp.where(in_b, cols_ref[pl.ds(k0, 8), sl], 0)
        vals8 = jnp.where(in_b, vals_ref[pl.ds(k0, 8), sl], 0.0)
        w3 = w3_st[...]
        acc = jnp.zeros(acc_st.shape, jnp.float32)
        for i in range(8):
            val = vals8[i:i + 1]                               # (1, 128)
            gathered, scattered = _slot_row(w3, cols8[i:i + 1], val, a_m, h)
            xw = xw + val * gathered
            acc = acc + scattered
        acc_st[...] += acc
        return xw

    def rows128(j, carry):
        off = pl.multiple_of(j * _LANES, _LANES)
        sl = pl.ds(off, _LANES)
        r0 = c * C + off
        rows = r0 + lane
        a_pre, ga_pre = a_ref[:, sl], ga_ref[:, sl]
        a_out_ref[:, sl] = a_pre           # rows of no batch pass through
        ga_out_ref[:, sl] = ga_pre

        def batch(s, carry):
            in_b = (rows >= s * rb) & (rows < (s + 1) * rb)
            a_m = jnp.where(in_b, a_pre, 0.0)
            xw = jax.lax.fori_loop(
                0, K // 8,
                lambda g, xw: slot_rows(pl.multiple_of(g * 8, 8), sl, in_b,
                                        a_m, xw),
                jnp.zeros((1, _LANES), jnp.float32))
            a_new, ga_new = _dual_update(
                loss_name, a_pre, ga_pre, y_ref[:, sl], xw, trn_ref[:, sl],
                rn_ref[:, sl], scal, use_adagrad)
            a_out_ref[:, sl] = jnp.where(in_b, a_new, a_out_ref[:, sl])
            ga_out_ref[:, sl] = jnp.where(in_b, ga_new, ga_out_ref[:, sl])

            @pl.when((s + 1) * rb <= r0 + _LANES)
            def _primal():             # batch s ends in these 128 rows
                pltpu.sync_copy(tcn_ref.at[q, s], tcn_st)
                w_new, gw_new = _primal_update(
                    reg_name, w_st[...], gw_st[...], _sum3(acc_st[...], h),
                    tcn_st[...], cn_ref[...], scal, use_adagrad)
                load_w(w_new)
                gw_st[...] = gw_new
                acc_st[...] = jnp.zeros_like(acc_st)

            return carry

        s_lo = r0 // rb
        s_hi = jnp.minimum((r0 + _LANES - 1) // rb, n_rb - 1)
        return jax.lax.fori_loop(s_lo, s_hi + 1, batch, carry)

    jax.lax.fori_loop(0, C // _LANES, rows128, 0)
    w_out_ref[...] = w_st[...]
    gw_out_ref[...] = gw_st[...]


def _onehot_call(cols, vals, blk, y, w, alpha, gw, ga, trn, tcn, rn, cn,
                 scalars, *, row_batches: int, loss_name: str,
                 reg_name: str, use_adagrad: bool, interpret: bool):
    """The kernel over P processors at once: cols/vals (P, n_blk, M, K),
    blk (P,) active tile of each, w/gw/cn (P, db), y/alpha/ga/trn/rn
    (P, M), tcn (P, row_batches, db), scalars (P, 5)."""
    import jax.experimental.pallas.tpu as pltpu

    P, _, M, K = cols.shape
    db = w.shape[1]
    rb = M // row_batches
    if rb < 1 or K % 8:
        raise ValueError(f"the one-hot kernel needs row_batches <= M and K "
                         f"a multiple of 8 (sparse.format.choose_k); got "
                         f"M={M}, row_batches={row_batches}, K={K}")
    h = -(-db // _LANES)
    h = -(-h // 16) * 16               # bf16 packs 16 rows per vreg
    dp = h * _LANES
    C = _LANES * max(1, min(_ROW_CHUNK, -(-M // _LANES) * _LANES,
                            _SLOT_BLOCK_BYTES // (16 * K)) // _LANES)

    def wide(x, fill):                 # (P, ..., db) -> (P, ..., h, 128)
        pad = [(0, 0)] * (x.ndim - 1) + [(0, dp - db)]
        x = jnp.pad(x.astype(jnp.float32), pad, constant_values=fill)
        return x.reshape(*x.shape[:-1], h, _LANES)

    def row(x):                        # (P, M) -> (P, 1, M), lane-dense
        return x.astype(jnp.float32).reshape(P, 1, M)

    slots = pl.BlockSpec((None, None, K, C),
                         lambda q, c, blk: (q, blk[q], 0, c))
    rows = pl.BlockSpec((None, 1, C), lambda q, c, blk: (q, 0, c))
    wvec = pl.BlockSpec((None, h, _LANES), lambda q, c, blk: (q, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(P, pl.cdiv(M, C)),
        in_specs=[slots, slots, rows, rows, rows, rows, rows, wvec, wvec,
                  pl.BlockSpec(memory_space=pl.ANY),        # tcn, in HBM
                  wvec,
                  pl.BlockSpec((None, 1, 5), lambda q, c, blk: (q, 0, 0))],
        out_specs=[wvec, wvec, rows, rows],
        scratch_shapes=[pltpu.VMEM((h, _LANES), jnp.float32),
                        pltpu.VMEM((h, _LANES), jnp.float32),
                        pltpu.VMEM((3 * h, _LANES), jnp.bfloat16),
                        pltpu.VMEM((3 * h, _LANES), jnp.float32),
                        pltpu.VMEM((h, _LANES), jnp.float32)])
    w2, gw2, a2, ga2 = pl.pallas_call(
        functools.partial(_onehot_kernel, rb=rb, n_rb=row_batches,
                          loss_name=loss_name, reg_name=reg_name,
                          use_adagrad=use_adagrad),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((P, h, _LANES), jnp.float32)] * 2
        + [jax.ShapeDtypeStruct((P, 1, M), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(blk.astype(jnp.int32), jnp.swapaxes(cols, -1, -2),
      jnp.swapaxes(vals, -1, -2), row(y), row(alpha), row(ga), row(trn),
      row(rn), wide(w, 0.0), wide(gw, 0.0), wide(tcn, 0.0), wide(cn, 1.0),
      scalars.astype(jnp.float32).reshape(P, 1, 5))
    return (w2.reshape(P, dp)[:, :db], a2.reshape(P, M),
            gw2.reshape(P, dp)[:, :db], ga2.reshape(P, M))


@functools.lru_cache(maxsize=None)
def _onehot_step(row_batches: int, loss_name: str, reg_name: str,
                 use_adagrad: bool, interpret: bool):
    """One processor's block step; under ``vmap`` the batch becomes the
    kernel's leading grid axis instead of a loop over copied slices."""
    call = functools.partial(_onehot_call, row_batches=row_batches,
                             loss_name=loss_name, reg_name=reg_name,
                             use_adagrad=use_adagrad, interpret=interpret)

    @jax.custom_batching.custom_vmap
    def step(*args):
        return tuple(o[0] for o in call(*(a[None] for a in args)))

    @step.def_vmap
    def _batched(axis_size, in_batched, *args):
        args = [a if b else jnp.broadcast_to(a, (axis_size, *a.shape))
                for a, b in zip(args, in_batched)]
        return call(*args), (True,) * 4

    return step


@functools.partial(
    jax.jit, static_argnames=("row_batches", "loss_name", "reg_name",
                              "use_adagrad", "interpret"))
def dso_sparse_block_step_pallas(cols, vals, blk, y, w, alpha, gw, ga,
                                 tile_row_nnz, tile_col_nnz, row_nnz,
                                 col_nnz, scalars, *, row_batches: int,
                                 loss_name: str, reg_name: str,
                                 interpret: bool, use_adagrad: bool = True):
    """All ``row_batches`` sequential tile steps of one active block, read
    in place from a processor's packed tiles.  cols/vals (n_blk, M, K) with
    block-local column indices, ``blk`` () the active tile; w/gw/col_nnz
    (db,); alpha/ga/y/row_nnz/tile_row_nnz (M,); ``tile_col_nnz``
    (row_batches, db); scalars = [eta, lam, m, w_lo, w_hi].

    Equivalent to scanning ``core.dso.sparse_tile_step`` over the row
    batches of ``M // row_batches`` rows, with the AdaGrad step or, for
    ``use_adagrad=False``, the plain ``eta`` step; trailing rows pass
    through.
    """
    return _onehot_step(row_batches, loss_name, reg_name, use_adagrad,
                        interpret)(
        cols, vals, blk, y, w, alpha, gw, ga, tile_row_nnz, tile_col_nnz,
        row_nnz, col_nnz, scalars)


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
