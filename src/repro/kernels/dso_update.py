"""Pallas TPU kernels for the DSO tile step (the paper's Eq. 8, tile form).

The hot loop of Algorithm 1 on TPU is the *tile step* (DESIGN.md §3): for the
active (q, sigma_r(q)) block, compute

    g_w = lam * phi'(w) * n_j / |Omega-bar_j| - X^T alpha / m      (primal)
    g_a = -l*'(-alpha) * n_i / (m |Omega_i|)  - X w / m            (dual)

then AdaGrad-scale, step, and project (App. B). Both sides read the
*pre-update* w and alpha (the simultaneous/Jacobi form used in Lemma 2), so
primal/dual order does not matter — which is exactly what makes a fused
single pass possible: the same ``(bm, bd)`` tile of X feeds both mat-vecs.

Fused single-pass kernel (``_fused_tile_kernel``) — data flow per grid step
``(mi, dj)`` over the 2-D grid (row tiles outer, column tiles inner):

          X tile (bm, bd)  ── read ONCE from HBM ──┐
                                                   ├─> col_acc[dj] += a^T X   (n_dt, bd) VMEM
          alpha (bm,1) ────────────────────────────┤      └ dj==n_dt-1 ... mi==n_mt-1: w update
          w     (1,bd) ────────────────────────────┘
                                                   └─> row_acc    += X w      (bm, 1)  VMEM
                                                          └ dj==n_dt-1: alpha update (per row tile)

    * ``row_acc`` (bm x 1) accumulates the dual mat-vec ``X w`` over the
      inner dj sweep; the last column tile finalizes the alpha-slice update.
    * ``col_acc`` (n_dt x bd) accumulates the primal mat-vec ``X^T alpha``
      across the outer mi sweep (one bd-row per column tile); the last row
      tile finalizes the w-block update.

HBM traffic per tile step: X is streamed ONCE (4*M*D bytes) instead of the
two-pass version's twice (once per kernel) — the dominant term of the
paper's (|Omega| T_u / p + T_c) T epoch cost: asymptotically 2x less
traffic as M*D grows relative to the M + D vector terms.  The per-tile
nonzero counts (n_j per column, n_i per row) are *precomputed* by the
callers (``ops.dso_tile_step`` / ``core.dso.make_grid_data``) and passed
in as vectors instead of being re-derived from X with
``(x != 0).sum(...)`` on every step of every epoch.

``_fused_block_kernel`` additionally folds the ``row_batches`` sub-scan of
``core/dso._inner_iteration`` into the kernel grid: row tiles become
*sequential* minibatch steps (the w block and its AdaGrad accumulator live
in VMEM scratch across the whole launch and are updated after every row
tile), so one launch covers the whole active block.

Sparse variant (``kernels/dso_sparse.py``) — same fused block step on the
packed block-ELL tiles of ``repro.sparse.format``, where the dense (bm, bd)
X read is replaced by the (bm, K) cols+vals arrays (K = padded max row
nnz), making the streamed bytes nnz-proportional; the gather of w and the
scatter-add of X^T alpha run as factored one-hot matmuls on the MXU:

    cols (bm, K) i32 ──┐   packed tile, read ONCE (8*bm*K B vs 4*bm*bd B)
    vals (bm, K) f32 ──┤
                       ├─> gather  sum_k vals*w_st[cols] -> X w     (bm, 1)
    w_st (1, bd) VMEM ─┤       └ alpha update per row tile
                       └─> scatter add vals*alpha at cols -> X^T a  (1, bd)
    alpha (bm, 1) ─────┘       └ w update, w_st advances (sequential)

Both variants share ``_primal_update``/``_dual_update`` below, so the
Eq.-(8) math is written once.

K-bucketed ragged layout (``sparse.format.BucketedGridData``, backends
``sparse_bucketed_jnp``/``sparse_bucketed_pallas``) — the uniform layout
above pads every tile to the GRID's max K, so on power-law feature
distributions (a few tiles 10-50x denser than the median) both the
streamed and the resident bytes are paid at the worst tile's width
everywhere.  The bucketed layout groups tiles into <= 4 power-of-two
widths; the grid's payload is ONE flat ragged buffer of K_CHUNK-wide
column chunks plus an int32 chunk lookup table, and the block step is a
SINGLE Pallas launch whose scalar-prefetched index map walks the table
(``dso_sparse.dso_bucketed_block_step_pallas``; data flow diagram there):

    cols_fl/vals_fl (p, n_chunks, mb, Kc) ── flat chunk pool, all buckets
    chunk_lut (p, p, n_kc) i32 / chunk_cnt (p, p) ── tile -> chunk indices
         └─> grid (row_batches, n_kc), PrefetchScalarGridSpec: block kc of
             row batch mi is chunk lut[kc] — the index map IS the dispatch,
             no lax.switch, one launch per block step; kc past cnt repeats
             the last live chunk and is masked in VMEM staging

so a tile step streams 8*mb*K_bucket bytes (its own width) instead of
8*mb*max-K, and the resident grid shrinks from p^2*mb*max-K to
sum_k slots_k*mb*K_k — epoch bytes track real nnz, not max-K padding.
The trajectory is identical to ``sparse_jnp`` (same statistics, same
Eq.-8 math; padding slots contribute exact zeros at every width), and
bit-identical to ``sparse_bucketed_jnp``, whose jnp twin runs the same
staged math.

The legacy two-pass kernels are kept as ``dso_tile_step_pallas_twopass``
for the fused-vs-two-pass regression test (tests/test_dso_fused.py).

Block shapes default to (256, 512) float32 — 512 KiB per X block, well under
VMEM, with the MXU-aligned 128-multiple on both axes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BM = 256  # rows per X block
DEFAULT_BD = 512  # cols per X block
_ADA_EPS = 1e-8


def _dot(a, b):
    """float32 mat-vec at full precision (a TPU may run a default-precision
    f32 dot as reduced-precision bf16 passes)."""
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _reg_grad(reg_name: str, w):
    if reg_name == "l2":
        return 2.0 * w
    if reg_name == "l1":
        return jnp.sign(w)
    raise ValueError(reg_name)


def _dual_grad(loss_name: str, a, y):
    if loss_name == "hinge":
        return -y
    if loss_name == "logistic":
        b = jnp.clip(y * a, 1e-6, 1.0 - 1e-6)
        return y * (jnp.log(b) - jnp.log1p(-b))
    if loss_name == "square":
        return a - y
    raise ValueError(loss_name)


def _project_alpha(loss_name: str, a, y):
    if loss_name == "hinge":
        return y * jnp.clip(y * a, 0.0, 1.0)
    if loss_name == "logistic":
        return y * jnp.clip(y * a, 1e-6, 1.0 - 1e-6)
    return a


def _primal_update(reg_name: str, w, gw, acc, tcn, cn, scal,
                   use_adagrad: bool = True):
    """Eq. (8) primal side + AdaGrad (or the plain eta_t step, ``gw``
    unchanged, as ``engine.update.eq8_apply``) + App. B box projection."""
    eta, lam, m = scal[0, 0], scal[0, 1], scal[0, 2]
    w_lo, w_hi = scal[0, 3], scal[0, 4]
    g_w = lam * _reg_grad(reg_name, w) * tcn / cn - acc / m
    if not use_adagrad:
        return jnp.clip(w - eta * g_w, w_lo, w_hi), gw
    gw_new = gw + g_w * g_w
    dw = eta * g_w * jax.lax.rsqrt(gw_new + _ADA_EPS)
    return jnp.clip(w - dw, w_lo, w_hi), gw_new


def _dual_update(loss_name: str, a, ga, y, acc, trn, rn, scal,
                 use_adagrad: bool = True):
    """Eq. (8) dual side + AdaGrad (or the plain eta_t step, ``ga``
    unchanged) + App. B domain projection."""
    eta, m = scal[0, 0], scal[0, 2]
    g_a = -_dual_grad(loss_name, a, y) * trn / (m * rn) - acc / m
    if not use_adagrad:
        return _project_alpha(loss_name, a + eta * g_a, y), ga
    ga_new = ga + g_a * g_a
    da = eta * g_a * jax.lax.rsqrt(ga_new + _ADA_EPS)
    return _project_alpha(loss_name, a + da, y), ga_new


# ------------------------------------------------------------------ fused --


def _fused_tile_kernel(x_ref, y_ref, w_ref, alpha_ref, gw_ref, ga_ref,
                       trn_ref, tcn_ref, rn_ref, cn_ref, scal_ref,
                       w_out_ref, a_out_ref, gw_out_ref, ga_out_ref,
                       col_acc_ref, row_acc_ref,
                       *, n_mt: int, n_dt: int, loss_name: str,
                       reg_name: str):
    """One Jacobi tile step over all of X in a single pass (X read once)."""
    mi = pl.program_id(0)   # row tiles, outer
    dj = pl.program_id(1)   # column tiles, inner

    x = x_ref[...]          # (bm, bd) — the only HBM read of this tile
    a = alpha_ref[...]      # (bm, 1), pre-update
    w = w_ref[...]          # (1, bd), pre-update

    @pl.when(mi == 0)
    def _init_col():
        col_acc_ref[pl.ds(dj, 1), :] = jnp.zeros_like(w)

    @pl.when(dj == 0)
    def _init_row():
        row_acc_ref[...] = jnp.zeros_like(a)

    col_acc_ref[pl.ds(dj, 1), :] += _dot(a.T, x)   # partial X^T alpha
    row_acc_ref[...] += _dot(x, w.T)               # partial X w

    # keep the output windows well-defined on every flush: default to the
    # pre-update values, overwritten below at the finalize steps
    w_out_ref[...] = w
    gw_out_ref[...] = gw_ref[...]
    a_out_ref[...] = a
    ga_out_ref[...] = ga_ref[...]

    @pl.when(dj == n_dt - 1)
    def _finalize_alpha():
        a_new, ga_new = _dual_update(
            loss_name, a, ga_ref[...], y_ref[...], row_acc_ref[...],
            trn_ref[...], rn_ref[...], scal_ref[...])
        a_out_ref[...] = a_new
        ga_out_ref[...] = ga_new

    @pl.when(mi == n_mt - 1)
    def _finalize_w():
        w_new, gw_new = _primal_update(
            reg_name, w, gw_ref[...], col_acc_ref[pl.ds(dj, 1), :],
            tcn_ref[...], cn_ref[...], scal_ref[...])
        w_out_ref[...] = w_new
        gw_out_ref[...] = gw_new


def _fused_block_kernel(x_ref, y_ref, w_ref, alpha_ref, gw_ref, ga_ref,
                        trn_ref, tcn_ref, rn_ref, cn_ref, scal_ref,
                        w_out_ref, a_out_ref, gw_out_ref, ga_out_ref,
                        w_st_ref, gw_st_ref, row_acc_ref,
                        *, n_mt: int, n_dt: int, loss_name: str,
                        reg_name: str):
    """Whole active block in one launch: each row tile is one *sequential*
    minibatch step (the ``row_batches`` sub-scan folded into the grid).

    The w block and its AdaGrad accumulator live in VMEM scratch across the
    launch; each row tile reads the current state (Jacobi within the tile),
    applies its primal update, and finalizes its alpha slice at the last
    column tile. Equivalent to scanning ``block_tile_step`` over row tiles.
    """
    mi = pl.program_id(0)   # row tiles = sequential minibatch steps
    dj = pl.program_id(1)   # column tiles, inner

    @pl.when(mi == 0)
    def _load_state():
        w_st_ref[pl.ds(dj, 1), :] = w_ref[...]
        gw_st_ref[pl.ds(dj, 1), :] = gw_ref[...]

    x = x_ref[...]                      # (bm, bd) — single HBM read
    a = alpha_ref[...]                  # (bm, 1)
    w = w_st_ref[pl.ds(dj, 1), :]       # state BEFORE this row tile's update

    @pl.when(dj == 0)
    def _init_row():
        row_acc_ref[...] = jnp.zeros_like(a)

    row_acc_ref[...] += _dot(x, w.T)    # dual mat-vec with pre-update w

    # primal update of this column slice from this row tile alone
    w_new, gw_new = _primal_update(
        reg_name, w, gw_st_ref[pl.ds(dj, 1), :], _dot(a.T, x),
        tcn_ref[...], cn_ref[...], scal_ref[...])
    w_st_ref[pl.ds(dj, 1), :] = w_new
    gw_st_ref[pl.ds(dj, 1), :] = gw_new
    w_out_ref[...] = w_new              # last row tile's flush is the result
    gw_out_ref[...] = gw_new

    a_out_ref[...] = a
    ga_out_ref[...] = ga_ref[...]

    @pl.when(dj == n_dt - 1)
    def _finalize_alpha():
        a_new, ga_new = _dual_update(
            loss_name, a, ga_ref[...], y_ref[...], row_acc_ref[...],
            trn_ref[...], rn_ref[...], scal_ref[...])
        a_out_ref[...] = a_new
        ga_out_ref[...] = ga_new


def _fused_call(kernel, X, y, w, alpha, gw, ga, trn, tcn, rn, cn, scalars,
                *, bm, bd, n_mt, n_dt, scratch, loss_name, reg_name,
                interpret):
    M, D = X.shape
    return pl.pallas_call(
        functools.partial(kernel, n_mt=n_mt, n_dt=n_dt, loss_name=loss_name,
                          reg_name=reg_name),
        grid=(n_mt, n_dt),
        in_specs=[
            pl.BlockSpec((bm, bd), lambda mi, dj: (mi, dj)),   # X
            pl.BlockSpec((bm, 1), lambda mi, dj: (mi, 0)),     # y
            pl.BlockSpec((1, bd), lambda mi, dj: (0, dj)),     # w
            pl.BlockSpec((bm, 1), lambda mi, dj: (mi, 0)),     # alpha
            pl.BlockSpec((1, bd), lambda mi, dj: (0, dj)),     # gw
            pl.BlockSpec((bm, 1), lambda mi, dj: (mi, 0)),     # ga
            pl.BlockSpec((bm, 1), lambda mi, dj: (mi, 0)),     # tile row nnz
            # tile col nnz: per row tile for the block kernel, total for the
            # tile kernel (callers pass a (1, 1, D) or (n_mt, 1, D) array).
            # The row-tile axis is squeezed so each block is (1, bd) over a
            # unit second-to-last dim: Mosaic refuses a (1, bd) block of an
            # (n_mt, D) array whose n_mt is neither 1 nor a multiple of 8
            pl.BlockSpec((None, 1, bd), (lambda mi, dj: (mi, 0, dj))
                         if tcn.shape[0] == n_mt
                         else (lambda mi, dj: (0, 0, dj))),
            pl.BlockSpec((bm, 1), lambda mi, dj: (mi, 0)),     # |Omega_i|
            pl.BlockSpec((1, bd), lambda mi, dj: (0, dj)),     # |Omega-bar_j|
            pl.BlockSpec((1, 5), lambda mi, dj: (0, 0)),       # scalars
        ],
        out_specs=[
            pl.BlockSpec((1, bd), lambda mi, dj: (0, dj)),     # w
            pl.BlockSpec((bm, 1), lambda mi, dj: (mi, 0)),     # alpha
            pl.BlockSpec((1, bd), lambda mi, dj: (0, dj)),     # gw
            pl.BlockSpec((bm, 1), lambda mi, dj: (mi, 0)),     # ga
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, D), jnp.float32),
            jax.ShapeDtypeStruct((M, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, D), jnp.float32),
            jax.ShapeDtypeStruct((M, 1), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(X, y, w, alpha, gw, ga, trn, tcn, rn, cn, scalars)


@functools.partial(
    jax.jit,
    static_argnames=("loss_name", "reg_name", "bm", "bd", "interpret"))
def dso_tile_step_pallas(X, y, w, alpha, gw, ga, row_nnz, col_nnz, scalars,
                         *, loss_name: str, reg_name: str,
                         bm: int = DEFAULT_BM, bd: int = DEFAULT_BD,
                         interpret: bool = False,
                         tile_row_nnz=None, tile_col_nnz=None):
    """One fused DSO tile step — X streamed ONCE. Shapes: X (M, D);
    w/gw/col_nnz (D,); alpha/ga/y/row_nnz (M,); scalars = [eta, lam, m,
    w_lo, w_hi] float32(5,). ``tile_row_nnz``/``tile_col_nnz`` are the
    per-row/per-column nonzero counts of X itself; pass precomputed values
    (core.dso.make_grid_data) to keep them off the per-step path.

    M, D must be multiples of (bm, bd) — callers pad (ops.py handles it).
    Returns (w_new, alpha_new, gw_new, ga_new); identical to the legacy
    two-pass ``dso_tile_step_pallas_twopass``.
    """
    M, D = X.shape
    assert M % bm == 0 and D % bd == 0, (M, D, bm, bd)
    n_mt, n_dt = M // bm, D // bd
    if tile_col_nnz is None:
        tile_col_nnz = (X != 0).astype(jnp.float32).sum(axis=0)
    if tile_row_nnz is None:
        tile_row_nnz = (X != 0).astype(jnp.float32).sum(axis=1)

    import jax.experimental.pallas.tpu as pltpu
    scratch = [pltpu.VMEM((n_dt, bd), jnp.float32),   # X^T alpha accumulator
               pltpu.VMEM((bm, 1), jnp.float32)]      # X w accumulator
    w2, a2, gw2, ga2 = _fused_call(
        _fused_tile_kernel, X, y.reshape(M, 1), w.reshape(1, D),
        alpha.reshape(M, 1), gw.reshape(1, D), ga.reshape(M, 1),
        tile_row_nnz.reshape(M, 1), tile_col_nnz.reshape(1, 1, D),
        row_nnz.reshape(M, 1), col_nnz.reshape(1, D), scalars.reshape(1, 5),
        bm=bm, bd=bd, n_mt=n_mt, n_dt=n_dt, scratch=scratch,
        loss_name=loss_name, reg_name=reg_name, interpret=interpret)
    return (w2.reshape(D), a2.reshape(M), gw2.reshape(D), ga2.reshape(M))


@functools.partial(
    jax.jit,
    static_argnames=("row_batches", "loss_name", "reg_name", "bd",
                     "interpret"))
def dso_block_step_pallas(X, y, w, alpha, gw, ga, tile_row_nnz, tile_col_nnz,
                          row_nnz, col_nnz, scalars, *, row_batches: int,
                          loss_name: str, reg_name: str,
                          bd: int = DEFAULT_BD, interpret: bool = False):
    """All ``row_batches`` sequential tile steps of one active block in a
    single launch. X (M, D) with M % row_batches == 0 and D % bd == 0;
    ``tile_col_nnz`` (row_batches, D) = per-column counts within each row
    tile; ``tile_row_nnz`` (M,) = per-row counts over the block width.

    Equivalent to scanning ``core.dso.block_tile_step`` over the row tiles.
    """
    M, D = X.shape
    assert M % row_batches == 0 and D % bd == 0, (M, D, row_batches, bd)
    bm = M // row_batches
    n_mt, n_dt = row_batches, D // bd

    import jax.experimental.pallas.tpu as pltpu
    scratch = [pltpu.VMEM((n_dt, bd), jnp.float32),   # travelling w state
               pltpu.VMEM((n_dt, bd), jnp.float32),   # its AdaGrad acc
               pltpu.VMEM((bm, 1), jnp.float32)]      # X w accumulator
    w2, a2, gw2, ga2 = _fused_call(
        _fused_block_kernel, X, y.reshape(M, 1), w.reshape(1, D),
        alpha.reshape(M, 1), gw.reshape(1, D), ga.reshape(M, 1),
        tile_row_nnz.reshape(M, 1), tile_col_nnz.reshape(n_mt, 1, D),
        row_nnz.reshape(M, 1), col_nnz.reshape(1, D), scalars.reshape(1, 5),
        bm=bm, bd=bd, n_mt=n_mt, n_dt=n_dt, scratch=scratch,
        loss_name=loss_name, reg_name=reg_name, interpret=interpret)
    return (w2.reshape(D), a2.reshape(M), gw2.reshape(D), ga2.reshape(M))


# -------------------------------------------------- legacy two-pass path --
# Kept for the fused-vs-two-pass regression test and benchmark: each kernel
# re-reads X from HBM (2x traffic) and re-derives the tile nonzero counts.


def _primal_kernel(x_ref, alpha_ref, w_ref, gw_ref, cn_ref, scal_ref,
                   w_out_ref, gw_out_ref, acc_ref, cnt_ref,
                   *, n_mt: int, loss_name: str, reg_name: str):
    mi = pl.program_id(1)  # inner reduction over row tiles

    @pl.when(mi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    x = x_ref[...]                      # (bm, bd)
    a = alpha_ref[...]                  # (bm, 1)
    acc_ref[...] += _dot(a.T, x)        # (1, bd) partial X^T alpha
    cnt_ref[...] += (x != 0).astype(jnp.float32).sum(axis=0, keepdims=True)

    @pl.when(mi == n_mt - 1)
    def _finalize():
        w_new, gw_new = _primal_update(
            reg_name, w_ref[...], gw_ref[...], acc_ref[...], cnt_ref[...],
            cn_ref[...], scal_ref[...])
        w_out_ref[...] = w_new
        gw_out_ref[...] = gw_new


def _dual_kernel(x_ref, w_ref, alpha_ref, ga_ref, y_ref, rn_ref, scal_ref,
                 a_out_ref, ga_out_ref, acc_ref, cnt_ref,
                 *, n_dt: int, loss_name: str, reg_name: str):
    di = pl.program_id(1)  # inner reduction over column tiles

    @pl.when(di == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    x = x_ref[...]                      # (bm, bd)
    w = w_ref[...]                      # (1, bd)
    acc_ref[...] += _dot(x, w.T)        # (bm, 1) partial X w
    cnt_ref[...] += (x != 0).astype(jnp.float32).sum(axis=1, keepdims=True)

    @pl.when(di == n_dt - 1)
    def _finalize():
        a_new, ga_new = _dual_update(
            loss_name, alpha_ref[...], ga_ref[...], y_ref[...], acc_ref[...],
            cnt_ref[...], rn_ref[...], scal_ref[...])
        a_out_ref[...] = a_new
        ga_out_ref[...] = ga_new


@functools.partial(
    jax.jit,
    static_argnames=("loss_name", "reg_name", "bm", "bd", "interpret"))
def dso_tile_step_pallas_twopass(X, y, w, alpha, gw, ga, row_nnz, col_nnz,
                                 scalars, *, loss_name: str, reg_name: str,
                                 bm: int = DEFAULT_BM, bd: int = DEFAULT_BD,
                                 interpret: bool = False):
    """Legacy two-kernel tile step (X read twice). Same contract/result as
    the fused ``dso_tile_step_pallas``."""
    M, D = X.shape
    assert M % bm == 0 and D % bd == 0, (M, D, bm, bd)
    n_mt, n_dt = M // bm, D // bd
    w2 = w.reshape(1, D)
    gw2 = gw.reshape(1, D)
    cn2 = col_nnz.reshape(1, D)
    a2 = alpha.reshape(M, 1)
    ga2 = ga.reshape(M, 1)
    y2 = y.reshape(M, 1)
    rn2 = row_nnz.reshape(M, 1)
    sc = scalars.reshape(1, 5)

    kw = dict(loss_name=loss_name, reg_name=reg_name)

    w_new, gw_new = pl.pallas_call(
        functools.partial(_primal_kernel, n_mt=n_mt, **kw),
        grid=(n_dt, n_mt),
        in_specs=[
            pl.BlockSpec((bm, bd), lambda dj, mi: (mi, dj)),   # X
            pl.BlockSpec((bm, 1), lambda dj, mi: (mi, 0)),     # alpha
            pl.BlockSpec((1, bd), lambda dj, mi: (0, dj)),     # w
            pl.BlockSpec((1, bd), lambda dj, mi: (0, dj)),     # gw
            pl.BlockSpec((1, bd), lambda dj, mi: (0, dj)),     # col_nnz
            pl.BlockSpec((1, 5), lambda dj, mi: (0, 0)),       # scalars
        ],
        out_specs=[
            pl.BlockSpec((1, bd), lambda dj, mi: (0, dj)),
            pl.BlockSpec((1, bd), lambda dj, mi: (0, dj)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, D), jnp.float32),
            jax.ShapeDtypeStruct((1, D), jnp.float32),
        ],
        # VMEM accumulators: partial X^T alpha and per-column tile counts
        scratch_shapes=_scratch_1xbd(bd),
        interpret=interpret,
    )(X, a2, w2, gw2, cn2, sc)

    a_new, ga_new = pl.pallas_call(
        functools.partial(_dual_kernel, n_dt=n_dt, **kw),
        grid=(n_mt, n_dt),
        in_specs=[
            pl.BlockSpec((bm, bd), lambda mi, dj: (mi, dj)),   # X
            pl.BlockSpec((1, bd), lambda mi, dj: (0, dj)),     # w (pre-update)
            pl.BlockSpec((bm, 1), lambda mi, dj: (mi, 0)),     # alpha
            pl.BlockSpec((bm, 1), lambda mi, dj: (mi, 0)),     # ga
            pl.BlockSpec((bm, 1), lambda mi, dj: (mi, 0)),     # y
            pl.BlockSpec((bm, 1), lambda mi, dj: (mi, 0)),     # row_nnz
            pl.BlockSpec((1, 5), lambda mi, dj: (0, 0)),       # scalars
        ],
        out_specs=[
            pl.BlockSpec((bm, 1), lambda mi, dj: (mi, 0)),
            pl.BlockSpec((bm, 1), lambda mi, dj: (mi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, 1), jnp.float32),
            jax.ShapeDtypeStruct((M, 1), jnp.float32),
        ],
        scratch_shapes=_scratch_bmx1(bm),
        interpret=interpret,
    )(X, w2, a2, ga2, y2, rn2, sc)

    return (w_new.reshape(D), a_new.reshape(M), gw_new.reshape(D),
            ga_new.reshape(M))


def _scratch_1xbd(bd):
    import jax.experimental.pallas.tpu as pltpu
    return [pltpu.VMEM((1, bd), jnp.float32), pltpu.VMEM((1, bd), jnp.float32)]


def _scratch_bmx1(bm):
    import jax.experimental.pallas.tpu as pltpu
    return [pltpu.VMEM((bm, 1), jnp.float32), pltpu.VMEM((bm, 1), jnp.float32)]
