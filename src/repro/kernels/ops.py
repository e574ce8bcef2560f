"""Public jit'd wrappers for the Pallas kernels (padding + dispatch).

The kernels compile (Mosaic) where the computation runs on a TPU and run in
the Pallas interpreter on every other platform; a caller may still pass
``interpret=True`` explicitly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import dso_update, ssd_scan as _ssd, swa_attention as _swa


def _platform() -> str:
    """Platform the next computation runs on: the ``jax.default_device``
    in effect (a ``with jax.default_device(cpu):`` block on a TPU host runs
    on the CPU), else the default backend."""
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.default_backend()
    return dev if isinstance(dev, str) else dev.platform


def _on_tpu() -> bool:
    return _platform() == "tpu"


def _resolve_interpret(interpret: bool | None) -> bool:
    """``interpret=None`` -> compiled (Mosaic) where the computation runs on
    a TPU, the Pallas interpreter everywhere else.  Every kernel wrapper
    resolves through here so the default is pinned in one place; nothing
    but an explicit ``interpret=`` argument overrides the platform."""
    if interpret is not None:
        return interpret
    return not _on_tpu()


def _pad_axis(x, axis: int, mult: int):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


def dso_tile_step(X, y, w, alpha, gw, ga, row_nnz, col_nnz, scalars, *,
                  loss_name: str, reg_name: str, bm: int | None = None,
                  bd: int | None = None, interpret: bool | None = None,
                  tile_row_nnz=None, tile_col_nnz=None, twopass: bool = False):
    """Padded wrapper around kernels/dso_update.py. Same contract, any M, D.

    ``tile_row_nnz``/``tile_col_nnz`` are the per-row/per-column nonzero
    counts of X (static sparsity statistics); pass precomputed values to
    keep them off the per-step path, else they are derived here (once,
    outside the kernel). ``twopass=True`` selects the legacy two-kernel
    path (X read twice) for regression/benchmark comparison.
    """
    interpret = _resolve_interpret(interpret)
    assert not (twopass and (tile_row_nnz is not None
                             or tile_col_nnz is not None)), \
        "the two-pass path derives tile counts in-kernel; stats would be " \
        "silently ignored"
    M, D = X.shape
    bm = bm or min(dso_update.DEFAULT_BM, max(8, M))
    bd = bd or min(dso_update.DEFAULT_BD, max(128, D))
    Xp, _ = _pad_axis(X, 0, bm)
    Xp, _ = _pad_axis(Xp, 1, bd)
    yp, _ = _pad_axis(y, 0, bm)
    # padded rows/cols must not divide by zero: nnz counts clamped to 1
    rnp = jnp.concatenate([row_nnz, jnp.ones(Xp.shape[0] - M, row_nnz.dtype)])
    cnp = jnp.concatenate([col_nnz, jnp.ones(Xp.shape[1] - D, col_nnz.dtype)])
    wp, _ = _pad_axis(w, 0, bd)
    gwp, _ = _pad_axis(gw, 0, bd)
    ap, _ = _pad_axis(alpha, 0, bm)
    gap, _ = _pad_axis(ga, 0, bm)
    if twopass:
        w2, a2, gw2, ga2 = dso_update.dso_tile_step_pallas_twopass(
            Xp, yp, wp, ap, gwp, gap, rnp, cnp, scalars,
            loss_name=loss_name, reg_name=reg_name, bm=bm, bd=bd,
            interpret=interpret)
        return w2[:D], a2[:M], gw2[:D], ga2[:M]
    if tile_row_nnz is None:
        tile_row_nnz = (X != 0).astype(jnp.float32).sum(axis=1)
    if tile_col_nnz is None:
        tile_col_nnz = (X != 0).astype(jnp.float32).sum(axis=0)
    # padded rows/cols have zero tile counts -> their updates are no-ops
    trnp, _ = _pad_axis(tile_row_nnz.astype(jnp.float32), 0, bm)
    tcnp, _ = _pad_axis(tile_col_nnz.astype(jnp.float32), 0, bd)
    w2, a2, gw2, ga2 = dso_update.dso_tile_step_pallas(
        Xp, yp, wp, ap, gwp, gap, rnp, cnp, scalars,
        loss_name=loss_name, reg_name=reg_name, bm=bm, bd=bd,
        interpret=interpret, tile_row_nnz=trnp, tile_col_nnz=tcnp)
    return w2[:D], a2[:M], gw2[:D], ga2[:M]


# largest X block a single block-kernel launch may keep resident in VMEM
# (conservative slice of the ~16 MB budget; scratch needs room too)
_SINGLE_LAUNCH_BYTES = 4 << 20


def dso_block_step(X, y, w, alpha, gw, ga, tile_row_nnz, tile_col_nnz,
                   row_nnz, col_nnz, scalars, *, row_batches: int,
                   loss_name: str, reg_name: str, bd: int | None = None,
                   interpret: bool | None = None, force_scan: bool = False):
    """All ``row_batches`` sequential tile steps of an active block.

    Matches the semantics of scanning ``core.dso.block_tile_step`` over
    ``row_batches`` row tiles of ``M // row_batches`` rows each: trailing
    rows beyond ``row_batches * (M // row_batches)`` are left untouched
    (exactly like the sub-scan's truncation). ``tile_col_nnz`` has shape
    (row_batches, D); ``tile_row_nnz`` (M,).

    Fast path: ONE ``dso_block_step_pallas`` launch covering the whole
    block. Its row-tile height bm = M // row_batches is not padded
    (padding would move rows across sequential-update boundaries), so on a
    real TPU (interpret=False) the fast path requires bm sublane-aligned
    (bm % 8 == 0) and the (bm, bd) X block within the VMEM budget; other
    shapes fall back to a ``lax.scan`` of the fused ``dso_tile_step``
    kernel per row batch — still one X read per tile step, just one
    launch per batch. ``force_scan`` selects the fallback explicitly
    (used by tests to exercise it in interpret mode).
    """
    interpret = _resolve_interpret(interpret)
    M, D = X.shape
    bd = bd or min(dso_update.DEFAULT_BD, max(128, D))
    rb = M // row_batches
    Mk = rb * row_batches
    # VMEM for a single launch: the (rb, bd) X block plus the kernel's
    # (n_dt, bd) x2 travelling w-state scratch (8 bytes per padded column)
    Dp = -(-D // bd) * bd
    single_launch = not force_scan and (
        interpret or (rb % 8 == 0
                      and rb * bd * 4 + 8 * Dp <= _SINGLE_LAUNCH_BYTES))

    if single_launch:
        Xk = X[:Mk]
        Xp, _ = _pad_axis(Xk, 1, bd)
        cnp = jnp.concatenate([col_nnz,
                               jnp.ones(Xp.shape[1] - D, col_nnz.dtype)])
        wp, _ = _pad_axis(w, 0, bd)
        gwp, _ = _pad_axis(gw, 0, bd)
        tcnp, _ = _pad_axis(tile_col_nnz.astype(jnp.float32), 1, bd)
        w2, a2, gw2, ga2 = dso_update.dso_block_step_pallas(
            Xp, y[:Mk], wp, alpha[:Mk], gwp, ga[:Mk],
            tile_row_nnz[:Mk].astype(jnp.float32), tcnp, row_nnz[:Mk], cnp,
            scalars, row_batches=row_batches, loss_name=loss_name,
            reg_name=reg_name, bd=bd, interpret=interpret)
    else:
        # fallback: fused tile-step kernel per row batch (it pads and
        # row-tiles internally, so any rb works on TPU). Mirrors the jnp
        # sub-scan in core/dso._inner_iteration — that path is the
        # reference these sequencing/truncation semantics must match
        # (pinned by test_block_step_scan_fallback_matches_single_launch)
        trn = tile_row_nnz.astype(jnp.float32)
        tcn = tile_col_nnz.astype(jnp.float32)

        def sub(carry, s):
            w_c, a_c, gw_c, ga_c = carry
            sl = s * rb
            Xt = jax.lax.dynamic_slice(X, (sl, 0), (rb, D))
            yt = jax.lax.dynamic_slice(y, (sl,), (rb,))
            at = jax.lax.dynamic_slice(a_c, (sl,), (rb,))
            gat = jax.lax.dynamic_slice(ga_c, (sl,), (rb,))
            rnt = jax.lax.dynamic_slice(row_nnz, (sl,), (rb,))
            trnt = jax.lax.dynamic_slice(trn, (sl,), (rb,))
            tcnt = jax.lax.dynamic_slice(tcn, (s, 0), (1, D))[0]
            w_c, at, gw_c, gat = dso_tile_step(
                Xt, yt, w_c, at, gw_c, gat, rnt, col_nnz, scalars,
                loss_name=loss_name, reg_name=reg_name, bd=bd,
                interpret=interpret, tile_row_nnz=trnt, tile_col_nnz=tcnt)
            a_c = jax.lax.dynamic_update_slice(a_c, at, (sl,))
            ga_c = jax.lax.dynamic_update_slice(ga_c, gat, (sl,))
            return (w_c, a_c, gw_c, ga_c), None

        (w2, a2, gw2, ga2), _ = jax.lax.scan(
            sub, (w, alpha, gw, ga), jnp.arange(row_batches))
        return w2, a2, gw2, ga2

    if Mk < M:  # truncated trailing rows pass through unchanged
        a2 = jnp.concatenate([a2, alpha[Mk:]])
        ga2 = jnp.concatenate([ga2, ga[Mk:]])
    return w2[:D], a2, gw2[:D], ga2


def mosaic_sparse_gather_error() -> str | None:
    """Probe the platform the next computation runs on (``_platform``) for
    the bucketed sparse kernel's gating ops (2-D gather + scatter-add; the
    uniform kernel needs neither).  Returns
    ``None`` when it lowers them, else the lowering error string — the ROADMAP
    "Mosaic-native scatter/gather" seam: fall back LOUDLY instead of
    surfacing an opaque Mosaic error from inside the real kernel.

    The probe result is cached *per platform name*, not per process: test
    harnesses (and multi-backend processes) can switch the default backend
    under a running JAX, and a probe verdict for ``cpu`` must not be served
    for ``tpu`` or vice versa.
    """
    return _mosaic_sparse_gather_error(_platform())


@functools.lru_cache(maxsize=None)
def _mosaic_sparse_gather_error(platform: str) -> str | None:
    """Run the probe on ``platform`` (assumed to be ``_platform()`` — the
    cache key merely scopes the verdict).

    Compiles (and runs) a minimal Pallas kernel exercising exactly what
    the bucketed kernel of ``kernels/dso_sparse.py`` needs beyond the dense
    kernels: a 2-D gather from a VMEM vector and a scatter-add back into
    it.
    """
    from jax.experimental import pallas as pl

    def probe(cols_ref, w_ref, o_ref):
        cols = cols_ref[...]                       # (8, 8) int32
        g = jnp.take(w_ref[...][0], cols, axis=0)  # 2-D gather
        o_ref[...] = jnp.zeros_like(w_ref[...]) \
            .at[0, cols.reshape(-1)].add(g.reshape(-1))   # scatter-add

    try:
        out = pl.pallas_call(
            probe, out_shape=jax.ShapeDtypeStruct((1, 128), jnp.float32),
            interpret=False,
        )(jnp.zeros((8, 8), jnp.int32), jnp.zeros((1, 128), jnp.float32))
        jax.block_until_ready(out)
        return None
    except Exception as e:  # any lowering/compile failure gates the kernel
        return f"{type(e).__name__}: {e}"


def dso_sparse_block_step(cols, vals, y, w, alpha, gw, ga, tile_row_nnz,
                          tile_col_nnz, row_nnz, col_nnz, scalars, *,
                          row_batches: int, loss_name: str, reg_name: str,
                          use_adagrad: bool = True, blk_id=None,
                          interpret: bool | None = None):
    """Sparse (block-ELL) counterpart of ``dso_block_step``: all
    ``row_batches`` sequential tile steps of an active block, run by the
    one-hot Pallas kernel (kernels/dso_sparse.py).

    ``cols``/``vals`` are one packed (M, K) tile, or with ``blk_id`` a
    processor's whole (n_blk, M, K) payload, which the kernel then reads
    tile ``blk_id`` of in place.  Same truncation semantics as the dense
    path: trailing rows beyond ``row_batches * (M // row_batches)`` pass
    through unchanged.  ``use_adagrad=False`` takes the plain ``eta`` step
    of ``engine.update.eq8_apply`` (gw and ga unchanged).
    ``interpret=None`` compiles (Mosaic) on a TPU and interprets elsewhere,
    like the dense wrappers.
    """
    interpret = _resolve_interpret(interpret)
    from repro.kernels import dso_sparse
    if blk_id is None:
        cols, vals, blk_id = cols[None], vals[None], 0
    return dso_sparse.dso_sparse_block_step_pallas(
        cols, vals, jnp.asarray(blk_id, jnp.int32), y, w, alpha, gw, ga,
        tile_row_nnz, tile_col_nnz, row_nnz, col_nnz, scalars,
        row_batches=row_batches, loss_name=loss_name, reg_name=reg_name,
        use_adagrad=use_adagrad, interpret=interpret)


def dso_bucketed_block_step(cols_fl, vals_fl, lut, cnt, y, w, alpha, gw, ga,
                            tile_row_nnz, tile_col_nnz, row_nnz, col_nnz,
                            scalars, *, row_batches: int, loss_name: str,
                            reg_name: str, interpret: bool | None = None):
    """One-kernel K-bucketed counterpart of ``dso_sparse_block_step``: all
    ``row_batches`` sequential tile steps of an active block streamed from
    the flat chunk view (kernels/dso_sparse.py scalar-prefetch kernel).

    ``cols_fl``/``vals_fl`` (n_chunks, M, K_CHUNK) are the processor's
    whole flat buffer; ``lut`` (n_kc,)/``cnt`` () select this tile's
    chunks.  Same truncation and interpret resolution as the uniform-K
    sparse wrapper; compiled, it is refused with a ``ValueError`` where the
    ``mosaic_sparse_gather_error`` probe fails (its kernel still gathers).
    """
    interpret = _resolve_interpret(interpret)
    if not interpret:
        err = mosaic_sparse_gather_error()
        if err is not None:
            raise ValueError(
                f"bucketed one-kernel Pallas backend requested compiled "
                f"(interpret=False) but the {_platform()!r} "
                f"backend cannot lower its scatter-add / 2-D gather "
                f"(probe failed: {err.splitlines()[0]}); use the "
                f"'sparse_bucketed_jnp' backend (bit-identical math "
                f"through XLA) or pass interpret=True for the Pallas "
                f"interpreter")
    from repro.kernels import dso_sparse
    M = y.shape[0]
    rb = M // row_batches
    Mk = rb * row_batches
    w2, a2, gw2, ga2 = dso_sparse.dso_bucketed_block_step_pallas(
        cols_fl[:, :Mk], vals_fl[:, :Mk], lut, cnt, y[:Mk], w, alpha[:Mk],
        gw, ga[:Mk], tile_row_nnz[:Mk], tile_col_nnz, row_nnz[:Mk], col_nnz,
        scalars, row_batches=row_batches, loss_name=loss_name,
        reg_name=reg_name, interpret=interpret)
    if Mk < M:  # truncated trailing rows pass through unchanged
        a2 = jnp.concatenate([a2, alpha[Mk:]])
        ga2 = jnp.concatenate([ga2, ga[Mk:]])
    return w2, a2, gw2, ga2


def swa_attention(q, k, v, *, window: int, causal: bool = True,
                  q_offset: int = 0, bq: int | None = None,
                  bk: int | None = None, interpret: bool | None = None):
    """Padded wrapper around kernels/swa_attention.py."""
    interpret = _resolve_interpret(interpret)
    B, Hq, Tq, Dh = q.shape
    Tk = k.shape[2]
    bq = bq or min(_swa.DEFAULT_BQ, max(8, Tq))
    bk = bk or min(_swa.DEFAULT_BK, max(8, Tk))
    qp, _ = _pad_axis(q, 2, bq)
    kp, _ = _pad_axis(k, 2, bk)
    vp, _ = _pad_axis(v, 2, bk)
    # padded keys must never be attended: they sit at positions >= Tk, and
    # every real query has position <= q_offset + Tq - 1 < padded positions
    # only when causal; for safety we also rely on window masking for pads
    # beyond the last real key (kpos > qpos always for pads under causal).
    out = _swa.swa_attention(qp, kp, vp, window=window, causal=causal,
                             q_offset=q_offset, bq=bq, bk=bk,
                             interpret=interpret)
    return out[:, :, :Tq]


def ssd_scan(x, dt, A, B, C, *, chunk: int | None = None,
             interpret: bool | None = None):
    """Padded wrapper around kernels/ssd_scan.py."""
    interpret = _resolve_interpret(interpret)
    b, t, h, dh = x.shape
    chunk = chunk or min(_ssd.DEFAULT_CHUNK, max(8, t))
    xp, _ = _pad_axis(x, 1, chunk)
    dtp, _ = _pad_axis(dt, 1, chunk)  # pad dt with 0: zero step = no effect
    Bp, _ = _pad_axis(B, 1, chunk)
    Cp, _ = _pad_axis(C, 1, chunk)
    y = _ssd.ssd_scan(xp, dtp, A, Bp, Cp, chunk=chunk, interpret=interpret)
    return y[:, :t]
