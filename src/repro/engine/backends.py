"""TileBackend registry: one ``block_step`` contract, five implementations.

A backend is the pairing of a *layout* (how the grid's tiles are stored:
dense row shards or packed block-ELL) with a *kernel* (how the Eq.-(8)
tile steps of an active block execute: jnp ops or a Pallas kernel).  Every
backend exposes the same two hooks, so the epoch driver is written once:

  ``select_block(arrays_q, blk_id, blk_cols, db)``
      slice processor q's resident data down to the active block's payload
      (a column slice of the dense shard / the (mb, K) packed tile).

  ``block_step(meta, block, y_q, w_blk, alpha_q, gw_blk, ga_q, rn_q,
               col_nnz_blk, trn_blk, tcn_blk, eta_t, row_batches)``
      run all ``row_batches`` sequential tile steps of the active block and
      return the updated ``(w_blk, alpha_q, gw_blk, ga_q)``.

Registered backends:

  dense_jnp             — jnp mat-vec tile steps, scanned over row batches
  dense_pallas_fused    — fused single-pass Pallas tile-step kernel, one
                          launch per row batch (X streamed once per step)
  dense_pallas_block    — block-step Pallas kernel: the row-batch sub-scan
                          folded into the kernel grid, ONE launch per block
                          (falls back to the fused-kernel scan off-shape)
  sparse_jnp            — gather/scatter tile steps on block-ELL tiles
  sparse_pallas         — one-hot Pallas sparse kernel: gather and
                          scatter-add as factored one-hot matmuls on the
                          MXU, the active tile read in place
  sparse_bucketed_jnp   — one-kernel math on the K-bucketed ragged layout's
                          *flat chunk view* in plain jnp: chunk staging via
                          the tile's lut + the staged Eq.-(8) step
                          (kernels/dso_sparse.py ``_staged_step_math``)
  sparse_bucketed_pallas — the SAME staging + math as ONE scalar-prefetch
                          Pallas kernel: grid = (row_batches, n_kc), the
                          prefetched chunk lut drives the index map, no
                          ``lax.switch`` anywhere — bit-identical to
                          sparse_bucketed_jnp by construction
  sparse_bucketed_jnp_switch / sparse_bucketed_pallas_switch
                        — the legacy bucket dispatch: ``lax.switch`` over
                          the tile's bucket into the uniform-K step at that
                          bucket's packed width (kept as the comparison
                          baseline; equal to the one-kernel pair to f32
                          reduction order, not bitwise)

Bucketed payload note: the one-kernel pair streams the flat chunk view
``(cols_fl, vals_fl, chunk_lut, chunk_cnt)``; the ``_switch`` pair needs
the per-bucket rectangles + (p, p) index maps.  ``TileBackend.payload``
("flat" | "buckets") records which variant a backend consumes, and every
driver passes it to ``as_tile_data(..., bucketed_payload=...)``.  Inside
``shard_map`` (one device per processor) the active tile's scalar lut
prefetch (or, for _switch, the scalar bucket index) means only that tile's
``mb * K_bucket`` bytes stream from HBM — the layout's whole point.  Under
the single-device grid simulator's vmap the switch lowers to a select that
evaluates every branch, while the one-kernel path stays one dynamic-sliced
stream — which is why it also wins wall-clock in the simulator
(``benchmarks/dso_perf.py --bucketed-onekernel``).

Legacy ``impl`` selectors ("jnp", "pallas", "sparse", "sparse_pallas",
"auto") resolve through ``resolve_backend``, ``auto``'s kernel once the
grid is built through ``resolve_backend_for_layout``; unknown names raise
``ValueError`` listing everything registered.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.engine.update import block_tile_step, sparse_tile_step
from repro.sparse.format import (BUCKET_SKEW_THRESHOLD, ONEHOT_MAX_DB,
                                 SPARSE_DENSITY_THRESHOLD)


class TileBackend(NamedTuple):
    name: str
    layout: str             # "dense" | "sparse" | "bucketed"
    select_block: Callable  # (arrays_q, blk_id, blk_cols, db) -> block tuple
    block_step: Callable    # see module docstring
    payload: str = "flat"   # bucketed payload variant this backend consumes
                            # ("flat" chunk view | "buckets" rectangles);
                            # ignored by dense/sparse layouts


# --------------------------------------------------------------- selects --


def _dense_select(arrays_q, blk_id, blk_cols, db):
    (X_q,) = arrays_q
    mb = X_q.shape[0]
    return (jax.lax.dynamic_slice(X_q, (0, blk_cols), (mb, db)),)


def _sparse_select(arrays_q, blk_id, blk_cols, db):
    cols_q, vals_q = arrays_q
    _, mb, K = cols_q.shape
    return (jax.lax.dynamic_slice(cols_q, (blk_id, 0, 0), (1, mb, K))[0],
            jax.lax.dynamic_slice(vals_q, (blk_id, 0, 0), (1, mb, K))[0])


def _payload_select(arrays_q, blk_id, blk_cols, db):
    # the whole payload rides through to the block step with the active
    # block id added: the one-hot sparse kernel reads the tile in place
    # (the id drives its index map, so no tile is sliced out in HBM), and
    # the bucketed tile slice is width-dependent, picked by the step via
    # its lut row (flat) or its lax.switch branch (buckets)
    return tuple(arrays_q) + (blk_id,)


# ------------------------------------------------------------ block steps --


def _dense_slice(block, r0, rb):
    (X_blk,) = block
    return dict(X_tile=jax.lax.dynamic_slice(X_blk, (r0, 0),
                                             (rb, X_blk.shape[1])))


def _sparse_slice(block, r0, rb):
    cols_blk, vals_blk = block
    K = cols_blk.shape[1]
    return dict(cols=jax.lax.dynamic_slice(cols_blk, (r0, 0), (rb, K)),
                vals=jax.lax.dynamic_slice(vals_blk, (r0, 0), (rb, K)))


def _make_jnp_block_step(slice_tile, tile_step):
    """The jnp backends' shared row-batch ``lax.scan`` scaffold: slice the
    per-batch operands, run the layout's tile step (``slice_tile`` yields
    its payload kwargs), write alpha/ga back in place."""

    def step(meta, block, y_q, w_blk, alpha_q, gw_blk, ga_q, rn_q,
             col_nnz_blk, trn_blk, tcn_blk, eta_t, row_batches):
        lam, m, loss_name, reg_name, use_adagrad, w_lo, w_hi = meta
        mb = y_q.shape[0]
        db = w_blk.shape[0]
        rb = mb // row_batches

        def sub(carry, s):
            w_blk, alpha_q, gw_blk, ga_q = carry
            yt = jax.lax.dynamic_slice(y_q, (s * rb,), (rb,))
            at = jax.lax.dynamic_slice(alpha_q, (s * rb,), (rb,))
            gat = jax.lax.dynamic_slice(ga_q, (s * rb,), (rb,))
            rnt = jax.lax.dynamic_slice(rn_q, (s * rb,), (rb,))
            trn_t = jax.lax.dynamic_slice(trn_blk, (s * rb,), (rb,))
            tcn_t = jax.lax.dynamic_slice(tcn_blk, (s, 0), (1, db))[0]
            w_blk, at, gw_blk, gat = tile_step(
                **slice_tile(block, s * rb, rb), y_tile=yt, w_blk=w_blk,
                alpha_blk=at, gw_blk=gw_blk, ga_blk=gat, row_nnz_tile=rnt,
                col_nnz_blk=col_nnz_blk, eta_t=eta_t, lam=lam, m=m,
                loss_name=loss_name, reg_name=reg_name,
                use_adagrad=use_adagrad, w_lo=w_lo, w_hi=w_hi,
                tile_row_nnz=trn_t, tile_col_nnz=tcn_t)
            alpha_q = jax.lax.dynamic_update_slice(alpha_q, at, (s * rb,))
            ga_q = jax.lax.dynamic_update_slice(ga_q, gat, (s * rb,))
            return (w_blk, alpha_q, gw_blk, ga_q), None

        (w_blk, alpha_q, gw_blk, ga_q), _ = jax.lax.scan(
            sub, (w_blk, alpha_q, gw_blk, ga_q), jnp.arange(row_batches))
        return w_blk, alpha_q, gw_blk, ga_q

    return step


def _make_dense_pallas_block_step(force_scan: bool):
    def step(meta, block, y_q, w_blk, alpha_q, gw_blk, ga_q, rn_q,
             col_nnz_blk, trn_blk, tcn_blk, eta_t, row_batches):
        from repro.kernels import ops
        lam, m, loss_name, reg_name, use_adagrad, w_lo, w_hi = meta
        if not use_adagrad:
            raise NotImplementedError(
                "the fused Pallas kernels implement the AdaGrad step; use a "
                "jnp backend for use_adagrad=False")
        (X_blk,) = block
        scalars = jnp.stack([eta_t, lam, m, w_lo, w_hi]).astype(jnp.float32)
        w_blk, alpha_q, gw_blk, ga_q = ops.dso_block_step(
            X_blk, y_q, w_blk, alpha_q, gw_blk, ga_q, trn_blk, tcn_blk,
            rn_q, col_nnz_blk, scalars, row_batches=row_batches,
            loss_name=loss_name, reg_name=reg_name, force_scan=force_scan)
        return w_blk, alpha_q, gw_blk, ga_q
    return step


_dense_jnp_block_step = _make_jnp_block_step(_dense_slice, block_tile_step)
_sparse_jnp_block_step = _make_jnp_block_step(_sparse_slice,
                                              sparse_tile_step)


def _make_bucketed_block_step(sparse_block_step):
    """Bucket dispatch over any sparse-layout block step: look up the
    active tile's (bucket, slot), then ``lax.switch`` into the branch that
    slices that bucket's (mb, K_k) tile and runs the wrapped step on it.
    Branch outputs are K-independent (the updated state vectors), so the
    switch is shape-legal even though every bucket has a different width.
    """

    def step(meta, block, y_q, w_blk, alpha_q, gw_blk, ga_q, rn_q,
             col_nnz_blk, trn_blk, tcn_blk, eta_t, row_batches):
        *payload, bid_q, pos_q, blk_id = block
        n_buckets = len(payload) // 2
        bid = jax.lax.dynamic_index_in_dim(bid_q, blk_id, keepdims=False)
        pos = jax.lax.dynamic_index_in_dim(pos_q, blk_id, keepdims=False)
        operands = (pos, y_q, w_blk, alpha_q, gw_blk, ga_q, rn_q,
                    col_nnz_blk, trn_blk, tcn_blk, eta_t)

        def make_branch(k):
            cols_k, vals_k = payload[2 * k], payload[2 * k + 1]

            def branch(ops_):
                (pos, y_q, w_blk, alpha_q, gw_blk, ga_q, rn_q,
                 col_nnz_blk, trn_blk, tcn_blk, eta_t) = ops_
                _, mb, K = cols_k.shape
                # a foreign-bucket pos is clamped by dynamic_slice; the
                # garbage branch result is discarded by the switch/select
                cols_blk = jax.lax.dynamic_slice(
                    cols_k, (pos, 0, 0), (1, mb, K))[0]
                vals_blk = jax.lax.dynamic_slice(
                    vals_k, (pos, 0, 0), (1, mb, K))[0]
                return sparse_block_step(
                    meta, (cols_blk, vals_blk), y_q, w_blk, alpha_q,
                    gw_blk, ga_q, rn_q, col_nnz_blk, trn_blk, tcn_blk,
                    eta_t, row_batches)

            return branch

        return jax.lax.switch(
            bid, [make_branch(k) for k in range(n_buckets)], operands)

    return step


def _sparse_pallas_block_step(meta, block, y_q, w_blk, alpha_q, gw_blk, ga_q,
                              rn_q, col_nnz_blk, trn_blk, tcn_blk, eta_t,
                              row_batches):
    """``block`` is ``(cols_q, vals_q, blk_id)`` (the processor's payload
    and its active tile) or, from the bucket switch, one sliced
    ``(cols_blk, vals_blk)`` tile."""
    from repro.kernels import ops
    lam, m, loss_name, reg_name, use_adagrad, w_lo, w_hi = meta
    cols, vals, *blk_id = block
    scalars = jnp.stack([eta_t, lam, m, w_lo, w_hi]).astype(jnp.float32)
    return ops.dso_sparse_block_step(
        cols, vals, y_q, w_blk, alpha_q, gw_blk, ga_q, trn_blk, tcn_blk,
        rn_q, col_nnz_blk, scalars, row_batches=row_batches,
        loss_name=loss_name, reg_name=reg_name, use_adagrad=use_adagrad,
        blk_id=blk_id[0] if blk_id else None)


def _bucketed_flat_args(meta, block):
    """Shared unpacking of the flat-chunk-view payload: the processor's
    whole flat buffer plus the active tile's lut row and live-chunk count
    (dead lut slots are pre-clamped by the tiler, so downstream indexing
    needs no branching)."""
    lam, m, loss_name, reg_name, use_adagrad, w_lo, w_hi = meta
    if not use_adagrad:
        raise NotImplementedError(
            "the one-kernel bucketed backends implement the AdaGrad step; "
            "use sparse_jnp (uniform K) for use_adagrad=False")
    cols_fl, vals_fl, lut_q, cnt_q, blk_id = block
    n_kc = lut_q.shape[1]
    lut_b = jax.lax.dynamic_slice(lut_q, (blk_id, 0), (1, n_kc))[0]
    cnt_b = jax.lax.dynamic_index_in_dim(cnt_q, blk_id, keepdims=False)
    return cols_fl, vals_fl, lut_b, cnt_b, loss_name, reg_name


def _make_bucketed_flat_block_step(use_pallas: bool):
    """One-kernel bucketed block steps on the flat chunk view.  Both
    variants run the SAME staging + ``_staged_step_math``
    (kernels/dso_sparse.py) — one as a single scalar-prefetch Pallas
    launch, one as plain jnp — so their trajectories are bit-identical.
    """

    def step(meta, block, y_q, w_blk, alpha_q, gw_blk, ga_q, rn_q,
             col_nnz_blk, trn_blk, tcn_blk, eta_t, row_batches):
        lam, m, _, _, _, w_lo, w_hi = meta
        cols_fl, vals_fl, lut_b, cnt_b, loss_name, reg_name = \
            _bucketed_flat_args(meta, block)
        scalars = jnp.stack([eta_t, lam, m, w_lo, w_hi]).astype(jnp.float32)
        if use_pallas:
            from repro.kernels import ops
            fn = ops.dso_bucketed_block_step
        else:
            from repro.kernels import dso_sparse
            fn = dso_sparse.dso_bucketed_block_step_jnp
        return fn(
            cols_fl, vals_fl, lut_b, cnt_b, y_q, w_blk, alpha_q, gw_blk,
            ga_q, trn_blk, tcn_blk, rn_q, col_nnz_blk, scalars,
            row_batches=row_batches, loss_name=loss_name, reg_name=reg_name)

    return step


# ---------------------------------------------------------------- registry --

_BACKENDS: dict[str, TileBackend] = {}

#: legacy run_dso_grid / ShardedDSO ``impl`` selectors -> canonical backends
LEGACY_IMPLS = {
    "jnp": "dense_jnp",
    "pallas": "dense_pallas_block",
    "sparse": "sparse_jnp",
    "sparse_pallas": "sparse_pallas",
}


def register_backend(backend: TileBackend) -> TileBackend:
    if backend.layout not in ("dense", "sparse", "bucketed"):
        raise ValueError(f"backend layout must be dense|sparse|bucketed, "
                         f"got {backend.layout!r}")
    _BACKENDS[backend.name] = backend
    return backend


def registered_backends() -> tuple[str, ...]:
    return tuple(_BACKENDS)


def _unknown(name) -> ValueError:
    return ValueError(
        f"unknown backend/impl {name!r}: registered backends are "
        f"{sorted(_BACKENDS)} (legacy impl selectors: "
        f"{sorted(LEGACY_IMPLS)} and 'auto')")


def get_backend(name) -> TileBackend:
    """Canonical-name lookup; pass-through for ``TileBackend`` instances."""
    if isinstance(name, TileBackend):
        return name
    try:
        return _BACKENDS[name]
    except KeyError:
        raise _unknown(name) from None


def resolve_backend(impl, density: float | None = None, *,
                    k_skew: float | None = None) -> TileBackend:
    """``impl`` selector (canonical or legacy) + problem stats -> backend.

    ``auto`` picks the layout: sparse when the problem density is below
    ``sparse.format.SPARSE_DENSITY_THRESHOLD`` (the paper's datasets are
    well below it; dense synthetic ones are not); within the sparse
    regime, a per-tile-K skew (``sparse.format.tile_k_skew``) at or above
    ``BUCKET_SKEW_THRESHOLD`` upgrades to the K-bucketed ragged layout
    (power-law feature distributions, where uniform max-K padding
    dominates the packed bytes).  ``k_skew=None`` means the caller did not
    probe the skew — ``auto`` then stays on the uniform sparse layout.
    For ``auto`` the backend returned is the layout's jnp one: the
    layout's kernel is chosen from the built grid, by
    ``resolve_backend_for_layout``.  Unknown names raise ``ValueError``
    listing the registry — nothing falls through silently.
    """
    if isinstance(impl, TileBackend):
        return impl
    if impl == "auto":
        if density is None:
            raise ValueError("impl='auto' needs the problem density to pick "
                             "a layout; pass density= or a concrete backend")
        if density >= SPARSE_DENSITY_THRESHOLD:
            name = "dense_jnp"
        elif k_skew is not None and k_skew >= BUCKET_SKEW_THRESHOLD:
            name = "sparse_bucketed_jnp"
        else:
            name = "sparse_jnp"
        return _BACKENDS[name]
    if impl in LEGACY_IMPLS:
        return _BACKENDS[LEGACY_IMPLS[impl]]
    return get_backend(impl)


#: kernel selector x data layout -> canonical backend
_LAYOUT_KERNELS = {
    "jnp": {"dense": "dense_jnp", "sparse": "sparse_jnp",
            "bucketed": "sparse_bucketed_jnp"},
    "pallas": {"dense": "dense_pallas_block", "sparse": "sparse_pallas",
               "bucketed": "sparse_bucketed_pallas"},
}


def _auto_kernel(layout: str, db: int) -> str:
    """``auto``'s kernel for a built grid: on the uniform sparse layout the
    one-hot Pallas kernel where the computation runs on a TPU and the block
    is at most ``ONEHOT_MAX_DB`` columns wide, else XLA's gather and
    scatter-add; the jnp kernel of the other layouts."""
    if layout == "sparse" and db <= ONEHOT_MAX_DB:
        from repro.kernels import ops
        if ops._on_tpu():
            return "sparse_pallas"
    return _LAYOUT_KERNELS["jnp"][layout]


def resolve_backend_for_layout(impl, layout: str, db: int) -> TileBackend:
    """Backend for built grid data: its layout is fixed and ``db`` is its
    block width (columns per processor).

    ``auto`` chooses the layout's kernel here, and only here
    (``_auto_kernel``: platform and ``db``).  The legacy kernel selectors
    "jnp"/"pallas" pick the layout's backend of that kernel; canonical
    names must match the data's layout (a dense grid cannot run a sparse
    backend and vice versa).
    """
    if not isinstance(impl, TileBackend):
        if impl == "auto":
            return _BACKENDS[_auto_kernel(layout, db)]
        if impl in ("jnp", "pallas"):
            return _BACKENDS[_LAYOUT_KERNELS[impl][layout]]
    backend = resolve_backend(impl)
    if backend.layout != layout:
        raise ValueError(
            f"backend {backend.name!r} has layout {backend.layout!r} but the "
            f"grid data is {layout!r}; the layout is fixed by the data's "
            f"type — pass a {layout} backend or the kernel selector "
            f"'jnp'/'pallas'")
    return backend


register_backend(TileBackend("dense_jnp", "dense", _dense_select,
                             _dense_jnp_block_step))
register_backend(TileBackend("dense_pallas_fused", "dense", _dense_select,
                             _make_dense_pallas_block_step(force_scan=True)))
register_backend(TileBackend("dense_pallas_block", "dense", _dense_select,
                             _make_dense_pallas_block_step(force_scan=False)))
register_backend(TileBackend("sparse_jnp", "sparse", _sparse_select,
                             _sparse_jnp_block_step))
register_backend(TileBackend("sparse_pallas", "sparse", _payload_select,
                             _sparse_pallas_block_step))
register_backend(TileBackend(
    "sparse_bucketed_jnp", "bucketed", _payload_select,
    _make_bucketed_flat_block_step(use_pallas=False)))
register_backend(TileBackend(
    "sparse_bucketed_pallas", "bucketed", _payload_select,
    _make_bucketed_flat_block_step(use_pallas=True)))
register_backend(TileBackend(
    "sparse_bucketed_jnp_switch", "bucketed", _payload_select,
    _make_bucketed_block_step(_sparse_jnp_block_step), payload="buckets"))
register_backend(TileBackend(
    "sparse_bucketed_pallas_switch", "bucketed", _payload_select,
    _make_bucketed_block_step(_sparse_pallas_block_step), payload="buckets"))
