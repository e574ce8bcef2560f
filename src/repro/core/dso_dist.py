"""Distributed DSO: Algorithm 1 on a ring of JAX devices.

``shard_map`` over a 1-D mesh axis ``"dso"`` of p devices. Each device is one
of the paper's processors:

  resident  : its row-shard of X (dense or block-ELL), labels, alpha-shard,
              dual AdaGrad acc.
  travelling: one w-block + its primal AdaGrad acc, moved after every inner
              iteration.  Under the cyclic schedule the move is a
              ``jax.lax.ppermute`` ring step — this *is* the paper's bulk
              synchronization, expressed as an XLA ``collective-permute``
              (overlappable with compute).

The ring is a double-buffered pipeline by default (``overlap=True``): the
travelling ``(w, gw)`` pair is fused into ONE stacked ppermute buffer (one
rendezvous per inner iteration instead of two), and the scan carry holds a
one-slot *staged* prefetch — the next block's statistic/payload slices
(``engine.driver.stage_block``), which depend only on the block id, are
computed while the current shift is in flight, so the transfer sits off
the critical path.  The consumed update is unchanged
(``engine.driver.staged_step`` runs exactly ``inner_iteration``'s ops), so
trajectories are bit-identical to the ``overlap=False`` serial-shift path.

General permutation schedules ("random"/"lpt"/"fixed") route point-to-point
by default (``comm="p2p"``): the chunk's host-side permutations and their
inverses compile into static ``ppermute`` source→target pairs — the block
each device needs next is fetched from exactly the device holding it, O(db)
bytes per device per step instead of the O(p·db) legacy
``all_gather``+select path (kept under ``comm="allgather"``; identical
values either way, pinned bitwise by tests).

Under every schedule only w (d/p numbers per device per inner iteration)
is ever communicated; alpha and X never move — exactly the paper's
communication pattern, giving the (|Omega| T_u / p + T_c) T epoch cost of
Theorem 1.

The math is identical to ``dso.run_dso_grid`` (the engine's one
``inner_iteration``, any registered tile backend); tests assert
bit-equality between the two for every backend x schedule combination.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.saddle import Problem, duality_gap, primal_objective
from repro.engine.backends import get_backend
from repro.engine.data import (DSOState, as_tile_data, check_tile_stats,
                               eta_schedule, init_state, prob_meta,
                               tile_dims)
from repro.engine.driver import (TELEMETRY_FIELDS, inner_iteration,
                                 resolve_backend_and_build, stage_block,
                                 staged_step, telemetry_row,
                                 warn_ragged_eval)
from repro.engine.schedules import get_schedule


def make_dso_mesh(p: int | None = None) -> Mesh:
    devs = np.array(jax.devices())
    p = p or len(devs)
    if len(devs) < p:
        raise ValueError(f"need {p} devices, have {len(devs)}")
    return jax.sharding.Mesh(devs[:p], ("dso",))


def _epoch_shardmap(mesh: Mesh, p: int, db: int, loss_name: str,
                    reg_name: str, use_adagrad: bool, row_batches: int,
                    *, backend_name: str = "dense_jnp", ring: bool = True,
                    n_data: int | None = None, overlap: bool = True,
                    telemetry: bool = False):
    """Builds the jitted sharded multi-epoch function for a fixed problem
    shape: ``etas`` (one step size per epoch) and ``perms`` (the schedule's
    (n, p, p) block permutations) drive a ``lax.scan`` over epochs INSIDE
    the shard_map, and the travelling/resident state (w, gw, alpha, ga) is
    donated — epoch state updates in place, with no per-epoch host
    dispatch.

    ``ring=True`` (cyclic schedule): the w-block moves to the ring
    neighbour by ``ppermute`` and ``perms`` is ignored (the owner map is
    sigma_r).  With ``overlap=True`` (default) the ring is the
    double-buffered pipeline: ``(w, gw)`` travel as ONE stacked ppermute
    buffer and the carry holds the staged prefetch of the next block's
    slices (``stage_block``), which depend only on the block id and so
    overlap with the shift in the XLA schedule; ``overlap=False`` keeps
    the legacy serial-shift body (two ppermutes on the critical path) as
    the benchmark baseline.  Both consume identical updates — trajectories
    are bit-identical.

    ``ring=False``: the general-permutation all-gather path — blocks move
    by all-gather + dynamic select, and the epoch ends by restoring the
    device-q-holds-block-q invariant.  (The p2p alternative is
    ``_epoch_shardmap_p2p``, traced per chunk from the host permutations.)

    ``telemetry=True`` adds the device-resident telemetry lane: every body
    also accumulates this device's per-(epoch, inner iteration)
    ``engine.driver.TELEMETRY_FIELDS`` rows and the function returns a
    fifth output stitched across the mesh to (n, p, p, F) — the SAME
    [epoch, r, worker, field] layout the grid driver's
    ``run_epochs_telemetry`` emits, so grid and sharded telemetry agree
    exactly.  The rows only read before/after values: trajectories are
    bit-identical with telemetry on or off.
    """
    backend = get_backend(backend_name)
    if n_data is None:
        # the bucketed layout's payload length is data-dependent (two
        # arrays per K-bucket + the index maps) — callers pass it in
        n_data = 2 if backend.layout == "sparse" else 1

    def epochs_body(*args):
        arrays = args[:n_data]
        (yq, rnq, tcnq, trnq, col_nnz, w_blk, gw_blk, alpha_q, ga_q,
         etas, perms, lam, m, w_lo, w_hi) = args[n_data:]
        # Inside shard_map: per-device views with a leading axis of 1.
        arrays_q = tuple(a[0] for a in arrays)
        q = jax.lax.axis_index("dso")
        yq, rnq = yq[0], rnq[0]
        tcnq, trnq = tcnq[0], trnq[0]
        w_blk, gw_blk = w_blk[0], gw_blk[0]
        alpha_q, ga_q = alpha_q[0], ga_q[0]
        meta = (lam, m, loss_name, reg_name, use_adagrad, w_lo, w_hi)
        ring_perm = [(i, (i - 1) % p) for i in range(p)]
        qs = jnp.arange(p, dtype=jnp.int32)

        def step_block(blk_id, w_b, gw_b, alpha_q, ga_q, eta_t):
            return inner_iteration(backend, meta, col_nnz, blk_id, w_b,
                                   gw_b, alpha_q, ga_q, arrays_q, yq, rnq,
                                   tcnq, trnq, eta_t, row_batches)

        def stage(blk_id):
            return stage_block(backend, col_nnz, blk_id, arrays_q, yq,
                               tcnq, trnq, row_batches, db)

        mb = yq.shape[0]
        n_f = len(TELEMETRY_FIELDS)

        def tel(tbuf, r, trn_blk, w_old, w_new, a_old, a_new, gw_new,
                ga_new):
            # this device's telemetry row for inner iteration r — only
            # traced when telemetry is on (a static Python flag)
            return tbuf.at[r].set(telemetry_row(w_old, w_new, a_old, a_new,
                                                gw_new, ga_new, trn_blk))

        def trn_of(blk_id):
            return jax.lax.dynamic_slice(trnq, (blk_id, 0), (1, mb))[0]

        def tbuf0():
            return jnp.zeros((p, n_f), jnp.float32)

        def cyclic_epoch(carry, xs):
            eta_t, _ = xs
            if telemetry:
                carry = carry + (tbuf0(),)

            def inner(r, c):
                w_blk, gw_blk, alpha_q, ga_q = c[:4]
                blk_id = (q + r) % p                       # sigma(q, r)
                w_new, a_new, gw_new, ga_new = step_block(
                    blk_id, w_blk, gw_blk, alpha_q, ga_q, eta_t)
                out = ()
                if telemetry:
                    out = (tel(c[4], r, trn_of(blk_id), w_blk, w_new,
                               alpha_q, a_new, gw_new, ga_new),)
                # bulk synchronization: pass the block to the ring neighbour
                w_new, gw_new = jax.lax.ppermute((w_new, gw_new), "dso",
                                                 ring_perm)
                return (w_new, gw_new, a_new, ga_new) + out

            carry = jax.lax.fori_loop(0, p, inner, carry)
            return ((carry[:4], carry[4]) if telemetry else (carry, None))

        def cyclic_epoch_pipelined(carry, xs):
            # Double-buffered ring: the carry threads a one-slot staged
            # prefetch of the NEXT block's slices alongside the travelling
            # pair.  The staged slices depend only on the block id — not on
            # the ppermute result — so the latency-hiding scheduler runs
            # them under the in-flight shift; and (w, gw) cross the ring as
            # ONE stacked buffer: one rendezvous per inner iteration
            # instead of two.  The consumed block is always sigma(q, r),
            # exactly the serial-shift driver's — bit-identical trajectory.
            eta_t, _ = xs
            if telemetry:
                carry = carry + (tbuf0(),)

            def inner(r, c):
                w_blk, gw_blk, alpha_q, ga_q, staged = c[:5]
                w_new, a_new, gw_new, ga_new = staged_step(
                    backend, meta, staged, w_blk, gw_blk, alpha_q, ga_q,
                    arrays_q, yq, rnq, eta_t, row_batches)
                out = ()
                if telemetry:
                    # staged[2] is the active tile's row-nnz slice — the
                    # prefetched statistic doubles as the telemetry input
                    out = (tel(c[5], r, staged[2], w_blk, w_new, alpha_q,
                               a_new, gw_new, ga_new),)
                buf = jax.lax.ppermute(jnp.stack([w_new, gw_new]), "dso",
                                       ring_perm)
                staged = stage((q + r + 1) % p)   # prefetch sigma(q, r+1)
                return (buf[0], buf[1], a_new, ga_new, staged) + out

            carry = jax.lax.fori_loop(0, p, inner, carry)
            return ((carry[:5], carry[5]) if telemetry else (carry, None))

        def shuffle_epoch(carry, xs):
            eta_t, perm_e = xs
            if telemetry:
                carry = carry + (tbuf0(),)
            # own[r] = holder map BEFORE inner iteration r (devices hold
            # their own block at epoch start); own[p] = after the last one
            own = jnp.concatenate([qs[None, :], perm_e.astype(jnp.int32)],
                                  axis=0)

            def fetch(c, r_next):
                # the block this device needs before inner iteration
                # r_next — or its home block q when r_next == p (the
                # end-of-epoch restore)
                w_blk, gw_blk = c
                w_all = jax.lax.all_gather(w_blk, "dso")
                gw_all = jax.lax.all_gather(gw_blk, "dso")
                inv = jnp.argsort(own[r_next])     # block -> holder device
                want = jnp.where(r_next < p, perm_e[r_next % p, q], q)
                return w_all[inv[want]], gw_all[inv[want]]

            def inner(r, c):
                w_blk, gw_blk, alpha_q, ga_q = c[:4]
                w_blk, gw_blk = fetch((w_blk, gw_blk), r)
                blk_id = perm_e[r, q]
                w_new, a_new, gw_new, ga_new = step_block(
                    blk_id, w_blk, gw_blk, alpha_q, ga_q, eta_t)
                out = ()
                if telemetry:
                    out = (tel(c[4], r, trn_of(blk_id), w_blk, w_new,
                               alpha_q, a_new, gw_new, ga_new),)
                return (w_new, gw_new, a_new, ga_new) + out

            carry = jax.lax.fori_loop(0, p, inner, carry)
            # restore the epoch-start invariant: device q holds block q
            w_blk, gw_blk, alpha_q, ga_q = carry[:4]
            w_blk, gw_blk = fetch((w_blk, gw_blk), jnp.int32(p))
            out = (w_blk, gw_blk, alpha_q, ga_q)
            return ((out, carry[4]) if telemetry else (out, None))

        if ring and overlap:
            # the staged slot threads ACROSS epochs: the last iteration of
            # epoch e prefetches sigma(q, p) = q — exactly epoch e+1's
            # first block — so one stage(q) primes the whole chunk
            carry0 = (w_blk, gw_blk, alpha_q, ga_q, stage(q))
            (w_blk, gw_blk, alpha_q, ga_q, _), tbufs = jax.lax.scan(
                cyclic_epoch_pipelined, carry0, (etas, perms))
        else:
            epoch = cyclic_epoch if ring else shuffle_epoch
            (w_blk, gw_blk, alpha_q, ga_q), tbufs = jax.lax.scan(
                epoch, (w_blk, gw_blk, alpha_q, ga_q), (etas, perms))
        out = (w_blk[None], gw_blk[None], alpha_q[None], ga_q[None])
        if telemetry:
            # (n, p, 1, F) per device; stitched to (n, p, p, F) on the
            # worker axis by the out spec — the grid driver's layout
            out = out + (tbufs[:, :, None, :],)
        return out

    out_specs = (P("dso"), P("dso"), P("dso"), P("dso"))
    if telemetry:
        out_specs = out_specs + (P(None, None, "dso"),)
    sharded = jax.shard_map(
        epochs_body, mesh=mesh,
        in_specs=(P("dso"),) * (n_data + 4) + (P(None),)
        + (P("dso"),) * 4 + (P(), P(), P(), P(), P(), P()),
        out_specs=out_specs,
        # pallas_call has no varying-manual-axes rule; the outputs are all
        # "dso"-sharded anyway, so the check adds nothing here
        check_vma="pallas" not in backend_name,
    )
    donate = tuple(range(n_data + 5, n_data + 9))   # w, gw, alpha, ga
    return jax.jit(sharded, donate_argnums=donate)


def _p2p_routes(perm_e: np.ndarray):
    """Static ppermute routing for one epoch's (p, p) permutation
    ``perm_e[r, q]`` = block device q consumes at inner iteration r, given
    the epoch-start invariant that device q holds block q.

    Returns ``p + 1`` source→target pair lists, indexed exactly like the
    all-gather path's ``fetch(c, r_next)``: entry ``r_next`` moves each
    block from its holder BEFORE inner iteration ``r_next`` straight to
    its ``r_next``-consumer (the schedule's inverse permutation names the
    holder), and entry ``p`` is the end-of-epoch restore that sends every
    block home.  A ``None`` entry marks an identity move (elided).
    """
    perm = np.asarray(perm_e)
    p = perm.shape[-1]
    # own[r] = holder map before inner iteration r; own[p] = after the last
    own = np.concatenate([np.arange(p)[None, :], perm], axis=0)
    inv = np.argsort(own, axis=-1)          # inv[r, b] = holder of block b
    qs = np.arange(p)
    routes = []
    for r_next in range(p + 1):
        want = perm[r_next] if r_next < p else qs
        src = inv[r_next][want]             # src[t] sends to device t
        if np.array_equal(src, qs):
            routes.append(None)
        else:
            routes.append([(int(src[t]), t) for t in range(p)])
    return routes


def _epoch_shardmap_p2p(mesh: Mesh, p: int, db: int, loss_name: str,
                        reg_name: str, use_adagrad: bool, row_batches: int,
                        perms_host: np.ndarray, *,
                        backend_name: str = "dense_jnp", n_data: int = 1,
                        telemetry: bool = False):
    """The point-to-point twin of ``_epoch_shardmap(ring=False)``: the
    chunk's permutations are ALSO host values here, so every block move
    compiles to a static-pair ``ppermute`` — each device receives exactly
    the O(db) block it consumes next, instead of the all-gather path's
    O(p·db) bytes.  ``(w, gw)`` travel as one stacked buffer (one
    rendezvous per move) and identity moves are elided.

    The body is the all-gather ``shuffle_epoch`` verbatim except inside
    ``fetch``: the gather + argsort + select becomes a ``lax.switch`` over
    ``r_next`` whose branches are the epoch's static ppermutes
    (``_p2p_routes``).  Keeping the surrounding program shape identical —
    same fori_loop, same traced ``perms`` operand, same tile-step code —
    keeps the compiled arithmetic identical too: values are bit-identical
    to the all-gather path, only the transport differs.

    When all epochs in the chunk share one permutation (lpt broadcasts a
    single Latin square; fixed schedules usually too) one traced epoch
    body scans over the whole chunk; otherwise the chunk unrolls per
    epoch (callers memoize on the permutation values).
    """
    backend = get_backend(backend_name)
    perms_host = np.asarray(perms_host)
    n = perms_host.shape[0]
    uniform = n > 0 and bool((perms_host == perms_host[0]).all())
    routes = [_p2p_routes(perms_host[e]) for e in range(1 if uniform else n)]

    def epochs_body(*args):
        arrays = args[:n_data]
        (yq, rnq, tcnq, trnq, col_nnz, w_blk, gw_blk, alpha_q, ga_q,
         etas, perms, lam, m, w_lo, w_hi) = args[n_data:]
        arrays_q = tuple(a[0] for a in arrays)
        q = jax.lax.axis_index("dso")
        yq, rnq = yq[0], rnq[0]
        tcnq, trnq = tcnq[0], trnq[0]
        w_blk, gw_blk = w_blk[0], gw_blk[0]
        alpha_q, ga_q = alpha_q[0], ga_q[0]
        meta = (lam, m, loss_name, reg_name, use_adagrad, w_lo, w_hi)

        def step_block(blk_id, w_b, gw_b, alpha_q, ga_q, eta_t):
            return inner_iteration(backend, meta, col_nnz, blk_id, w_b,
                                   gw_b, alpha_q, ga_q, arrays_q, yq, rnq,
                                   tcnq, trnq, eta_t, row_batches)

        mb = yq.shape[0]
        n_f = len(TELEMETRY_FIELDS)

        def make_epoch(route):
            def fetch(c, r_next):
                # the p2p fetch: one static ppermute, switch-dispatched on
                # r_next (every device branches the same way — r_next is
                # uniform across the mesh, so the collectives line up)
                w_blk, gw_blk = c
                branches = [
                    (lambda b: b) if prs is None
                    else (lambda b, prs=prs:
                          jax.lax.ppermute(b, "dso", prs))
                    for prs in route
                ]
                buf = jax.lax.switch(r_next, branches,
                                     jnp.stack([w_blk, gw_blk]))
                return buf[0], buf[1]

            def epoch(carry, xs):
                eta_t, perm_e = xs
                if telemetry:
                    carry = carry + (jnp.zeros((p, n_f), jnp.float32),)

                def inner(r, c):
                    w_blk, gw_blk, alpha_q, ga_q = c[:4]
                    w_blk, gw_blk = fetch((w_blk, gw_blk), r)
                    blk_id = perm_e[r, q]
                    w_new, a_new, gw_new, ga_new = step_block(
                        blk_id, w_blk, gw_blk, alpha_q, ga_q, eta_t)
                    out = ()
                    if telemetry:
                        trn_blk = jax.lax.dynamic_slice(
                            trnq, (blk_id, 0), (1, mb))[0]
                        out = (c[4].at[r].set(telemetry_row(
                            w_blk, w_new, alpha_q, a_new, gw_new, ga_new,
                            trn_blk)),)
                    return (w_new, gw_new, a_new, ga_new) + out

                carry = jax.lax.fori_loop(0, p, inner, carry)
                # restore the epoch-start invariant: device q holds block q
                w_blk, gw_blk, alpha_q, ga_q = carry[:4]
                w_blk, gw_blk = fetch((w_blk, gw_blk), jnp.int32(p))
                out = (w_blk, gw_blk, alpha_q, ga_q)
                return ((out, carry[4]) if telemetry else (out, None))

            return epoch

        carry = (w_blk, gw_blk, alpha_q, ga_q)
        if uniform:
            # one traced epoch body reused for every epoch in the chunk
            carry, tbufs = jax.lax.scan(make_epoch(routes[0]), carry,
                                        (etas, perms))
        else:
            tb = []
            for e in range(n):
                carry, tbuf_e = make_epoch(routes[e])(
                    carry, (etas[e], perms[e]))
                tb.append(tbuf_e)
            tbufs = jnp.stack(tb) if telemetry else None
        w_blk, gw_blk, alpha_q, ga_q = carry
        out = (w_blk[None], gw_blk[None], alpha_q[None], ga_q[None])
        if telemetry:
            out = out + (tbufs[:, :, None, :],)
        return out

    out_specs = (P("dso"), P("dso"), P("dso"), P("dso"))
    if telemetry:
        out_specs = out_specs + (P(None, None, "dso"),)
    sharded = jax.shard_map(
        epochs_body, mesh=mesh,
        in_specs=(P("dso"),) * (n_data + 4) + (P(None),)
        + (P("dso"),) * 4 + (P(), P(), P(), P(), P(), P()),
        out_specs=out_specs,
        check_vma="pallas" not in backend_name,
    )
    donate = tuple(range(n_data + 5, n_data + 9))   # w, gw, alpha, ga
    return jax.jit(sharded, donate_argnums=donate)


class ShardedDSO:
    """Driver object holding device-placed state for Algorithm 1.

    ``impl`` accepts any registered engine backend (or the legacy
    selectors, including ``"auto"`` with the same density threshold — and
    the same per-tile-K skew upgrade to the bucketed ragged layout — as
    ``run_dso_grid``); ``schedule`` accepts any engine schedule — "cyclic"
    keeps the paper's ring, "random" is the NOMAD-style shuffle, "lpt"
    load-balances the per-tile nnz across workers per inner iteration.

    ``overlap=True`` (default) runs the cyclic ring as the double-buffered
    pipeline (staged prefetch + one fused ppermute per inner iteration);
    ``overlap=False`` keeps the legacy serial-shift body.  ``comm``
    selects the transport for general-permutation schedules: "p2p"
    (default via "auto") compiles each chunk's permutations into static
    point-to-point ppermute pairs — O(db) bytes per device per move —
    while "allgather" keeps the legacy all-gather+select path.  All four
    combinations produce bit-identical trajectories; the knobs only move
    communication off (or back onto) the critical path.
    """

    def __init__(self, prob: Problem, mesh: Mesh | None = None,
                 row_batches: int = 1, use_adagrad: bool = True,
                 alpha0: float = 0.0, impl: str = "jnp",
                 schedule: str = "cyclic", seed: int = 0, obs=None,
                 overlap: bool = True, comm: str = "auto",
                 telemetry=None):
        self.prob = prob
        # observability seam (duck-typed recorder or None; never required):
        # metrics() mirrors its eval scalars into obs gauges when attached
        self.obs = obs
        # telemetry seam (duck-typed TelemetrySpec or None): the epoch
        # functions grow the device-side telemetry output and run_epochs
        # drains it per chunk (trajectories bit-identical either way)
        self.telemetry = telemetry
        self.mesh = mesh or make_dso_mesh()
        self.p = self.mesh.devices.size
        self.backend, data = resolve_backend_and_build(prob, impl, self.p,
                                                       row_batches)
        self.sparse = self.backend.layout != "dense"
        self.schedule = get_schedule(schedule)
        self.key = jax.random.PRNGKey(seed)
        check_tile_stats(data, row_batches)
        tile = as_tile_data(data, bucketed_payload=self.backend.payload)
        _, self.mb, self.db = tile_dims(tile)
        state = init_state(prob, data, alpha0)
        self.use_adagrad = use_adagrad
        self.row_batches = row_batches
        self.eta0_record = None   # last eta0 seen, for the snapshot config
        self._ckpt_extra = dict(alpha0=float(alpha0), seed=int(seed))
        (self.lam, self.m_f, _, _, _, self.w_lo, self.w_hi) = prob_meta(prob)

        shard = NamedSharding(self.mesh, P("dso"))
        repl = NamedSharding(self.mesh, P(None))
        self._shard = shard
        # resident layout payload: device q holds its dense row shard or
        # its (p, mb, K) row of packed block-ELL tiles
        self._data_shards = tuple(jax.device_put(a, shard)
                                  for a in tile.arrays)
        self.yg = jax.device_put(tile.yg, shard)
        self.rng_ = jax.device_put(tile.row_nnz_g, shard)
        # static sparsity statistics, resident next to each row shard
        self.tcn = jax.device_put(tile.tile_col_nnz_g, shard)
        self.trn = jax.device_put(tile.tile_row_nnz_g, shard)
        self.col_nnz = jax.device_put(tile.col_nnz, repl)
        # state.w_grid is indexed by block id; device q starts owning block q
        self.w = jax.device_put(state.w_grid, shard)
        self.gw = jax.device_put(state.gw_grid, shard)
        self.alpha = jax.device_put(state.alpha, shard)
        self.ga = jax.device_put(state.ga, shard)
        # balanced schedules (lpt) weigh the per-tile nnz
        self._tile_nnz = (np.asarray(tile.tile_row_nnz_g).sum(axis=-1)
                          if self.schedule.balanced else None)
        n_data = len(self._data_shards)
        # the sharded device_put copies above are now the only live data;
        # the builder's unsharded arrays go out of scope here so resident
        # memory stays one grid (nnz-proportional on the sparse path)
        del data, tile, state
        self.epochs_done = 0
        if comm not in ("auto", "p2p", "allgather"):
            raise ValueError(
                f"comm must be 'auto', 'p2p' or 'allgather', got {comm!r}")
        self.overlap = bool(overlap)
        self.comm = comm
        # the ring schedule is already point-to-point; p2p routing only
        # replaces the general-permutation all-gather path
        self._p2p = (not self.schedule.ring) and comm in ("auto", "p2p")
        self._n_data = n_data
        self._p2p_cache = {}   # perms bytes -> jitted chunk fn (LRU)
        self._epochs_fn = (None if self._p2p else _epoch_shardmap(
            self.mesh, self.p, self.db, prob.loss_name, prob.reg_name,
            use_adagrad, row_batches, backend_name=self.backend.name,
            ring=self.schedule.ring, n_data=n_data, overlap=self.overlap,
            telemetry=self.telemetry is not None))

    def _p2p_fn(self, perms_host: np.ndarray):
        """The jitted p2p chunk function for these host permutations,
        memoized on their values (an lpt/fixed schedule re-draws the same
        square every chunk — one trace serves the whole run); LRU-capped
        so a random schedule cannot grow the cache without bound."""
        key = (perms_host.shape, perms_host.tobytes())
        fn = self._p2p_cache.pop(key, None)
        if fn is None:
            fn = _epoch_shardmap_p2p(
                self.mesh, self.p, self.db, self.prob.loss_name,
                self.prob.reg_name, self.use_adagrad, self.row_batches,
                perms_host, backend_name=self.backend.name,
                n_data=self._n_data,
                telemetry=self.telemetry is not None)
        self._p2p_cache[key] = fn       # re-insert: most-recently-used
        while len(self._p2p_cache) > 8:
            self._p2p_cache.pop(next(iter(self._p2p_cache)))
        return fn

    def run_epochs(self, n: int, eta0: float = 0.1):
        """Run ``n`` epochs in one donated-scan dispatch.  With a
        telemetry spec attached the chunk's device buffer is drained here
        (which syncs on the device->host fetch — the chunk wall it hands
        the spec times completed epochs)."""
        self.eta0_record = eta0
        t0 = self.epochs_done
        etas = eta_schedule(eta0, t0, n, self.use_adagrad)
        ctx = ({"tile_nnz": self._tile_nnz} if self.schedule.balanced
               else {})
        self.key, perms = self.schedule.draw(self.key, t0, n, self.p, **ctx)
        fn = (self._p2p_fn(np.asarray(perms)) if self._p2p
              else self._epochs_fn)
        t_wall = time.perf_counter() if self.telemetry is not None else 0.0
        out = fn(
            *self._data_shards, self.yg, self.rng_, self.tcn, self.trn,
            self.col_nnz, self.w, self.gw, self.alpha, self.ga, etas,
            perms, self.lam, self.m_f, self.w_lo, self.w_hi)
        if self.telemetry is not None:
            self.w, self.gw, self.alpha, self.ga, tbuf = out
            jax.block_until_ready(tbuf)
            transport = ("ring" if self.schedule.ring
                         else ("p2p" if self._p2p else "allgather"))
            self.telemetry.drain(
                tbuf, t0=t0, etas=etas, perms=np.asarray(perms),
                db=self.db, transport=transport,
                wall_s=time.perf_counter() - t_wall)
        else:
            self.w, self.gw, self.alpha, self.ga = out
        self.epochs_done += n

    def epoch(self, eta0: float = 0.1):
        self.run_epochs(1, eta0)

    def wait(self):
        """Block until the in-flight epoch dispatch has finished — the
        supervisor's wall-clock lane must time completed work, not async
        dispatch latency."""
        jax.block_until_ready((self.w, self.gw, self.alpha, self.ga))
        return self

    # -- elastic-runtime seams (repro.runtime stays out of this module) ----
    def solver_state(self) -> DSOState:
        """The complete blocked solver state as the engine's ``DSOState``
        pytree (block-id order: after every epoch device q holds block q —
        see ``w_full``).  What ``runtime.snapshot`` persists and
        ``runtime.reshard`` repartitions."""
        return DSOState(w_grid=self.w, gw_grid=self.gw, alpha=self.alpha,
                        ga=self.ga, epoch=jnp.int32(self.epochs_done))

    def snapshot_config(self) -> dict:
        """The run record ``runtime.resume`` needs to rebuild this driver
        (mirrors ``engine.driver.solve``'s snapshot config)."""
        prob = self.prob
        return dict(backend=self.backend.name, schedule=self.schedule.name,
                    p=self.p, mb=self.mb, db=self.db, m=prob.m, d=prob.d,
                    loss_name=prob.loss_name, reg_name=prob.reg_name,
                    lam=float(prob.lam), row_batches=self.row_batches,
                    eta0=(0.1 if self.eta0_record is None
                          else float(self.eta0_record)),
                    use_adagrad=bool(self.use_adagrad),
                    eval_every=1, checkpoint_every=0,
                    layout=self.backend.layout, inner_iteration=0,
                    **self._ckpt_extra)

    def restore(self, state: DSOState, key=None, epochs_done=None):
        """Adopt a checkpointed (or resharded) solver state: shard the
        blocked arrays back onto the mesh and reset the RNG/epoch cursor.
        The next ``run_epochs`` continues the stored trajectory exactly
        (same schedule stream from the stored key + cursor)."""
        if tuple(state.w_grid.shape) != (self.p, self.db):
            raise ValueError(
                f"state has w grid {tuple(state.w_grid.shape)}, this mesh "
                f"runs a ({self.p}, {self.db}) grid — reshard first "
                f"(repro.runtime.reshard.reshard_state)")
        put = lambda a: jax.device_put(jnp.asarray(a), self._shard)  # noqa: E731
        self.w, self.gw = put(state.w_grid), put(state.gw_grid)
        self.alpha, self.ga = put(state.alpha), put(state.ga)
        if key is not None:
            self.key = jnp.asarray(key)
        self.epochs_done = (int(state.epoch) if epochs_done is None
                            else int(epochs_done))

    # -- evaluation helpers ------------------------------------------------
    def w_full(self):
        """Global w, accounting for the ring position after each epoch.

        After one epoch every block is back on its home device — the ring
        made a full trip under the cyclic schedule, and the shuffle path
        restores the invariant explicitly — so device q again holds block
        q: the gathered (p, db) array is already in block-id order.
        """
        return jnp.asarray(self.w).reshape(-1)[: self.prob.d]

    def alpha_full(self):
        return jnp.asarray(self.alpha).reshape(-1)[: self.prob.m]

    def metrics(self) -> dict:
        w, a = self.w_full(), self.alpha_full()
        out = dict(
            epoch=self.epochs_done,
            primal=float(primal_objective(self.prob, w)),
            gap=float(duality_gap(self.prob, w, a)),
        )
        if self.obs is not None:
            for k, v in out.items():
                if k != "epoch":
                    self.obs.metrics.gauge(f"eval.{k}").set(v)
        return out


def run_dso_sharded(prob: Problem, epochs: int = 10, eta0: float = 0.1,
                    mesh: Mesh | None = None, row_batches: int = 1,
                    use_adagrad: bool = True, alpha0: float = 0.0,
                    eval_every: int = 1, impl: str = "jnp",
                    schedule: str = "cyclic", seed: int = 0):
    if eval_every < 1:
        raise ValueError(f"eval_every must be >= 1, got {eval_every}")
    opt = ShardedDSO(prob, mesh, row_batches, use_adagrad, alpha0, impl,
                     schedule, seed)
    warn_ragged_eval(epochs, eval_every)
    history = []
    while opt.epochs_done < epochs:
        opt.run_epochs(min(eval_every, epochs - opt.epochs_done), eta0)
        history.append(opt.metrics())
    return opt.w_full(), opt.alpha_full(), history
