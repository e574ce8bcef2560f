"""Driver layer: ONE jitted, state-donated epoch function behind every
execution mode.

``run_epochs`` scans the backend-parameterized ``epoch_body`` over a chunk
of epochs with the ``DSOState`` donated (in-place update, one dispatch per
evaluation chunk); ``solve`` wraps it in the evaluation-chunk loop shared
by the grid simulator, the random-schedule runner, and the out-of-core
path, and ``solve_serial`` drives the paper-exact pointwise epochs through
the same chunk loop.  The ``shard_map`` ring (``core.dso_dist.ShardedDSO``)
builds its per-device body from the same ``inner_iteration``.

Trace-cost note: each distinct chunk length traces the scan once, so when
``eval_every`` does not divide ``epochs`` the ragged final chunk costs one
extra compile — ``warn_ragged_eval`` flags it (once per shape) with a
divisor suggestion.
"""

from __future__ import annotations

import functools
import time
import warnings
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.losses import get_loss
from repro.core.regularizers import get_regularizer
from repro.core.saddle import Problem, project_alpha
from repro.engine.backends import (TileBackend, get_backend, resolve_backend,
                                   resolve_backend_for_layout)
from repro.engine.data import (DSOState, TileData, as_tile_data,
                               check_tile_stats, eta_schedule, gather_alpha,
                               gather_w, init_state_data, make_grid_data,
                               prob_meta, tile_dims)
from repro.engine.evaluate import problem_eval_hook
from repro.engine.schedules import get_schedule
from repro.sparse.format import (SPARSE_DENSITY_THRESHOLD, density,
                                 make_bucketed_grid_data,
                                 make_sparse_grid_data, problem_k_per_tile,
                                 tile_k_skew)

Array = jax.Array


class SolveResult(NamedTuple):
    """Unified result of every driver: gathered (unpadded) iterates, the
    evaluation-hook history, and the final grid state (None for serial)."""

    w: Array
    alpha: Array
    history: list
    state: Any = None


def resolve_backend_and_build(prob, impl, p: int, row_batches: int):
    """The one auto-probe + layout-builder dispatch behind both drivers
    (``solve`` and ``core.dso_dist.ShardedDSO``): resolve the layout —
    probing the per-tile-K skew only when ``auto`` is already in the
    sparse density regime (the probe is a host pass over the nonzero
    pattern) — build the grid in it, then resolve the backend for the
    built grid (``auto``'s kernel is chosen there)."""
    k_skew = (tile_k_skew(problem_k_per_tile(prob, p))
              if impl == "auto"
              and density(prob) < SPARSE_DENSITY_THRESHOLD else None)
    layout = resolve_backend(impl, density(prob), k_skew=k_skew).layout
    builders = {"dense": make_grid_data,
                "sparse": make_sparse_grid_data,
                "bucketed": make_bucketed_grid_data}
    data = builders[layout](prob, p, row_batches)
    return resolve_backend_for_layout(impl, layout, tile_dims(data)[2]), data


# ----------------------------------------------------- inner iteration --


def stage_block(backend: TileBackend, col_nnz, blk_id, arrays_q, y_q,
                tcn_q, trn_q, row_batches: int, db: int):
    """Stage everything about the active block that depends ONLY on its id:
    the per-block sparsity-statistic slices.  None of this depends on the
    travelling ``(w, gw)`` block, so the double-buffered sharded driver
    computes the stage for inner iteration t+1 while iteration t's
    ``ppermute`` is still in flight — the prefetch half of the pipeline.

    The data payload slice is NOT staged: it is re-derived from the block
    id at consume time (``staged_step``), keeping the staged carry O(tile
    statistics) — and keeping the compiled tile-step arithmetic literally
    identical to the serial driver's, the bit-identity contract.
    """
    blk_cols = blk_id * db
    col_nnz_blk = jax.lax.dynamic_slice(col_nnz, (blk_cols,), (db,))
    mb = y_q.shape[0]
    trn_blk = jax.lax.dynamic_slice(trn_q, (blk_id, 0), (1, mb))[0]
    tcn_blk = jax.lax.dynamic_slice(tcn_q, (0, blk_cols), (row_batches, db))
    return (blk_id, col_nnz_blk, trn_blk, tcn_blk)


def staged_step(backend: TileBackend, meta, staged, w_blk, gw_blk, alpha_q,
                ga_q, arrays_q, y_q, rn_q, eta_t, row_batches: int):
    """Consume a ``stage_block`` tuple: select the staged block's payload
    and run all its tile steps on the (now-arrived) travelling ``(w, gw)``
    block.  The ops are exactly ``inner_iteration``'s — same slices, same
    kernel — so the pipelined driver's trajectory is bit-identical to the
    serial one."""
    blk_id, col_nnz_blk, trn_blk, tcn_blk = staged
    db = w_blk.shape[0]
    block = backend.select_block(arrays_q, blk_id, blk_id * db, db)
    # the scope names the tile step's ops in the compiled program's
    # metadata (``.../vmap(tile_step)/...``), where a profile finds them
    with jax.named_scope("tile_step"):
        return backend.block_step(meta, block, y_q, w_blk, alpha_q, gw_blk,
                                  ga_q, rn_q, col_nnz_blk, trn_blk, tcn_blk,
                                  eta_t, row_batches)


def inner_iteration(backend: TileBackend, meta, col_nnz, blk_id, w_blk,
                    gw_blk, alpha_q, ga_q, arrays_q, y_q, rn_q, tcn_q, trn_q,
                    eta_t, row_batches: int):
    """All tile steps of one processor on one active block — the single
    backend-parameterized inner iteration of Algorithm 1.

    ``meta`` = (lam, m, loss_name, reg_name, use_adagrad, w_lo, w_hi);
    ``arrays_q`` is processor q's slice of ``TileData.arrays``;
    ``tcn_q`` (row_batches, d_pad) / ``trn_q`` (p, mb) are its precomputed
    tile sparsity statistics.  The block-level slicing is shared here; the
    layout payload slice and the kernel are the backend's two hooks.
    Composed as ``stage_block`` (the block-id-only slices the pipelined
    sharded driver prefetches) + ``staged_step`` (the consume).
    """
    db = w_blk.shape[0]
    staged = stage_block(backend, col_nnz, blk_id, arrays_q, y_q, tcn_q,
                         trn_q, row_batches, db)
    return staged_step(backend, meta, staged, w_blk, gw_blk, alpha_q, ga_q,
                       arrays_q, y_q, rn_q, eta_t, row_batches)


# ---------------------------------------------------------- epoch body --


def epoch_body(backend: TileBackend, data: TileData, state: DSOState, perm,
               eta_t, meta, *, row_batches: int, p: int):
    """One epoch under an explicit ``(p, p)`` permutation schedule:
    ``perm[r, q]`` = block owned by processor q at inner iteration r.
    All p processors update their disjoint blocks simultaneously (vmap) —
    Lemma 2's block-disjointness makes this equal to any serial order.
    """

    def inner(r, st: DSOState) -> DSOState:
        blk_ids = perm[r]
        # gather the w blocks each processor owns this inner iteration
        w_owned = jnp.take(st.w_grid, blk_ids, axis=0)    # (p, db)
        gw_owned = jnp.take(st.gw_grid, blk_ids, axis=0)

        def per_q(blk_id, w_blk, gw_blk, a_q, ga_q, *rest):
            # rest: the layout's data arrays (X_q | cols_q, vals_q),
            # then y_q, rn_q, tcn_q, trn_q
            arrays_q, (y_q, rn_q, tcn_q, trn_q) = rest[:-4], rest[-4:]
            return inner_iteration(backend, meta, data.col_nnz, blk_id,
                                   w_blk, gw_blk, a_q, ga_q, arrays_q, y_q,
                                   rn_q, tcn_q, trn_q, eta_t, row_batches)

        w_new, a_new, gw_new, ga_new = jax.vmap(per_q)(
            blk_ids, w_owned, gw_owned, st.alpha, st.ga, *data.arrays,
            data.yg, data.row_nnz_g, data.tile_col_nnz_g,
            data.tile_row_nnz_g)
        w_grid = st.w_grid.at[blk_ids].set(w_new)
        gw_grid = st.gw_grid.at[blk_ids].set(gw_new)
        return DSOState(w_grid, gw_grid, a_new, ga_new, st.epoch)

    state = jax.lax.fori_loop(0, p, inner, state)
    return state._replace(epoch=state.epoch + 1)


_EPOCH_STATICS = ("backend", "loss_name", "reg_name", "use_adagrad",
                  "row_batches", "p", "db")


@functools.partial(jax.jit, static_argnames=_EPOCH_STATICS,
                   donate_argnums=(1,))
def run_epochs(data: TileData, state: DSOState, perms, etas, lam, m, w_lo,
               w_hi, *, backend, loss_name, reg_name, use_adagrad,
               row_batches, p, db):
    """``len(etas)`` epochs in ONE dispatch: a ``lax.scan`` over
    (permutation schedule, step size) pairs with the (w, alpha, gw, ga)
    state donated, so epoch state is updated in place instead of
    round-tripping host dispatch (and copies) per epoch.
    ``perms``: (n_epochs, p, p) from the Schedule layer."""
    be = get_backend(backend)
    meta = (lam, m, loss_name, reg_name, use_adagrad, w_lo, w_hi)

    def step(st, xs):
        perm_t, eta_t = xs
        st = epoch_body(be, data, st, perm_t, eta_t, meta,
                        row_batches=row_batches, p=p)
        return st, None

    state, _ = jax.lax.scan(step, state, (perms, etas))
    return state


# --------------------------------------------------- ragged-eval warning --

_RAGGED_WARNED: set = set()


def warn_ragged_eval(epochs: int, eval_every: int, *, stacklevel: int = 3):
    """Warn (once per (epochs, eval_every) shape) when the evaluation
    chunking leaves a ragged final chunk: each distinct chunk length traces
    the donated epoch scan once more, so the ragged tail costs one extra
    compile.  Suggests the largest chunk that divides ``epochs``."""
    if eval_every <= 0 or eval_every >= epochs or epochs % eval_every == 0:
        return
    key = (epochs, eval_every)
    if key in _RAGGED_WARNED:
        return
    _RAGGED_WARNED.add(key)
    div = next(k for k in range(min(eval_every, epochs), 0, -1)
               if epochs % k == 0)
    warnings.warn(
        f"epochs={epochs} is not a multiple of eval_every={eval_every}: the "
        f"ragged final chunk of {epochs % eval_every} epoch(s) triggers an "
        f"extra lax.scan trace of the epoch driver; prefer a chunking that "
        f"divides epochs (e.g. eval_every={div})",
        RuntimeWarning, stacklevel=stacklevel)


# ------------------------------------------------------------- solve() --


def _next_multiple(t: int, k: int) -> int:
    """Smallest multiple of k strictly greater than t."""
    return (t // k + 1) * k


# ------------------------------------------------- observability (obs=) --
#
# The obs seam is duck-typed like store=/health=: the engine never imports
# repro.obs.  Everything below runs ONLY under ``if obs is not None`` —
# the metrics-off contract (obs/__init__.py) is that the chunk loop does
# no obs work and allocates nothing when obs is None.


def _enter(obs, name: str, **attrs):
    """Open span ``name`` by hand and return it for its ``__exit__``:
    manual enter/exit (not contextlib), so the obs-off path allocates
    nothing."""
    span = obs.span(name, **attrs)
    span.__enter__()
    return span


def _obs_throughput(obs, *, rows: float, nnz: float, payload_bytes: float):
    """Bind the static per-epoch work totals once per run; returns the
    per-chunk callback recording the throughput gauges."""
    g_rows = obs.metrics.gauge("rows_per_s")
    g_nnz = obs.metrics.gauge("nnz_per_s")
    g_bytes = obs.metrics.gauge("packed_bytes_per_s")
    g_eta = obs.metrics.gauge("eta")
    h_epoch = obs.metrics.histogram("epoch_s")

    def record(n: int, dt: float, eta: float):
        dt = max(dt, 1e-12)
        g_rows.set(rows * n / dt)
        g_nnz.set(nnz * n / dt)
        g_bytes.set(payload_bytes * n / dt)
        g_eta.set(eta)
        h_epoch.observe(dt / n)

    return record


def _obs_eval(obs, entry):
    """Record every numeric field of an evaluation-history entry as an
    ``eval.<key>`` gauge (primal, gap, pd_gap, ... — whatever the hook
    computes becomes a standard metric).  Non-dict entries (custom hooks)
    are left alone."""
    if not isinstance(entry, dict):
        return
    for k, v in entry.items():
        if k != "epoch" and isinstance(v, (int, float)):
            obs.metrics.gauge(f"eval.{k}").set(v)


def solve(source, *, backend="auto", schedule="cyclic", p: int = 4,
          epochs: int = 10, eta0: float = 0.1, use_adagrad: bool = True,
          row_batches: int = 1, alpha0: float = 0.0, eval_every: int = 1,
          seed: int = 0, eval_hook="auto", loss_name: str | None = None,
          reg_name: str | None = None, lam: float | None = None,
          m: int | None = None, d: int | None = None,
          checkpoint_every: int = 0, store=None,
          init=None, health=None, obs=None) -> SolveResult:
    """The one epoch driver behind grid / random / out-of-core execution.

    ``source`` is either a dense ``Problem`` (the grid data is built here,
    laid out for the chosen backend) or pre-built grid data (``GridData`` /
    ``SparseGridData`` / ``TileData`` — the out-of-core entry, which then
    needs ``loss_name``/``reg_name``/``lam``/``m``/``d`` and fixes the
    layout, so ``backend`` is a kernel choice).

    ``backend`` — canonical name, legacy impl selector, or TileBackend;
    ``schedule`` — "cyclic", "random", or a ``Schedule`` (e.g.
    ``fixed_schedule(perms)``); ``eval_hook`` — ``hook(t, w, alpha) ->
    dict`` appended to the history per evaluation chunk ("auto": Problem
    objectives for a Problem source, no evaluation for data sources).

    Epochs between evaluation points run as ONE donated-scan dispatch
    (``run_epochs``).

    Elastic-runtime seam (``repro.runtime``): ``checkpoint_every=k`` adds
    chunk boundaries at every k-th GLOBAL epoch, and ``store`` (duck-typed,
    e.g. ``runtime.snapshot.SnapshotStore``) receives
    ``store.save(state=, key=, epochs_done=, history=, config=)`` at each
    of them — the complete solver state at that boundary.  ``init`` (a
    ``runtime.snapshot.DSOSnapshot``: ``state``/``key``/``epochs_done``/
    ``history``) resumes from such a snapshot: the epoch cursor threads
    through ``schedules.draw`` (whose chunk-invariance contract makes the
    resumed trajectory bit-identical to the uninterrupted one) and the
    step-size schedule.  Checkpoint boundaries that fall between
    evaluation points introduce extra chunk lengths (one scan trace each);
    prefer ``checkpoint_every`` a multiple of ``eval_every``.

    Health seam (``repro.runtime.health``): ``health`` (duck-typed, e.g.
    ``HealthGuard``) is consulted at every chunk boundary —
    ``health.inject(state, t)`` before the chunk (chaos seam),
    ``health.check_state(state)`` (jitted all-finite probe, BEFORE the
    evaluation hook so a poisoned state is never evaluated or saved) and
    ``health.check_history(history)`` (objective-regression monitor)
    after it.  A failed check rolls back to the latest *valid* snapshot
    in ``store`` (falling back to ``init``, then to a fresh start), backs
    ``eta0`` off by ``health.eta_decay``, and retries; once
    ``health.max_retries`` rollbacks are spent, ``health.exhausted``
    either raises ``HealthError`` or requests degradation to the
    paper-exact ``solve_serial`` safe mode (Problem sources only).

    Observability seam (``repro.obs``): ``obs`` (duck-typed, e.g.
    ``obs.RunRecorder``) receives one ``span("solve")`` per call, the
    request every span below carries the id of: ``solve_setup`` (tile
    checks, tiling, initial state, PRNG key, obs set-up), then per chunk
    ``epoch_chunk`` with children ``chunk_schedule`` (permutation draw and
    step sizes), ``chunk_dispatch`` (the epoch program's enqueue) and
    ``chunk_wait`` (``block_until_ready``, so the chunk times completed
    epochs, and the rows/s, nnz/s, packed payload bytes/s and eta
    gauges); ``eval`` with child ``eval_gather`` (w and alpha off the
    grid) around the hook; ``snapshot_save`` / ``restore`` around those
    boundaries; every evaluation-history field as an ``eval.<key>``
    gauge; and (when ``health`` is given without its own recorder) the
    health guard's ledger events.  ``solve`` and ``eval`` close when the
    hook raises.  ``obs=None`` (default) is a true no-op: no obs calls,
    no allocations, bit-identical trajectories.

    """
    if eval_every < 1:
        raise ValueError(f"eval_every must be >= 1, got {eval_every}")
    if checkpoint_every < 0:
        raise ValueError(
            f"checkpoint_every must be >= 0, got {checkpoint_every}")
    if store is not None and checkpoint_every < 1:
        raise ValueError("a snapshot store needs checkpoint_every >= 1 to "
                         "know its boundaries")
    if obs is not None:
        span_solve = _enter(obs, "solve")
        span = _enter(obs, "solve_setup")
    try:
        sched = get_schedule(schedule)
        if isinstance(source, Problem):
            given = [k for k, v in (("loss_name", loss_name),
                                    ("reg_name", reg_name), ("lam", lam),
                                    ("m", m), ("d", d)) if v is not None]
            if given:
                raise ValueError(
                    f"{given} conflict with the Problem source (its own "
                    f"loss/reg/lam/shape are used); either drop them or "
                    f"pass pre-built grid data instead of the Problem")
            prob = source
            be, data = resolve_backend_and_build(prob, backend, p,
                                                 row_batches)
            loss_name, reg_name = prob.loss_name, prob.reg_name
            m, d = prob.m, prob.d
            lam_f, m_f, _, _, _, w_lo, w_hi = prob_meta(prob)
            if eval_hook == "auto":
                eval_hook = problem_eval_hook(prob)
        else:
            data = source
            missing = [k for k, v in (("loss_name", loss_name),
                                      ("reg_name", reg_name), ("lam", lam),
                                      ("m", m), ("d", d)) if v is None]
            if missing:
                raise ValueError(f"solving from pre-built grid data "
                                 f"requires {missing} (no Problem to read "
                                 f"them from)")
            be = resolve_backend_for_layout(backend,
                                            as_tile_data(data).layout,
                                            tile_dims(data)[2])
            loss = get_loss(loss_name)
            box = loss.w_box(lam) if loss.w_box is not None else np.inf
            lam_f, m_f = jnp.float32(lam), jnp.float32(m)
            w_lo, w_hi = jnp.float32(-box), jnp.float32(box)
            if eval_hook == "auto":
                eval_hook = None
        if obs is not None:    # which tile kernel this solve runs
            span.set(backend=be.name)
        check_tile_stats(data, row_batches)
        tile = as_tile_data(data)
        p_, mb_, db = tile_dims(tile)
        kw = dict(backend=be.name, loss_name=loss_name, reg_name=reg_name,
                  use_adagrad=use_adagrad, row_batches=row_batches, p=p_,
                  db=db)

        chunk = eval_every if eval_hook is not None else epochs
        warn_ragged_eval(epochs, chunk)
        # balanced schedules (lpt) weigh the per-tile nnz; computed once
        sched_ctx = ({"tile_nnz":
                      np.asarray(tile.tile_row_nnz_g).sum(axis=-1)}
                     if sched.balanced else {})
        # the complete run record a snapshot carries (runtime.resume
        # rebuilds the solver call from it; runtime.reshard rewrites
        # p/mb/db)
        cfg = dict(backend=be.name, schedule=sched.name, p=p_, mb=mb_,
                   db=db, m=int(m), d=int(d), loss_name=loss_name,
                   reg_name=reg_name, lam=float(lam_f),
                   row_batches=row_batches, eta0=float(eta0),
                   use_adagrad=bool(use_adagrad), alpha0=float(alpha0),
                   seed=int(seed), eval_every=int(eval_every),
                   checkpoint_every=int(checkpoint_every), layout=be.layout,
                   inner_iteration=0)
        if health is not None:  # backoff params ride in every snapshot too
            cfg.update(eta_decay=float(health.eta_decay),
                       max_retries=int(health.max_retries))
        if init is not None:
            got = tuple(init.state.w_grid.shape)
            if got != (p_, db):
                raise ValueError(
                    f"snapshot state has w grid {got}, this run's grid is "
                    f"({p_}, {db}) — resuming across a different p needs "
                    f"repro.runtime.reshard first")
            # copied, not aliased: the epoch scan donates its state, and
            # the caller's snapshot must survive the resumed run
            # (re-reshard, etc.)
            state = jax.tree.map(lambda a: jnp.array(a, copy=True),
                                 init.state)
            key = jnp.asarray(init.key)
            t = int(init.epochs_done)
            history = list(init.history)
        else:
            state = init_state_data(loss_name, data, alpha0)
            key = jax.random.PRNGKey(seed)
            t, history = 0, []
        eta_live = float(eta0)  # backed off per rollback under a health guard
        if obs is not None:
            # static per-epoch work totals, computed once: every epoch
            # touches every nonzero exactly once, streaming the layout
            # payload once
            obs.record(type="meta", phase="solve", epochs=int(epochs), **cfg)
            record_chunk = _obs_throughput(
                obs, rows=float(m),
                nnz=float(np.asarray(tile.row_nnz_g * tile.row_valid).sum()),
                payload_bytes=float(sum(getattr(a, "nbytes", 0)
                                        for a in tile.arrays)))
            if health is not None and getattr(health, "obs", None) is None:
                health.obs = obs   # ledger events join the same stream
            span.__exit__(None, None, None)
        while t < epochs:
            if health is not None:
                state = health.inject(state, t)
            stops = [epochs]
            if eval_hook is not None:
                stops.append(_next_multiple(t, chunk))
            if checkpoint_every:
                stops.append(_next_multiple(t, checkpoint_every))
            n = min(stops) - t
            # every statement of the chunk sits in one child span, so the
            # chunk's self time is the recorder's own bookkeeping
            if obs is not None:
                span_chunk = _enter(obs, "epoch_chunk", t0=t, epochs=n)
                span = _enter(obs, "chunk_schedule")
            key, perms = sched.draw(key, t, n, p_, **sched_ctx)
            etas = eta_schedule(eta_live, t, n, use_adagrad)
            if obs is not None:
                span.__exit__(None, None, None)
                span = _enter(obs, "chunk_dispatch")
                t_chunk = time.perf_counter()
            state = run_epochs(tile, state, perms, etas, lam_f, m_f, w_lo,
                               w_hi, **kw)
            if obs is not None:
                span.__exit__(None, None, None)
                span = _enter(obs, "chunk_wait")
                # sync so the chunk times completed epochs, not the enqueue
                jax.block_until_ready(state)
                record_chunk(n, time.perf_counter() - t_chunk, eta_live)
                span.__exit__(None, None, None)
                span_chunk.__exit__(None, None, None)
            t_new = t + n
            failure = None
            if health is not None:
                # state first: a poisoned iterate must never reach the eval
                # hook or the snapshot store
                failure = health.check_state(state)
            if failure is None and eval_hook is not None and (
                    t_new % chunk == 0 or t_new == epochs):
                if obs is not None:
                    span_eval = _enter(obs, "eval", epoch=t_new)
                    span = _enter(obs, "eval_gather")
                # the hook may end the solve by raising: eval closes anyway
                try:
                    w_now = gather_w(state, d)
                    alpha_now = gather_alpha(state, m)
                    if obs is not None:
                        span.__exit__(None, None, None)
                    entry = eval_hook(t_new, w_now, alpha_now)
                    # held past the hook, the gathered copies would add
                    # their bytes to the next chunk's peak device memory
                    del w_now, alpha_now
                    history.append(entry)
                    if obs is not None:
                        _obs_eval(obs, entry)
                finally:
                    if obs is not None:
                        span_eval.__exit__(None, None, None)
                if health is not None:
                    failure = health.check_history(history)
            if failure is not None:
                health.retries += 1
                if health.retries > health.max_retries:
                    if health.exhausted(failure=failure, epoch=t_new,
                                        eta0=eta_live,
                                        can_degrade=isinstance(source,
                                                               Problem)
                                        ) == "serial":
                        return solve_serial(source, epochs=epochs,
                                            eta0=eta_live, seed=seed,
                                            use_adagrad=use_adagrad,
                                            alpha0=alpha0,
                                            eval_every=eval_every, obs=obs)
                if obs is not None:
                    span = _enter(obs, "restore", epoch=t_new,
                                  failure=failure)
                snap = None
                if store is not None:
                    try:
                        snap = store.load()   # latest-VALID-wins
                    except FileNotFoundError:
                        snap = None
                if snap is None:
                    snap = init               # may still be None: fresh start
                eta_live *= health.eta_decay
                cfg["eta0"] = eta_live
                if snap is not None:
                    state = jax.tree.map(lambda a: jnp.array(a, copy=True),
                                         snap.state)
                    key = jnp.asarray(snap.key)
                    resumed = int(snap.epochs_done)
                    history = list(snap.history)
                else:
                    state = init_state_data(loss_name, data, alpha0)
                    key = jax.random.PRNGKey(seed)
                    resumed, history = 0, []
                health.note(kind="health", epoch=t_new, action="rollback",
                            epochs_lost=t_new - resumed, retry=health.retries,
                            failure=failure, resumed_from=resumed,
                            eta0=eta_live)
                if obs is not None:
                    span.__exit__(None, None, None)
                t = resumed
                continue
            t = t_new
            if store is not None and (t % checkpoint_every == 0
                                      or t == epochs):
                if obs is not None:
                    span = _enter(obs, "snapshot_save", epoch=t)
                store.save(state=state, key=key, epochs_done=t,
                           history=list(history), config=cfg)
                if obs is not None:
                    span.__exit__(None, None, None)
        if store is not None and hasattr(store, "flush"):
            # async-write stores overlap serialization with the chunk loop;
            # drain (and surface any write failure) before declaring the run
            # durable
            store.flush()
        return SolveResult(gather_w(state, d), gather_alpha(state, m),
                           history, state)
    finally:
        if obs is not None:
            span_solve.__exit__(None, None, None)


# ------------------------------------------- paper-exact serial driver --


def _coords(prob: Problem):
    Xn = np.asarray(prob.X)
    ii, jj = np.nonzero(Xn)
    return (ii.astype(np.int32), jj.astype(np.int32),
            Xn[ii, jj].astype(np.float32))


@functools.partial(jax.jit, static_argnames=("loss_name", "reg_name", "m",
                                             "use_adagrad"),
                   donate_argnums=(5, 6, 7, 8))
def _serial_epochs(ii, jj, vv, perms, etas, w, alpha, gw, ga, y, row_nnz,
                   col_nnz, lam, w_lo, w_hi, *, loss_name, reg_name, m,
                   use_adagrad):
    """``len(etas)`` paper-exact pointwise epochs in one donated-scan
    dispatch — the serial reference driven exactly like the grid engine.
    ``perms``: (n_epochs, nnz) visit order per epoch."""
    loss = get_loss(loss_name)
    reg = get_regularizer(reg_name)

    def body_factory(perm, eta_t):
        def body(carry, k):
            w, alpha, gw, ga = carry
            i, j, x = ii[perm[k]], jj[perm[k]], vv[perm[k]]
            wj, ai, yi = w[j], alpha[i], y[i]
            # Eq. (8), simultaneous read of (w_j, alpha_i) — the Lemma 2 form
            g_w = lam * reg.grad(wj) / col_nnz[j] - ai * x / m
            g_a = (-loss.dual_grad(ai, yi) / (m * row_nnz[i]) - wj * x / m)
            if use_adagrad:
                gw_i = gw[j] + g_w * g_w
                ga_i = ga[i] + g_a * g_a
                dw = eta_t * g_w * jax.lax.rsqrt(gw_i + 1e-8)
                da = eta_t * g_a * jax.lax.rsqrt(ga_i + 1e-8)
                gw = gw.at[j].set(gw_i)
                ga = ga.at[i].set(ga_i)
            else:
                dw, da = eta_t * g_w, eta_t * g_a
            # App. B projections, applied to the touched coordinates
            w = w.at[j].set(jnp.clip(wj - dw, w_lo, w_hi))
            ai_new = jnp.squeeze(loss.project_alpha(ai + da, yi))
            alpha = alpha.at[i].set(ai_new)
            return (w, alpha, gw, ga), None
        return body

    def epoch(carry, xs):
        perm, eta_t = xs
        carry, _ = jax.lax.scan(body_factory(perm, eta_t), carry,
                                jnp.arange(ii.shape[0]))
        return carry, None

    (w, alpha, gw, ga), _ = jax.lax.scan(epoch, (w, alpha, gw, ga),
                                         (perms, etas))
    return w, alpha, gw, ga


def solve_serial(prob: Problem, epochs: int = 10, eta0: float = 0.1,
                 seed: int = 0, use_adagrad: bool = True,
                 alpha0: float = 0.0, eval_every: int = 1,
                 eval_hook="auto", obs=None) -> SolveResult:
    """Paper-exact Algorithm 1 with p=1 (sequential pointwise updates),
    driven through the engine's evaluation-chunk loop.  ``obs`` is the
    same duck-typed observability seam as ``solve`` (the same spans where
    the step exists — ``solve``, ``solve_setup``, ``epoch_chunk`` with
    ``chunk_schedule``/``chunk_dispatch``/``chunk_wait``, ``eval`` — plus
    throughput gauges and eval metrics; None = true no-op)."""
    if eval_every < 1:
        raise ValueError(f"eval_every must be >= 1, got {eval_every}")
    if obs is not None:
        span_solve = _enter(obs, "solve")
        span = _enter(obs, "solve_setup")
    try:
        ii, jj, vv = _coords(prob)
        ii, jj, vv = jnp.asarray(ii), jnp.asarray(jj), jnp.asarray(vv)
        nnz = ii.shape[0]
        w = jnp.zeros(prob.d, jnp.float32)
        alpha = project_alpha(prob, jnp.full(prob.m, alpha0, jnp.float32))
        gw = jnp.zeros_like(w)
        ga = jnp.zeros_like(alpha)
        loss = get_loss(prob.loss_name)
        box = loss.w_box(prob.lam) if loss.w_box is not None else np.inf
        hook = problem_eval_hook(prob) if eval_hook == "auto" else eval_hook
        warn_ragged_eval(epochs, eval_every)
        key = jax.random.PRNGKey(seed)
        history = []
        t = 0
        if obs is not None:
            obs.record(type="meta", phase="solve_serial",
                       epochs=int(epochs), m=prob.m, d=prob.d, nnz=int(nnz),
                       eta0=float(eta0), loss_name=prob.loss_name,
                       reg_name=prob.reg_name, seed=int(seed))
            record_chunk = _obs_throughput(obs, rows=float(prob.m),
                                           nnz=float(nnz),
                                           payload_bytes=float(12 * nnz))
            span.__exit__(None, None, None)
        while t < epochs:
            n = min(eval_every, epochs - t)
            if obs is not None:
                span_chunk = _enter(obs, "epoch_chunk", t0=t, epochs=n)
                span = _enter(obs, "chunk_schedule")
            perms = []
            for _ in range(n):
                key, sk = jax.random.split(key)
                perms.append(jax.random.permutation(sk, nnz))
            perms = jnp.stack(perms)
            etas = eta_schedule(eta0, t, n, use_adagrad)
            if obs is not None:
                span.__exit__(None, None, None)
                span = _enter(obs, "chunk_dispatch")
                t_chunk = time.perf_counter()
            w, alpha, gw, ga = _serial_epochs(
                ii, jj, vv, perms, etas, w, alpha, gw, ga, prob.y,
                prob.row_nnz, prob.col_nnz, jnp.float32(prob.lam),
                jnp.float32(-box), jnp.float32(box),
                loss_name=prob.loss_name, reg_name=prob.reg_name, m=prob.m,
                use_adagrad=use_adagrad)
            if obs is not None:
                span.__exit__(None, None, None)
                span = _enter(obs, "chunk_wait")
                jax.block_until_ready((w, alpha))
                record_chunk(n, time.perf_counter() - t_chunk, eta0)
                span.__exit__(None, None, None)
                span_chunk.__exit__(None, None, None)
            t += n
            if hook is not None:
                if obs is not None:
                    span_eval = _enter(obs, "eval", epoch=t)
                try:
                    entry = hook(t, w, alpha)
                    history.append(entry)
                    if obs is not None:
                        _obs_eval(obs, entry)
                finally:
                    if obs is not None:
                        span_eval.__exit__(None, None, None)
        return SolveResult(w, alpha, history, None)
    finally:
        if obs is not None:
            span_solve.__exit__(None, None, None)
