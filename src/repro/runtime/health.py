"""Numerical health: probes, the recovery ledger, and rollback policy.

PR 5 made the runtime able to *replay* faults it planned (crash plans,
resharding).  This module is the half that survives faults nobody planned:

* **Probes** — ``all_finite`` is the jitted numerical-health probe: one
  fused ``isfinite``-reduce over every leaf of a state pytree (the
  ``DSOState`` at a chunk boundary costs a few KB of reads, so the probe
  is ~free next to an epoch).
  ``objective_regression`` is the host-side monitor over the evaluation
  history: an objective that climbs a ratio above its best-so-far (plus an
  absolute slack for noise around convergence) marks the trajectory
  diverged even while every number is still finite.

* **Ledger** — every detection and every action taken is a typed
  ``LedgerEvent`` (kind / epoch / action / epochs_lost / retry / detail).
  ``Supervisor.run_sharded`` returns its ledger, and a ``HealthGuard``
  accumulates one for ``engine.solve``, so tests and examples assert on
  *recovery behavior*, not just on the final iterate.

* **Policy** — ``HealthGuard`` is the duck-typed object ``engine.solve``
  accepts as ``health=`` (the engine stays free of runtime imports, the
  same way ``store=`` is duck-typed): it owns the eta-backoff-on-rollback
  parameters (Adaptive SGD, arXiv 1802.05811: shrink the step size on
  every restart from a failure, bounded retries) and the
  exhausted-retries decision — raise a ``HealthError`` naming what
  happened, or degrade to the paper-exact serial solver.

* **Wall clock** — ``WallClockMonitor`` is the straggler detector behind
  the supervisor's replanning lane: an EWMA of *warm* per-epoch chunk
  times (chunks that just paid a jit trace are marked cold and skipped —
  a compile spike is not a straggler) against the best time seen, firing
  after ``patience`` consecutive hot chunks.

* **Chaos** — ``NaNInjector`` poisons chosen state leaves at chosen
  epochs (once each): the seam the NaN-injection tests and the
  ``--chaos`` example drive through ``solve(..., health=guard)``.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np


class HealthError(RuntimeError):
    """Numerical-health failure the rollback policy could not recover."""


# --------------------------------------------------------------- probes --


@jax.jit
def _finite_probe(leaves):
    ok = jnp.bool_(True)
    for leaf in leaves:
        ok = ok & jnp.isfinite(leaf).all()
    return ok


def all_finite(tree) -> bool:
    """Jitted all-finite check over every leaf of a state pytree.

    Returns a host bool (the probe itself is one fused device reduce; the
    sync is the caller's decision point, so there is nothing to overlap).
    """
    leaves = [jnp.asarray(leaf) for leaf in jax.tree_util.tree_leaves(tree)]
    if not leaves:
        return True
    return bool(_finite_probe(leaves))


def objective_regression(history, *, key: str = "primal",
                         ratio: float = 2.0, slack: float = 1e-3):
    """Objective-regression monitor over the evaluation history.

    Returns a diagnostic string when the newest recorded objective exceeds
    ``best_so_far * ratio + slack`` (or is non-finite), else ``None``.
    Histories without the objective key (custom eval hooks) are skipped —
    the finite probe still covers them.
    """
    vals = [h[key] for h in history if isinstance(h, dict) and key in h]
    if len(vals) < 2:
        return None
    latest, best = float(vals[-1]), float(min(vals[:-1]))
    if not np.isfinite(latest):
        return f"objective {key}={latest} is not finite"
    if latest > best * ratio + slack:
        return (f"objective regression: {key}={latest:.6g} vs best-so-far "
                f"{best:.6g} (ratio {ratio}, slack {slack})")
    return None


# --------------------------------------------------------------- ledger --


@dataclass
class LedgerEvent:
    """One typed recovery-ledger entry: what was detected, what was done.

    ``detail`` carries event-specific fields (resumed_from, eta0, worker,
    ...); ``__getitem__`` reads attributes first and falls back to
    ``detail``, so ledger entries keep the dict-style access the PR-5
    supervisor log had (``ev["kind"]``, ``ev["lost_epochs"]``).
    """

    kind: str                 # crash|reshard|straggler|nan*|health|...
    epoch: int = 0            # epoch the event was detected/fired at
    action: str = ""          # what the runtime did about it
    epochs_lost: int = 0      # re-run epochs this event cost
    retry: int = 0            # consecutive-recovery counter when relevant
    detail: dict = field(default_factory=dict)

    def __getitem__(self, k):
        if hasattr(self, k):
            return getattr(self, k)
        return self.detail[k]

    def get(self, k, default=None):
        try:
            return self[k]
        except KeyError:
            return default

    def to_dict(self) -> dict:
        return dict(kind=self.kind, epoch=self.epoch, action=self.action,
                    epochs_lost=self.epochs_lost, retry=self.retry,
                    **self.detail)


def ledger_counts(ledger) -> dict:
    """{kind: occurrences} summary of a recovery ledger."""
    out: dict = {}
    for ev in ledger:
        out[ev["kind"]] = out.get(ev["kind"], 0) + 1
    return out


def render_ledger_event(ev) -> str:
    """One recovery-ledger entry as a human-readable line.

    Accepts a ``LedgerEvent`` or any dict-like with the same keys (the
    recorder's JSONL ``type="ledger"`` events round-trip through here) —
    the ONE rendering the supervisor example, the health guard, and the
    run-report all print, so ledger lines look identical everywhere.
    """
    kind = ev["kind"]
    epoch = ev.get("epoch", 0)
    action = ev.get("action", "")
    bits = [f"{kind}@{epoch}"]
    if action:
        bits.append(action)
    lost = ev.get("epochs_lost", 0)
    if lost:
        bits.append(f"(lost {lost} epoch{'s' if lost != 1 else ''})")
    retry = ev.get("retry", 0)
    if retry:
        bits.append(f"retry={retry}")
    detail = (ev.detail if hasattr(ev, "detail")
              else {k: v for k, v in ev.items()
                    if k not in ("seq", "ts", "type", "kind", "epoch",
                                 "action", "epochs_lost", "retry")})
    if detail:
        bits.append(" ".join(f"{k}={v}" for k, v in detail.items()))
    return " ".join(bits)


# ---------------------------------------------------------------- chaos --


class NaNInjector:
    """Poison chosen ``DSOState`` leaves at chosen epochs, once each.

    ``plan`` maps epoch -> (leaf, index): leaf is ``"w"`` (one w block) or
    ``"alpha"`` (one dual shard), index the block/shard row to poison.
    The injection happens at the chunk boundary *entering* that epoch, so
    the NaN propagates through a real epoch of updates before any probe
    sees it — the honest version of the fault.
    """

    def __init__(self, plan: dict):
        self.plan = {int(e): (leaf, int(idx))
                     for e, (leaf, idx) in plan.items()}
        self.fired: set = set()

    def inject(self, state, t: int):
        if t not in self.plan or t in self.fired:
            return state
        self.fired.add(t)
        leaf, idx = self.plan[t]
        if leaf == "w":
            return state._replace(
                w_grid=state.w_grid.at[idx].set(jnp.nan))
        if leaf == "alpha":
            return state._replace(alpha=state.alpha.at[idx].set(jnp.nan))
        raise ValueError(f"NaNInjector leaf {leaf!r}: 'w' | 'alpha'")


# ---------------------------------------------------------------- guard --


class HealthGuard:
    """Rollback-with-eta-backoff policy for ``engine.solve(health=...)``.

    The driver calls, per chunk: ``inject`` (chaos seam, identity unless
    an injector was given), ``check_state`` (jitted finite probe),
    ``check_history`` (objective-regression monitor), and — on a failed
    check — reads ``eta_decay`` and calls ``record``/``exhausted``.  The
    guard owns the retry budget; the driver owns the restore mechanics
    (it has the store and the init snapshot).

    ``on_exhausted``: ``"raise"`` (default) raises ``HealthError`` once
    ``max_retries`` rollbacks were spent; ``"serial"`` asks the driver to
    degrade to the paper-exact ``solve_serial`` safe mode instead (only
    possible for Problem sources — data sources raise with a diagnostic
    saying so).
    """

    def __init__(self, *, eta_decay: float = 0.5, max_retries: int = 3,
                 regression_ratio: float = 2.0,
                 regression_slack: float = 1e-3,
                 objective_key: str = "primal",
                 on_exhausted: str = "raise", injector=None):
        if not 0.0 < eta_decay <= 1.0:
            raise ValueError(f"eta_decay must be in (0, 1], got {eta_decay}")
        if on_exhausted not in ("raise", "serial"):
            raise ValueError(f"on_exhausted {on_exhausted!r}: raise|serial")
        self.eta_decay = eta_decay
        self.max_retries = max_retries
        self.regression_ratio = regression_ratio
        self.regression_slack = regression_slack
        self.objective_key = objective_key
        self.on_exhausted = on_exhausted
        self.injector = injector
        self.retries = 0
        self.ledger: list = []
        # observability seam: ``engine.solve(obs=...)`` binds its recorder
        # here (when unset), so guard decisions land in the run-event log
        self.obs = None

    # the four driver-facing hooks ---------------------------------------
    def inject(self, state, t: int):
        return state if self.injector is None else \
            self.injector.inject(state, t)

    def check_state(self, state):
        return None if all_finite(state) else "nonfinite state"

    def check_history(self, history):
        return objective_regression(history, key=self.objective_key,
                                    ratio=self.regression_ratio,
                                    slack=self.regression_slack)

    def record(self, event: LedgerEvent):
        self.ledger.append(event)
        if self.obs is not None:
            self.obs.record_ledger(event)

    def note(self, *, kind: str, epoch: int = 0, action: str = "",
             epochs_lost: int = 0, retry: int = 0, **detail):
        """Construct-and-record in one call — the driver stays free of
        runtime imports (it never touches ``LedgerEvent`` directly)."""
        self.record(LedgerEvent(kind=kind, epoch=epoch, action=action,
                                epochs_lost=epochs_lost, retry=retry,
                                detail=detail))

    def exhausted(self, *, failure: str, epoch: int, eta0: float,
                  can_degrade: bool) -> str:
        """Called when ``retries > max_retries``.  Returns ``"serial"`` to
        request safe-mode degradation, else raises ``HealthError``."""
        diag = (f"numerical health failed at epoch {epoch} ({failure}) "
                f"after {self.retries - 1} rollback(s); eta0 backed off to "
                f"{eta0:.3g} (decay {self.eta_decay}/rollback)")
        if self.on_exhausted == "serial":
            if can_degrade:
                self.record(LedgerEvent(kind="health", epoch=epoch,
                                        action="degrade_serial",
                                        retry=self.retries,
                                        detail=dict(failure=failure)))
                return "serial"
            diag += ("; on_exhausted='serial' needs a Problem source to "
                     "rebuild the pointwise reference from")
        raise HealthError(diag)


# ----------------------------------------------------------- wall clock --


class WallClockMonitor:
    """EWMA straggler detector over warm per-epoch chunk wall times.

    ``observe(s_per_epoch, cold=...)`` returns True when the EWMA has sat
    above ``factor`` x the best warm per-epoch time seen for ``patience``
    consecutive warm chunks.  Cold chunks (first at a new scan length, or
    right after a solver rebuild — they pay a jit trace) are recorded by
    the caller but never fed here: a compile spike is not a straggler.
    """

    def __init__(self, *, factor: float = 1.8, patience: int = 1,
                 beta: float = 0.5):
        if factor <= 1.0:
            raise ValueError(f"factor must be > 1, got {factor}")
        self.factor = factor
        self.patience = patience
        self.beta = beta
        self.reset()

    def reset(self):
        """Full reset — after a reshard the epoch cost structure changed,
        so both the baseline and the EWMA restart."""
        self.baseline = None
        self.ewma = None
        self.streak = 0

    def calm(self):
        """Post-replan reset of the hot streak only: the baseline stays,
        so the detector can escalate if the replan did not help."""
        self.streak = 0
        self.ewma = None

    def observe(self, s_per_epoch: float, *, cold: bool = False) -> bool:
        if cold:
            return False
        self.baseline = (s_per_epoch if self.baseline is None
                         else min(self.baseline, s_per_epoch))
        self.ewma = (s_per_epoch if self.ewma is None else
                     self.beta * s_per_epoch + (1 - self.beta) * self.ewma)
        if self.ewma > self.factor * self.baseline:
            self.streak += 1
        else:
            self.streak = 0
        return self.streak >= self.patience
