"""Elastic runtime: checkpointed DSO state, deterministic resume, p -> p'
live resharding, and self-healing around the engine.

The engine (``repro.engine``) is a pure function of (data layout, schedule,
state): it holds everything in device memory and bakes the processor count
p into the block grid at ingest.  This layer makes that survivable and
elastic.  Data flow:

      engine.solve(..., checkpoint_every=k, store=S,         ShardedDSO
        |          health=guard)                               | .solver_state()
        |  every k epochs: the COMPLETE solver state           | .snapshot_config()
        |  (w, alpha, gw/ga, RNG key, cursor, history,         | .wait()
        v   config) crosses the seam as one DSOSnapshot        v
   snapshot.py ──────────────────────────────────────────────────────────
        |   flat-npz pytree codec (atomic writes; the same codec
        |   training/checkpoint.py delegates to) + per-leaf CRC32 and a
        |   whole-file digest (verify_pytree) + SnapshotStore
        |   (dso_<epochs_done>.npz, latest-VALID-wins: corrupt files are
        |   quarantined; retention GC via keep_last / keep_every pinning).
        |   async_writes=True moves the npz serialization + atomic rename
        |   to a single background writer thread: save() blocks only for
        |   the device->host fetch (the donation hazard) and the epoch
        |   loop overlaps the disk write; flush() is the durability
        |   barrier (re-raising writer errors), every read path
        |   (load/latest/epochs/verify/quarantine) barriers automatically,
        |   and a SIGKILL mid-write leaves only an invisible .tmp file —
        |   latest-VALID-wins is unchanged
        |
        ├──> health.py      all_finite (jitted probe) + objective-
        |                   regression monitor; HealthGuard = the rollback
        |                   -with-eta-backoff policy solve(health=) runs;
        |                   WallClockMonitor = the straggler EWMA;
        |                   LedgerEvent = the typed recovery ledger every
        |                   detection/action lands in; NaNInjector = the
        |                   chaos seam
        |
        ├──> resume.py      solve(..., init=snap): replays the config and
        |                   threads (key, cursor) back into schedules.draw
        |                   — bit-identical to the uninterrupted run
        |                   (draw's chunk-invariance contract)
        |
        ├──> reshard.py     p -> p': when the padded sizes agree and p/p'
        |                   divide evenly, sparse.format.regrid_direct
        |                   re-blocks tile->tile (merge/split of shard
        |                   entry lists through the SAME addressing pass
        |                   and packers a fresh ingest would run — no
        |                   global CSR, no lexsort); otherwise
        |                   grid_to_csr + the normal tilers re-tile at p'
        |                   (both paths equal field-for-field, pinned by
        |                   tests).  reshard_state repartitions the
        |                   blocked state — same iterate, new grid.
        |                   Exact at p' == p; a different serializable
        |                   execution otherwise.
        |
        └──> supervisor.py  Supervisor(store, fault_plan).run_sharded():
                            chunks ShardedDSO.run_epochs between
                            checkpoint boundaries and planned faults;
                            crash -> restore latest VALID snapshot (re-run
                            is bit-identical; streak-capped with eta
                            backoff), reshard -> live resize onto a new
                            mesh, nan/corrupt -> caught by the health
                            lane, persistent straggler -> wall-clock EWMA
                            replans (lpt schedule, then live reshard).
                            Returns (opt, recovery ledger).

Observability (``repro.obs``, duck-typed ``obs=`` — this package never
imports it):

        Supervisor(..., obs=rec) / HealthGuard (rec bound by solve)
        |   every LedgerEvent is ALSO forwarded to the recorder
        |   (record_ledger), and snapshot_save / restore / reshard /
        |   epoch_chunk land as timed spans next to the per-chunk
        |   throughput gauges — one ordered run-event JSONL stream
        v
        obs.RunRecorder ──> the JSONL run-event log (obs.read_events)

``render_ledger_event`` is the one human-readable rendering of a ledger
event, shared by the examples.

Nothing here re-implements solver math: snapshots capture exactly what the
epoch driver threads between chunks, which is why resume can promise 0.0
drift instead of "close enough".
"""

from repro.runtime.health import (HealthError, HealthGuard, LedgerEvent,
                                  NaNInjector, WallClockMonitor, all_finite,
                                  ledger_counts, objective_regression,
                                  render_ledger_event)
from repro.runtime.reshard import reshard, reshard_state, retile
from repro.runtime.resume import check_resumable, resume, solve_kwargs
from repro.runtime.snapshot import (DSOSnapshot, SnapshotIntegrityError,
                                    SnapshotStore, flatten_pytree,
                                    load_pytree, load_snapshot, read_meta,
                                    save_pytree, save_snapshot,
                                    verify_pytree)
from repro.runtime.supervisor import (FaultEvent, Supervisor, make_fault_plan,
                                      periodic_crashes)

__all__ = [
    "DSOSnapshot", "SnapshotIntegrityError", "SnapshotStore",
    "flatten_pytree", "load_pytree", "load_snapshot", "read_meta",
    "save_pytree", "save_snapshot", "verify_pytree",
    "HealthError", "HealthGuard", "LedgerEvent", "NaNInjector",
    "WallClockMonitor", "all_finite", "ledger_counts",
    "objective_regression", "render_ledger_event",
    "check_resumable", "resume", "solve_kwargs",
    "reshard", "reshard_state", "retile",
    "FaultEvent", "Supervisor", "make_fault_plan", "periodic_crashes",
]
