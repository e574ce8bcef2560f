"""Unified observability: metric registry, span tracer, run-event log.

The paper's headline claim is near-linear scaling with p; this package is
how the repo watches that claim in flight.  One ``RunRecorder`` merges
three streams into a single ordered event log (JSONL) plus an end-of-run
summary dict:

   metrics.py    Counter / Gauge / Histogram with labels, memoized in a
     |           MetricRegistry bound to the recorder
     |               rows/s, nnz/s, packed bytes/s, eta, primal, pd_gap,
     |               ingest rows/malformed/quarantined, serving tokens
   trace.py      SpanTracer: nested host spans on perf_counter, each
     |           with an integer id, its parent's id and the id of the
     |           enclosing ``solve`` (the request id) -> JSONL span
     |           events; optional jax.profiler.TraceAnnotation
     |           pass-through carrying the same ids, so the JSONL log
     |           joins the profiler's trace (the device's clock) by id;
     |           python_gc annotations for collector pauses
   recorder.py   RunRecorder: the ONE sink; also absorbs the runtime's
                 typed LedgerEvent stream (record_ledger), so health and
                 replan decisions land between the throughput samples
                 that motivated them.
   telemetry.py  TelemetrySpec: the DEVICE-side lane — per-(epoch, inner
                 iteration r, worker q) buffers of update norms, rows/nnz
                 processed, and nonfinite flags accumulated as an extra
                 carry INSIDE the jitted epoch scan, drained host-side at
                 chunk boundaries into ``type="telemetry"`` events; comm
                 bytes per slot priced from the schedule's permutations
                 (ring / p2p routes / allgather).  Heatmap renderers
                 (nnz_throughput, wall_balance) fold the stream into the
                 per-tile matrices ``report.py --section heatmap`` shows.

Seams (all duck-typed ``obs=``, default ``None`` — the layers below never
import this package):

  engine.solve(..., obs=rec)       solve > solve_setup | epoch_chunk >
                                   (chunk_schedule | chunk_dispatch |
                                   chunk_wait) | eval > eval_gather spans,
                                   per-chunk throughput gauges, eval
                                   metrics (primal, pd_gap)
  engine.solve_serial(..., obs=rec)
  runtime.Supervisor(..., obs=rec) same stream: epoch_chunk/snapshot_save/
                                   restore/reshard spans, ledger events
  core.dso_dist.ShardedDSO(obs=)   restore spans + metrics() gauges
  sparse.ingest_libsvm(..., obs=)  ingest passes as spans, rows/malformed/
                                   quarantined counters
  serving.DecodeEngine(obs=)       serve_batch spans, request/token
                                   counters, tokens/s gauge

plus the device lane (duck-typed ``telemetry=``, default ``None``):

  engine.solve(..., telemetry=spec)        grid scan telemetry carry
  ShardedDSO(..., telemetry=spec)          sharded scan telemetry carry
  runtime.Supervisor(..., telemetry=spec)  threads the spec through every
                                           rebuild/reshard AND attributes
                                           simulated straggler sleeps

Event schema — one JSON object per line, ``seq`` (monotone int) and
``ts`` (seconds since recorder construction) on every event:

  {"seq", "ts", "type": "meta",   ...run identity (free-form)}
  {"seq", "ts", "type": "metric", "name", "kind": "counter"|"gauge"|
      "histogram", "value"[, "labels"]}
  {"seq", "ts", "type": "span",   "name", "t0", "dur_s", "depth", "id",
      "parent", "solve"[, "attrs"]}
  {"seq", "ts", "type": "ledger", "kind", "epoch", "action",
      "epochs_lost", "retry", ...detail fields}
  {"seq", "ts", "type": "telemetry", "kind": "chunk", "t0", "epochs",
      "p", "db", "transport": "ring"|"p2p"|"allgather", "wall_s",
      "eta": [per-epoch], "nonfinite": int, and per-(epoch, r, q) nested
      lists "dw_norm", "dalpha_norm", "rows", "nnz", "comm_bytes"}
  {"seq", "ts", "type": "telemetry", "kind": "delay", "worker",
      "seconds", "t0", "epochs"}   (host-attributed straggler wall time)

``benchmarks/report.py --section run-report --events <log.jsonl>``
renders a log into the human-readable scaling/recovery report, and
``examples/elastic_dso.py --chaos`` writes one per run (uploaded as the
CI chaos artifact).

METRICS-OFF CONTRACT: every seam defaults to ``obs=None`` and guards all
instrumentation behind ``if obs is not None``.  With ``obs=None`` the
chunk loop performs no obs calls and allocates nothing for obs, and
trajectories are bit-identical to a recorder-on run (the recorder only
observes; it never touches solver state) — both pinned by
tests/test_obs.py.  With a recorder on, the per-chunk cost is a handful
of dict appends: tests/test_obs.py pins the number of events one chunk
emits, and PERF.md keeps the traced cost measured on the chip.
"""

from repro.obs.metrics import (Counter, Gauge, Histogram, Metric,
                               MetricRegistry)
from repro.obs.recorder import RunRecorder, iter_events, read_events
from repro.obs.telemetry import (TELEMETRY_FIELDS, TelemetrySpec,
                                 comm_bytes_matrix, nnz_throughput,
                                 render_heatmap, wall_balance)
from repro.obs.trace import SpanTracer

__all__ = [
    "Counter", "Gauge", "Histogram", "Metric", "MetricRegistry",
    "RunRecorder", "iter_events", "read_events",
    "TELEMETRY_FIELDS", "TelemetrySpec", "comm_bytes_matrix",
    "nnz_throughput", "render_heatmap", "wall_balance",
    "SpanTracer",
]
