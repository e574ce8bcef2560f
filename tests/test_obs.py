"""Observability layer: recorder round-trip, span nesting and ids, the
engine's span tree and its join with the profiler's trace, the metrics-off
no-op contract (bit-identical trajectories, zero obs work in the chunk
loop), and the pinned number of events one chunk records.

The contract under test (obs/__init__.py): every ``obs=`` seam defaults to
``None`` and guards all instrumentation behind ``if obs is not None``;
with a recorder attached, every metric sample, span, and ledger event
lands in ONE ordered JSONL stream the run report can render.
"""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from repro.data.synthetic import make_classification  # noqa: E402
from repro.obs import (Counter, Gauge, Histogram, MetricRegistry,  # noqa: E402
                       RunRecorder, SpanTracer, read_events)


def _prob(m=64, d=48, density=0.15, seed=0):
    return make_classification(m=m, d=d, density=density, loss="hinge",
                               lam=1e-3, seed=seed)


# ------------------------------------------------------------- registry --


def test_registry_memoizes_and_separates_labels():
    reg = MetricRegistry()
    c1 = reg.counter("rows", phase="train")
    c2 = reg.counter("rows", phase="train")
    c3 = reg.counter("rows", phase="eval")
    assert c1 is c2 and c1 is not c3
    c1.inc(3)
    c1.inc()
    assert c1.value == 4.0 and c3.value == 0.0
    assert len(reg) == 2


def test_registry_kind_conflict_raises():
    reg = MetricRegistry()
    reg.counter("x")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x")


def test_counter_monotone():
    with pytest.raises(ValueError, match="cannot decrease"):
        MetricRegistry().counter("c").inc(-1)


def test_histogram_summary():
    h = MetricRegistry().histogram("h")
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    assert h.count == 3 and h.sum == 6.0
    assert h.min == 1.0 and h.max == 3.0 and h.mean == 2.0


def test_registry_snapshot_shapes():
    reg = MetricRegistry()
    reg.gauge("g").set(1.5)
    reg.histogram("h").observe(2.0)
    snap = reg.snapshot()
    assert snap["g"] == {"kind": "gauge", "value": 1.5}
    assert snap["h"]["count"] == 1 and snap["h"]["mean"] == 2.0


# ---------------------------------------------------------------- spans --


def test_span_nesting_depth_and_order():
    rec = RunRecorder()
    with rec.span("outer"):
        with rec.span("inner", k=1):
            pass
    spans = [e for e in rec.events if e["type"] == "span"]
    # inner exits (and is recorded) first; depth reflects nesting
    assert [s["name"] for s in spans] == ["inner", "outer"]
    assert spans[0]["depth"] == 1 and spans[1]["depth"] == 0
    assert spans[0]["attrs"] == {"k": 1}
    assert spans[1]["dur_s"] >= spans[0]["dur_s"]


def test_span_tracer_injectable_clock():
    ticks = iter([0.0, 1.0, 3.0, 6.0])
    tracer = SpanTracer(clock=lambda: next(ticks))

    class Sink:
        events = []

        def record(self, **ev):
            self.events.append(ev)

    tracer._sink = sink = Sink()
    with tracer.span("a"):
        pass
    assert sink.events[0]["dur_s"] == 2.0   # t0=1.0 (after epoch0), end=3.0


# ------------------------------------------------------------- recorder --


def test_recorder_jsonl_round_trip_and_ordering(tmp_path):
    path = str(tmp_path / "run.jsonl")
    rec = RunRecorder(path, meta=dict(run="t", shape=[2, 3]))
    rec.metrics.counter("ingest.rows").inc(5)
    with rec.span("epoch_chunk", epochs=2):
        rec.metrics.gauge("rows_per_s").set(10.0)
    rec.record_ledger(dict(kind="crash", epoch=3, action="restore",
                           epochs_lost=1, retry=1))
    rec.close()
    back = read_events(path)
    assert [e["seq"] for e in back] == list(range(len(back)))
    assert back == rec.events
    assert [e["type"] for e in back] == ["meta", "metric", "metric",
                                        "span", "ledger"]
    # ts is monotone non-decreasing along the stream
    ts = [e["ts"] for e in back]
    assert ts == sorted(ts)
    summary = rec.summary()
    assert summary["events"] == 5
    assert summary["ledger"] == {"crash": 1}
    assert "epoch_chunk" in summary["spans"]


def test_recorder_tolerates_truncated_tail(tmp_path):
    path = str(tmp_path / "run.jsonl")
    rec = RunRecorder(path)
    rec.metrics.counter("c").inc()
    rec.metrics.counter("c").inc()
    rec.close()
    with open(path, "a") as f:
        f.write('{"seq": 99, "ts": 1.0, "type": "met')   # crashed mid-write
    back = read_events(path)
    assert len(back) == 2 and back[-1]["seq"] == 1


def test_recorder_jsonable_coercion(tmp_path):
    path = str(tmp_path / "run.jsonl")
    rec = RunRecorder(path)
    rec.record(type="meta", np_scalar=np.float32(1.5),
               arr=[np.int64(2)], weird=object())
    rec.close()
    ev = read_events(path)[0]
    assert ev["np_scalar"] == 1.5 and ev["arr"] == [2]
    assert isinstance(ev["weird"], str)


def test_ledger_event_forwarding():
    from repro.runtime.health import LedgerEvent
    rec = RunRecorder()
    ev = LedgerEvent(kind="nan", epoch=4, action="injected",
                     detail=dict(block=1))
    rec.record_ledger(ev)
    assert rec.ledger == [ev]
    got = rec.events[-1]
    assert got["type"] == "ledger" and got["kind"] == "nan"
    assert got["block"] == 1
    assert rec.ledger_counts() == {"nan": 1}


# ------------------------------------------------- solve() integration --


def test_solve_records_expected_stream(tmp_path):
    from repro.engine import pd_gap_eval_hook, solve
    prob = _prob()
    path = str(tmp_path / "run.jsonl")
    with RunRecorder(path) as rec:
        solve(prob, epochs=4, p=4, eta0=0.5, eval_every=2,
              eval_hook=pd_gap_eval_hook(prob), obs=rec)
        events = list(rec.events)
    back = read_events(path)
    assert back == events
    names = {e["name"] for e in events if e["type"] == "metric"}
    assert {"rows_per_s", "nnz_per_s", "packed_bytes_per_s", "eta",
            "epoch_s", "eval.primal", "eval.dual",
            "eval.pd_gap"} <= names
    spans = {e["name"] for e in events if e["type"] == "span"}
    assert {"epoch_chunk", "eval"} <= spans
    assert events[0]["type"] == "meta" and events[0]["phase"] == "solve"


CHUNK_CHILDREN = ("chunk_schedule", "chunk_dispatch", "chunk_wait")


def _spans(rec):
    return [e for e in rec.events if e["type"] == "span"]


def test_solve_span_tree(monkeypatch):
    """One solve gives one ``solve`` root whose id every span carries;
    each span's parent is the span it ran in; the chunk's children cover
    it, so its self time is the recorder's bookkeeping only.  The clock
    only moves inside the work: the step sizes (schedule), the epoch
    program (dispatch) and the gather of w (eval_gather)."""
    import repro.engine.driver as drv
    from repro.engine import pd_gap_eval_hook

    now = [0.0]
    for name, cost in (("eta_schedule", 1.0), ("run_epochs", 10.0),
                       ("gather_w", 100.0)):
        real = getattr(drv, name)

        def ticking(*a, _real=real, _cost=cost, **kw):
            now[0] += _cost
            return _real(*a, **kw)

        monkeypatch.setattr(drv, name, ticking)
    rec = RunRecorder(clock=lambda: now[0])
    prob = _prob()
    for _ in range(2):
        drv.solve(prob, epochs=4, p=4, eta0=0.5, eval_every=2,
                  eval_hook=pd_gap_eval_hook(prob), obs=rec)
    spans = _spans(rec)
    by_id = {s["id"]: s for s in spans}
    assert sorted(by_id) == list(range(len(spans)))   # every span closed
    roots = [s for s in spans if s["name"] == "solve"]
    assert len(roots) == 2 and all(r["parent"] is None for r in roots)
    assert all(r["solve"] == r["id"] for r in roots)
    want_parent = {"solve_setup": "solve", "epoch_chunk": "solve",
                   "eval": "solve", "eval_gather": "eval",
                   **{c: "epoch_chunk" for c in CHUNK_CHILDREN}}
    for s in spans:
        if s["name"] == "solve":
            continue
        parent = by_id[s["parent"]]
        assert parent["name"] == want_parent[s["name"]], s
        assert s["solve"] == parent["solve"]
        assert parent["t0"] <= s["t0"]
        assert s["t0"] + s["dur_s"] <= parent["t0"] + parent["dur_s"]
    for root in roots:
        mine = [s for s in spans if s["solve"] == root["id"]]
        names = [s["name"] for s in mine]
        assert names.count("epoch_chunk") == names.count("eval") == 2
        for chunk in (s for s in mine if s["name"] == "epoch_chunk"):
            kids = [s for s in mine if s["parent"] == chunk["id"]]
            assert [k["name"] for k in kids] == list(CHUNK_CHILDREN)
            assert [k["dur_s"] for k in kids] == [1.0, 10.0, 0.0]
            assert sum(k["dur_s"] for k in kids) == chunk["dur_s"]
        gathers = [s["dur_s"] for s in mine if s["name"] == "eval_gather"]
        assert gathers == [100.0, 100.0]


class _Stop(Exception):
    pass


def test_spans_close_when_hook_raises():
    """The benchmark ends every solve by raising from its hook: eval and
    solve still close, the tracer's stack is empty, every span entered was
    recorded with the ids it was given, and the next solve is a new
    root."""
    from repro.engine import solve

    def hook(t, w, alpha):
        if t == 2:
            raise _Stop
        return {"epoch": t}

    rec = RunRecorder()
    prob = _prob()
    with pytest.raises(_Stop):
        solve(prob, epochs=6, p=4, eta0=0.5, eval_every=1, eval_hook=hook,
              obs=rec)
    assert rec.tracer.depth == 0
    spans = _spans(rec)
    assert sorted(s["id"] for s in spans) == list(range(len(spans)))
    names = [s["name"] for s in spans]
    assert names.count("eval") == names.count("epoch_chunk") == 2
    last = spans[-1]
    assert last["name"] == "solve" and last["parent"] is None
    evals = [s for s in spans if s["name"] == "eval"]
    assert all(s["parent"] == last["id"] == s["solve"] for s in evals)
    solve(prob, epochs=1, p=4, eta0=0.5, eval_hook=None, obs=rec)
    root = _spans(rec)[-1]
    assert root["name"] == "solve" and root["parent"] is None
    assert root["id"] == len(spans)      # ids go on where the raise left


def _xplane_host_events(trace_dir):
    import glob
    import warnings

    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore", DeprecationWarning)
                    stats = dict(e.stats)
                out.append((e.name, int(e.start_ns), int(e.duration_ns),
                            stats))
    return out


def test_python_gc_span_while_recorder_open(tmp_path):
    """A collector pause is a ``python_gc`` host event in the profiler's
    trace while a recorder with annotations is open, and not after its
    ``close`` (which unregisters the hook)."""
    import gc

    import jax
    from jax.profiler import TraceAnnotation

    others = list(gc.callbacks)    # hooks other recorders left registered
    gc.callbacks.clear()
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            rec = RunRecorder(jax_annotations=True)
            assert len(gc.callbacks) == 1
            gc.collect()
            rec.close()
            assert gc.callbacks == []
            with TraceAnnotation("closed"):
                gc.collect()
        finally:
            jax.profiler.stop_trace()
    finally:
        gc.callbacks.extend(others)
    events = _xplane_host_events(str(tmp_path))
    closed = next(e for e in events if e[0] == "closed")
    pauses = [e for e in events if e[0] == "python_gc"]
    assert pauses and all(s + d <= closed[1] for _, s, d, _ in pauses)
    assert 2 in {st["generation"] for *_, st in pauses}   # gc.collect()


def test_jsonl_spans_join_the_profiler_trace(tmp_path):
    """With annotations on, every JSONL span is exactly one host event of
    the profiler's trace, joined by ``id``, with the same name and parent
    and a duration within 1 ms: the log and the device's clock join."""
    import jax
    from repro.engine import pd_gap_eval_hook, solve

    prob = _prob()
    rec = RunRecorder(jax_annotations=True)
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            solve(prob, epochs=3, p=4, eta0=0.5, eval_every=1,
                  eval_hook=pd_gap_eval_hook(prob), obs=rec)
        finally:
            jax.profiler.stop_trace()
    finally:
        rec.close()
    spans = _spans(rec)
    assert len(spans) == 2 + 3 * 6
    joined = {}
    for name, start, dur, st in _xplane_host_events(str(tmp_path)):
        if "id" in st and "hlo_op" not in st:
            assert st["id"] not in joined
            joined[st["id"]] = (name, st.get("parent"), st.get("solve"),
                                dur)
    assert sorted(joined) == sorted(s["id"] for s in spans)
    for s in spans:
        name, parent, solve_id, dur_ns = joined[s["id"]]
        assert (name, parent, solve_id) == (s["name"], s["parent"],
                                            s["solve"])
        assert abs(dur_ns / 1e9 - s["dur_s"]) < 1e-3
    # the tile kernel the solve resolved rides on its set-up span
    setup = [st for name, _, _, st in _xplane_host_events(str(tmp_path))
             if name == "solve_setup"]
    assert [st.get("backend") for st in setup] == ["dense_jnp"]


@pytest.mark.parametrize("backend,want", [("auto", "sparse_jnp"),
                                          ("sparse", "sparse_jnp"),
                                          ("dense_jnp", "dense_jnp")])
def test_solve_setup_names_the_backend(backend, want):
    """``solve_setup`` carries the resolved backend's name as ``backend``,
    whatever selector asked for it (``auto`` picks the sparse layout for
    this problem; on the CPU, XLA's gather)."""
    from repro.engine import solve

    rec = RunRecorder()
    solve(_prob(density=0.05), backend=backend, epochs=1, p=4, eta0=0.5,
          eval_hook=None, obs=rec)
    setup = [s for s in _spans(rec) if s["name"] == "solve_setup"]
    assert [s["attrs"] for s in setup] == [{"backend": want}]


def test_supervisor_chaos_stream_ordered(tmp_path):
    from repro.core.dso_dist import make_dso_mesh
    from repro.runtime import (FaultEvent, SnapshotStore, Supervisor)
    prob = _prob()
    rec = RunRecorder(str(tmp_path / "run.jsonl"))
    plan = (FaultEvent(2, "crash"), FaultEvent(4, "nan", 0))
    sup = Supervisor(SnapshotStore(str(tmp_path / "store")),
                     checkpoint_every=2, eta0=0.5, fault_plan=plan, obs=rec)
    _, ledger = sup.run_sharded(prob, 6, mesh=make_dso_mesh(1), impl="jnp",
                                seed=5)
    rec.close()
    back = read_events(rec.path)
    assert [e["seq"] for e in back] == list(range(len(back)))
    # every supervision decision reached the recorder, in ledger order
    rec_ledger = [e for e in back if e["type"] == "ledger"]
    assert [e["kind"] for e in rec_ledger] == [ev.kind for ev in ledger]
    spans = {e["name"] for e in back if e["type"] == "span"}
    assert {"epoch_chunk", "snapshot_save", "restore"} <= spans
    assert {"eval.primal", "eval.gap"} <= {
        e["name"] for e in back if e["type"] == "metric"}


def test_health_guard_forwards_to_recorder():
    from repro.runtime.health import HealthGuard
    rec = RunRecorder()
    guard = HealthGuard()
    guard.obs = rec
    guard.note(kind="health", epoch=3, action="rollback", failure="nan")
    assert len(guard.ledger) == 1
    assert rec.events[-1]["kind"] == "health"
    assert rec.events[-1]["failure"] == "nan"


# ------------------------------------------------- metrics-off contract --


def test_engine_never_imports_obs():
    """The obs seam is duck-typed: importing the engine (and runtime) must
    not pull repro.obs into sys.modules."""
    import subprocess
    code = ("import sys\n"
            "import repro.engine, repro.runtime, repro.sparse.ingest\n"
            "import repro.serving.engine\n"
            "bad = [m for m in sys.modules if m.startswith('repro.obs')]\n"
            "assert not bad, bad\n"
            "print('CLEAN')\n")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH=os.path.join(REPO, "src")))
    assert out.returncode == 0 and "CLEAN" in out.stdout, out.stderr


def test_metrics_off_is_true_noop(monkeypatch):
    """With obs=None the chunk loop must perform NO obs work: poison every
    obs helper so any obs-path call raises."""
    import repro.engine.driver as drv

    def boom(*a, **kw):
        raise AssertionError("obs path entered with obs=None")

    monkeypatch.setattr(drv, "_obs_throughput", boom)
    monkeypatch.setattr(drv, "_obs_eval", boom)
    # every span site (solve, solve_setup, the chunk's children, eval,
    # eval_gather, restore, snapshot_save) opens through _enter
    monkeypatch.setattr(drv, "_enter", boom)
    # telemetry=None must likewise never compile/enter the telemetry scan
    monkeypatch.setattr(drv, "run_epochs_telemetry", boom)
    prob = _prob()
    res = drv.solve(prob, epochs=3, p=4, eta0=0.5)
    assert len(res.history) == 3
    res = drv.solve_serial(prob, epochs=2, eta0=0.5)
    assert len(res.history) == 2


def test_metrics_off_bit_identical(tmp_path):
    """The recorder only observes: trajectories with obs on and off are
    bit-identical."""
    from repro.engine import solve
    prob = _prob()
    kw = dict(epochs=6, p=4, eta0=0.5, eval_every=2, seed=0)
    r_off = solve(prob, **kw)
    with RunRecorder(str(tmp_path / "run.jsonl")) as rec:
        r_on = solve(prob, obs=rec, **kw)
    assert bool((np.asarray(r_off.w) == np.asarray(r_on.w)).all())
    assert bool((np.asarray(r_off.alpha) == np.asarray(r_on.alpha)).all())
    assert [h["primal"] for h in r_off.history] == \
        [h["primal"] for h in r_on.history]


def test_recorder_overhead_amortized(tmp_path):
    """The recorder's cost per chunk, pinned as the exact number of events
    one chunk emits: the four chunk spans (epoch_chunk, chunk_schedule,
    chunk_dispatch, chunk_wait), the five throughput samples (rows_per_s,
    nnz_per_s, packed_bytes_per_s, eta, epoch_s) and, at an evaluation,
    eval and eval_gather — plus one telemetry event when the lane drains.
    A solve adds meta, solve_setup and solve once.  Growth shows here in
    review; the time it costs is a chip reading (PERF.md: traced solver
    time per epoch against the parent's, budget 2%)."""
    from repro.engine import solve
    from repro.obs import TelemetrySpec

    prob = _prob()
    kw = dict(p=4, eta0=0.5, eval_every=2, seed=0,
              eval_hook=lambda t, w, alpha: {"epoch": t})
    for chunks in (1, 3):
        for lane in (False, True):
            rec = RunRecorder(str(tmp_path / "run.jsonl"))
            solve(prob, epochs=2 * chunks, obs=rec,
                  telemetry=TelemetrySpec(obs=rec) if lane else None, **kw)
            rec.close()
            kinds = {}
            for e in rec.events:
                kinds[e["type"]] = kinds.get(e["type"], 0) + 1
            want = {"meta": 1, "span": 2 + 6 * chunks, "metric": 5 * chunks}
            if lane:
                want["telemetry"] = chunks
            assert kinds == want, (chunks, lane)


# ------------------------------------------------------------ run report --


def test_run_report_renders_chaos_log(tmp_path):
    from benchmarks.report import run_report
    path = str(tmp_path / "run.jsonl")
    rec = RunRecorder(path, meta=dict(run="unit"))
    record = None
    rec.metrics.counter("ingest.rows").inc(10)
    with rec.span("epoch_chunk", epochs=2):
        rec.metrics.gauge("rows_per_s").set(1e6)
        rec.metrics.gauge("eval.primal").set(0.5)
    rec.metrics.gauge("eval.primal").set(0.25)
    rec.record_ledger(dict(kind="crash", epoch=2, action="restore",
                           epochs_lost=1, retry=1))
    rec.close()
    del record
    text = run_report(path)
    assert "rows_per_s" in text and "1.00M" in text
    assert "eval.primal: 0.5 -> 0.25" in text
    assert "epoch_chunk" in text
    assert "crash@2 restore" in text
    assert "ingest.rows: 10" in text


def test_report_cli_run_report(tmp_path):
    import subprocess
    path = str(tmp_path / "run.jsonl")
    with RunRecorder(path) as rec:
        rec.metrics.gauge("rows_per_s").set(42.0)
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.report", "--section",
         "run-report", "--events", path],
        capture_output=True, text=True, cwd=REPO,
        env=dict(os.environ, PYTHONPATH=os.path.join(REPO, "src")))
    assert out.returncode == 0, out.stderr
    assert "Run report" in out.stdout and "rows_per_s" in out.stdout


# ------------------------------------------------------- telemetry lane --


def test_telemetry_fields_literal_sync():
    """engine.driver carries its own literal copy of TELEMETRY_FIELDS so
    the engine never imports repro.obs — the two tuples must stay
    identical (this test is the sync contract)."""
    from repro.engine import driver
    from repro.obs import TELEMETRY_FIELDS
    assert driver.TELEMETRY_FIELDS == TELEMETRY_FIELDS
    assert TELEMETRY_FIELDS == ("dw_norm", "dalpha_norm", "rows", "nnz",
                                "nonfinite")


def test_comm_bytes_matrix_ring_and_allgather():
    from repro.obs import comm_bytes_matrix
    p, db = 4, 16
    blk = 2 * 4 * db
    perms = np.tile(np.arange(p), (2, p, 1))
    ring = comm_bytes_matrix(perms, db, "ring")
    assert ring.shape == (2, p, p)
    assert (ring == blk).all()          # one ppermute per inner iteration
    ag = comm_bytes_matrix(perms, db, "allgather")
    # p payloads per fetch; the end-of-epoch restore folds into row p-1
    assert (ag[:, : p - 1] == blk * p).all()
    assert (ag[:, p - 1] == 2 * blk * p).all()
    with pytest.raises(ValueError, match="transport"):
        comm_bytes_matrix(perms, db, "smoke-signals")


def test_comm_bytes_matrix_p2p_hand_case():
    """p=2, epoch perm [[0,1],[1,0]]: the first route is the identity
    (elided), the swap before r=1 moves both blocks, and the end-of-epoch
    restore swaps them back into the last row -> [[0, 0], [2blk, 2blk]]."""
    from repro.obs import comm_bytes_matrix
    db = 8
    blk = 2 * 4 * db
    out = comm_bytes_matrix([[[0, 1], [1, 0]]], db, "p2p")
    np.testing.assert_array_equal(
        out, [[[0.0, 0.0], [2.0 * blk, 2.0 * blk]]])


def test_telemetry_spec_drain_schema_and_validation(tmp_path):
    from repro.obs import TelemetrySpec, iter_events
    path = str(tmp_path / "ev.jsonl")
    rec = RunRecorder(path)
    tel = TelemetrySpec(obs=rec)
    with pytest.raises(ValueError, match="telemetry buffer"):
        tel.drain(np.zeros((2, 2, 2, 3)), t0=0, etas=[0.5, 0.5],
                  perms=np.tile(np.arange(2), (2, 2, 1)), db=4,
                  transport="ring")
    buf = np.zeros((2, 2, 2, 5), np.float32)
    buf[..., 3] = 7.0
    buf[1, 0, 1, 4] = 1.0                    # one nonfinite probe fired
    tel.drain(buf, t0=4, etas=[0.5, 0.25],
              perms=np.tile(np.arange(2), (2, 2, 1)), db=4,
              transport="ring", wall_s=0.125)
    tel.attribute_delay(1, 0.75, t0=5, epochs=2)
    rec.close()
    assert tel.nonfinite_total() == 1
    evs = [e for e in iter_events(path) if e.get("type") == "telemetry"]
    kinds = [e["kind"] for e in evs]
    assert kinds == ["chunk", "delay"]
    chunk = evs[0]
    assert chunk["t0"] == 4 and chunk["epochs"] == 2 and chunk["p"] == 2
    assert chunk["transport"] == "ring" and chunk["nonfinite"] == 1
    assert chunk["eta"] == [0.5, 0.25]
    assert np.asarray(chunk["nnz"]).shape == (2, 2, 2)
    assert np.asarray(chunk["comm_bytes"]).shape == (2, 2, 2)
    want = {"type": "telemetry", "kind": "delay", "worker": 1,
            "seconds": 0.75, "t0": 5, "epochs": 2}
    assert {k: evs[1][k] for k in want} == want    # recorder adds seq/ts


def _toy_spec(slow_worker=2, p=4):
    """Two drained chunks with flat nnz plus one attributed straggler
    delay inside the second chunk's epoch window."""
    from repro.obs import TelemetrySpec
    tel = TelemetrySpec()
    perms = np.tile(np.arange(p), (2, p, 1))
    for t0 in (0, 2):
        buf = np.ones((2, p, p, 5), np.float32)
        buf[..., 4] = 0.0
        tel.drain(buf, t0=t0, etas=[0.5, 0.5], perms=perms, db=4,
                  transport="ring", wall_s=0.4)
    tel.attribute_delay(slow_worker, 3.0, t0=2, epochs=2)
    tel.attribute_delay(slow_worker, 3.0, t0=99, epochs=1)  # out of range
    return tel


def test_wall_balance_pins_attributed_straggler():
    from repro.obs import wall_balance
    tel = _toy_spec(slow_worker=2)
    mat, t0s = wall_balance(tel)
    assert t0s == [0, 2] and mat.shape == (4, 2)
    # flat nnz -> wall split evenly; the delay lands whole on worker 2's
    # row for the chunk containing t0=2 only (the t0=99 record matches no
    # chunk and is dropped)
    np.testing.assert_allclose(mat[:, 0], 0.1)
    np.testing.assert_allclose(mat[[0, 1, 3], 1], 0.1)
    np.testing.assert_allclose(mat[2, 1], 0.1 + 3.0)
    assert int(np.argmax(mat.sum(axis=1))) == 2


def test_render_heatmap_from_event_generator(tmp_path):
    """render_heatmap folds a one-shot iter_events generator into BOTH
    matrices (throughput + wall balance) — the generator must be
    normalized once, not consumed twice."""
    from repro.obs import TelemetrySpec, iter_events, render_heatmap
    path = str(tmp_path / "ev.jsonl")
    src = _toy_spec(slow_worker=1)
    with RunRecorder(path) as rec:
        tel = TelemetrySpec(obs=rec)
        for c in src.chunks:
            tel.drain(c.buf, t0=c.t0, etas=c.etas,
                      perms=np.tile(np.arange(c.p), (c.epochs, c.p, 1)),
                      db=c.db, transport=c.transport, wall_s=c.wall_s)
        tel.attribute_delay(1, 3.0, t0=2, epochs=2)
    text = render_heatmap(iter_events(path))
    assert "(no telemetry)" not in text
    assert "nnz throughput" in text and "wall balance" in text
    assert "argmax worker: 1" in text


def test_iter_events_is_lazy_and_tolerates_truncation(tmp_path):
    from repro.obs import iter_events
    path = str(tmp_path / "ev.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"type": "a"}) + "\n")
        f.write(json.dumps({"type": "b"}) + "\n")
        f.write('{"type": "tru')               # crash-truncated tail
    gen = iter_events(path)
    assert not isinstance(gen, list)           # a true generator
    assert next(gen)["type"] == "a"
    assert [e["type"] for e in gen] == ["b"]   # bad tail dropped
    assert read_events(path) == [{"type": "a"}, {"type": "b"}]


def test_histogram_quantiles_exact_then_deterministic():
    from repro.obs.metrics import _RESERVOIR_CAP
    h = MetricRegistry().histogram("h")
    for v in np.random.default_rng(0).permutation(1000):
        h.observe(float(v))
    # stream fits the reservoir -> exact nearest-rank quantiles
    assert h.quantile(0.5) == 500.0
    assert h.quantiles() == {"p50": 500.0, "p90": 900.0, "p99": 990.0}
    # past the cap the reservoir subsamples, but the crc32(name)-seeded
    # PRNG makes the estimate a pure function of (name, sample stream)
    vals = np.random.default_rng(1).normal(size=_RESERVOIR_CAP + 500)
    h1 = MetricRegistry().histogram("lat")
    h2 = MetricRegistry().histogram("lat")
    for v in vals:
        h1.observe(float(v))
        h2.observe(float(v))
    assert h1.quantiles() == h2.quantiles()
    snap = MetricRegistry()
    snap.histogram("s").observe(2.0)
    entry = snap.snapshot()["s"]
    assert entry["p50"] == entry["p90"] == entry["p99"] == 2.0


def test_history_ledger_and_trends_regression_flag(tmp_path):
    """benchmarks history ledger round trip: two appended records where a
    'higher is better' gate drops >20% must surface in --section trends
    as a REGRESSION."""
    from benchmarks.dso_perf import append_history
    from benchmarks.report import trends_report
    path = str(tmp_path / "history.jsonl")
    old = {"dso_sparse": {"gate": {"traffic_ratio_dense_over_sparse": 6.0,
                                   "threshold": 2.0, "pass": True}},
           "obs_overhead": {"gate": {"obs_overhead_per_epoch": 0.001,
                                     "pass": True}}}
    new = {"dso_sparse": {"gate": {"traffic_ratio_dense_over_sparse": 4.0,
                                   "threshold": 2.0, "pass": True}},
           "obs_overhead": {"gate": {"obs_overhead_per_epoch": 0.0011,
                                     "pass": True}}}
    assert append_history(old, path=path)["gates"][
        "dso_sparse"]["traffic_ratio_dense_over_sparse"] == 6.0
    append_history(new, path=path)
    text = trends_report(path)
    assert "dso_sparse.traffic_ratio_dense_over_sparse" in text
    assert "REGRESSION" in text
    # thresholds are config, not measurements -> never trended
    assert "dso_sparse.threshold" not in text
    # a 10% drift on a 'lower' gate stays inside the 20% tolerance
    assert text.count("REGRESSION") == 1
