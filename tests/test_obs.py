"""Observability layer: recorder round-trip, span nesting and ids, the
engine's span tree and its join with the profiler's trace, the metrics-off
no-op contract (bit-identical trajectories, zero obs work in the chunk
loop), and the pinned number of events one chunk records.

The contract under test (obs/__init__.py): every ``obs=`` seam defaults to
``None`` and guards all instrumentation behind ``if obs is not None``;
with a recorder attached, every metric sample, span, and ledger event
lands in ONE ordered JSONL stream.
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
    prob = _prob()
    res = drv.solve(prob, epochs=3, p=4, eta0=0.5)
    assert len(res.history) == 3
    res = drv.solve_serial(prob, epochs=2, eta0=0.5)
    assert len(res.history) == 2


@pytest.mark.parametrize("schedule", ["cyclic", "lpt"])
@pytest.mark.parametrize("backend", ["dense_jnp", "sparse_jnp",
                                     "sparse_pallas", "sparse_bucketed_jnp"])
def test_traced_solve_bit_identical(tmp_path, backend, schedule):
    """The recorder only observes: a solve with the recorder opened the
    way the benchmark's traced runs open it (``jax_annotations=True``)
    and one with ``obs=None`` give bit-identical w, alpha and history."""
    from repro.engine import solve
    prob = _prob()
    kw = dict(backend=backend, schedule=schedule, epochs=6, p=4, eta0=0.5,
              eval_every=2, seed=0)
    r_off = solve(prob, **kw)
    with RunRecorder(str(tmp_path / "run.jsonl"),
                     jax_annotations=True) as rec:
        r_on = solve(prob, obs=rec, **kw)
    assert bool((np.asarray(r_off.w) == np.asarray(r_on.w)).all())
    assert bool((np.asarray(r_off.alpha) == np.asarray(r_on.alpha)).all())
    assert r_off.history == r_on.history


def test_recorder_overhead_amortized(tmp_path):
    """The recorder's cost per chunk, pinned as the exact number of events
    one chunk emits: the four chunk spans (epoch_chunk, chunk_schedule,
    chunk_dispatch, chunk_wait), the five throughput samples (rows_per_s,
    nnz_per_s, packed_bytes_per_s, eta, epoch_s) and, at an evaluation,
    eval and eval_gather.  A solve adds meta, solve_setup and solve once.
    Growth shows here in review; the time it costs is a chip reading
    (PERF.md: traced solver time per epoch against the parent's, budget
    2%)."""
    from repro.engine import solve

    prob = _prob()
    kw = dict(p=4, eta0=0.5, eval_every=2, seed=0,
              eval_hook=lambda t, w, alpha: {"epoch": t})
    for chunks in (1, 3):
        rec = RunRecorder(str(tmp_path / "run.jsonl"))
        solve(prob, epochs=2 * chunks, obs=rec, **kw)
        rec.close()
        kinds = {}
        for e in rec.events:
            kinds[e["type"]] = kinds.get(e["type"], 0) + 1
        want = {"meta": 1, "span": 2 + 6 * chunks, "metric": 5 * chunks}
        assert kinds == want, chunks


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
