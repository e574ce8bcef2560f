"""The tile-step scope and the engine's host spans, read from a trace: on
hand-made events, and on two small traces kept beside this file.

``tpu_spans.xplane.pb``: one TPU v5 lite chip, a warm 3-epoch
``engine.solve`` (``sparse_jnp``, p = 4, a 4,000 x 1,000 power-law matrix
with 8 nonzeros per row) under ``jax.profiler`` with a
``RunRecorder(jax_annotations=True)``, inside one ``bench.solve``
annotation.  ``cpu_spans.xplane.pb``: the same on the CPU backend.
"""

import os
import shutil
from types import SimpleNamespace

import pytest

from bench import scopes
from bench import xplane as tr
from bench.metrics import load

HERE = os.path.dirname(os.path.abspath(__file__))
TPU = os.path.join(HERE, "data", "tpu_spans.xplane.pb")
CPU = os.path.join(HERE, "data", "cpu_spans.xplane.pb")
ENGINE_SPANS = {"solve", "solve_setup", "epoch_chunk", "chunk_schedule",
                "chunk_dispatch", "chunk_wait", "eval", "eval_gather"}
NEW = ("tile_step_ms", "tile_step_roofline", "engine_host_ms")


@pytest.mark.parametrize("op_name,inside", [
    ("jit(f)/while/body/closed_call/vmap(tile_step)/scatter-add", True),
    ("jit(run_epochs)/while/body/vmap(tile_step)/while/body/mul:", True),
    ("jit(f)/tile_step/gather", True),
    ("jit(f)/transpose(jvp(vmap(tile_step)))/dot", True),
    ("jit(f)/vmap()/gather:", False),
    ("jit(f)/vmap(tile_step_extra)/gather", False),
    ("jit(f)/tile_steps/gather", False),
    ("", False)])
def test_in_scope_matches_a_path_component(op_name, inside):
    assert scopes.in_scope(op_name, "tile_step") is inside


def _op(start, end, op_name, module="jit_run_epochs", run=0,
        where="/device:TPU:0"):
    return scopes.ScopedOp(start, end, where, module, run, op_name)


TS = "jit(run_epochs)/while/body/vmap(tile_step)/"
OUT = "jit(run_epochs)/while/body/vmap()/"


def test_unscoped_ops_take_the_scope_around_them():
    ops = [_op(0, 10, ""),                  # before any scoped op: out
           _op(10, 20, OUT + "gather:"),
           _op(20, 30, ""),                 # between out and in: out
           _op(30, 40, TS + "gather:"),
           _op(40, 70, ""),                 # the scatter-add: in
           _op(70, 80, TS + "div:"),
           _op(80, 90, ""),                 # after the last scoped op: out
           # another execution: neighbours never cross it
           _op(100, 110, "", run=100),
           _op(110, 120, TS + "mul:", run=100),
           _op(120, 130, "", run=100),
           # another program, same scope: not this program's time
           _op(200, 210, TS + "mul:", module="jit_other", run=200)]
    got = scopes.intervals(ops, "tile_step", "jit_run_epochs")
    assert got == [(30, 80), (110, 120)]


def test_an_enclosing_loop_inside_the_scope_is_counted_once():
    ops = [_op(0, 10, TS + "gather:"),
           _op(10, 50, ""),                 # a while loop with body ops
           _op(12, 20, TS + "mul:"),
           _op(20, 30, ""),
           _op(30, 48, TS + "add:"),
           _op(50, 60, TS + "div:")]
    assert scopes.intervals(ops, "tile_step", "jit_run_epochs") == [(0, 60)]


def _root(tmp_path, trace_file=None):
    """A checkout-shaped directory: the metric readers, and ``trace_file``
    where ``bench/run.py`` leaves a traced run."""
    shutil.copytree(os.path.join(os.path.dirname(HERE), "metrics"),
                    tmp_path / "bench" / "metrics")
    if trace_file is not None:
        (tmp_path / ".bench_trace").mkdir()
        shutil.copy(trace_file, tmp_path / ".bench_trace")
    return str(tmp_path / "bench" / "metrics")


def _ctx(trace, epochs, **kw):
    base = dict(trace=trace, window=tr.span_intervals(trace, "bench.solve"),
                epochs=epochs, solver_s=1.0, whole_epochs=[],
                hbm_bytes_per_epoch=819, peak={"hbm_bytes_per_s": 819e9})
    base.update(kw)
    return SimpleNamespace(**base)


def test_recorded_tpu_trace(tmp_path):
    metrics = _root(tmp_path, TPU)
    trace = tr.load(TPU)
    ctx = _ctx(trace, epochs=3)
    assert trace.devices == 1 and len(ctx.window) == 1
    assert ENGINE_SPANS <= {s.name for s in trace.spans}
    ops = scopes.load(TPU)
    assert [(o.start, o.end) for o in ops] == [(e.start, e.end)
                                               for e in trace.ops]
    run_epochs = [o for o in ops if o.module == "jit_run_epochs"]
    scoped = [o for o in run_epochs if scopes.in_scope(o.op_name, "tile_step")]
    # the scope is in the ops' metadata; the compiler's scatter-add fusion
    # carries none and is counted from the ops around it
    assert scoped and any(not o.op_name and o.end - o.start > 50_000
                          for o in run_epochs)
    scan = load("epoch_scan_ms").read(ctx)
    tile = load("tile_step_ms", metrics).read(ctx)
    strict = tr.length(tr.clip(tr.merge((o.start, o.end) for o in scoped),
                               ctx.window)) / 1e6 / 3
    assert strict < 0.6 * scan and 0.9 * scan <= tile <= scan
    roof = load("tile_step_roofline", metrics).read(ctx)
    assert roof == pytest.approx(100 * 1e-9 / (tile / 1e3))
    assert roof >= load("epoch_scan_roofline").read(ctx)
    host = load("engine_host_ms", metrics).read(ctx)
    idle_ms = (tr.length(ctx.window) / 1e9
               - tr.busy_seconds_per_device(trace, ctx.window)) * 1e3 / 3
    assert 0 < host < idle_ms


def test_recorded_cpu_trace_has_host_spans_and_no_scope(tmp_path):
    metrics = _root(tmp_path, CPU)
    trace = tr.load(CPU)
    ctx = _ctx(trace, epochs=3)
    assert trace.devices == 0 and ENGINE_SPANS <= {s.name
                                                   for s in trace.spans}
    assert scopes.load(CPU) == []
    assert load("tile_step_ms", metrics).read(ctx) is None
    assert load("tile_step_roofline", metrics).read(ctx) is None
    host = load("engine_host_ms", metrics).read(ctx)
    idle_ms = (tr.length(ctx.window) / 1e9
               - tr.busy_seconds_per_device(trace, ctx.window)) * 1e3 / 3
    assert 0 < host < idle_ms


def test_engine_host_ms_on_hand_made_trace(tmp_path):
    E = tr.Event
    ops = [E("run", 300, 900, "/device:TPU:0", "jit_run_epochs"),
           E("gather", 1000, 1050, "/device:TPU:0", "jit_gather_w")]
    spans = [E("bench.solve", 0, 1200, "python"),
             E("solve", 0, 1200, "python"),
             E("epoch_chunk", 0, 950, "python"),
             E("chunk_schedule", 0, 100, "python"),     # idle 100
             E("chunk_dispatch", 100, 400, "python"),   # idle 200 of 300
             E("chunk_wait", 400, 950, "python"),       # not a host step
             E("eval", 950, 1200, "python"),
             E("eval_gather", 950, 1100, "python"),     # idle 100 of 150
             E("solve_setup", 1300, 1400, "python")]    # outside the window
    ctx = _ctx(tr.Trace(ops, spans, 1), epochs=2)
    metrics = _root(tmp_path)
    assert load("engine_host_ms", metrics).read(ctx) == pytest.approx(
        400 / 2 / 1e6)


def test_new_metrics_absent_without_their_spans_or_scope(tmp_path):
    # a program without the spans and the scope (the parent's), or a run
    # without a trace: every new reader returns None and raises nothing
    metrics = _root(tmp_path)
    E = tr.Event
    trace = tr.Trace([E("op", 10, 20, "/device:TPU:0", "jit_run_epochs")],
                     [E("bench.solve", 0, 100, "python"),
                      E("epoch_chunk", 0, 100, "python")], 1)
    for ctx in (_ctx(trace, epochs=1), _ctx(tr.Trace([], [], 0), epochs=0,
                                             window=[])):
        for name in NEW:
            assert load(name, metrics).read(ctx) is None
    unscoped = os.path.join(HERE, "data", "cpu_trace.xplane.pb")
    metrics = _root(tmp_path / "old", unscoped)
    trace = tr.load(unscoped)
    ctx = _ctx(trace, epochs=8)
    assert "epoch_chunk" in {s.name for s in trace.spans}
    for name in NEW:
        assert load(name, metrics).read(ctx) is None
