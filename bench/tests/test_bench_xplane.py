"""The trace reduction: on hand-made events, and on a small trace recorded
on the CPU and kept beside this file."""

import os
from types import SimpleNamespace

import pytest

from bench import xplane as tr
from bench.metrics import load

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "cpu_trace.xplane.pb")


def _ctx(trace, **kw):
    base = dict(trace=trace, window=tr.span_intervals(trace, "bench.solve"),
                epochs=4, solver_s=4e-6, whole_epochs=[2, 2],
                hbm_bytes_per_epoch=819, peak={"hbm_bytes_per_s": 819e9})
    base.update(kw)
    return SimpleNamespace(**base)


def _hand():
    E = tr.Event
    ops = [E("%while.3 = (f32[4]{0:T(128)}) while(f32[4]{0} %x), "
             "condition=%c, body=%b", 100, 900, "/device:TPU:0",
             "jit_run_epochs"),
           E("gather", 100, 600, "/device:TPU:0", "jit_run_epochs"),
           E("scatter", 600, 900, "/device:TPU:0", "jit_run_epochs"),
           E("gap_op", 1200, 1500, "/device:TPU:0", "jit_gap"),
           E("gather", 2100, 2900, "/device:TPU:0", "jit_run_epochs")]
    spans = [E("bench.solve", 0, 1000, "python"),
             E("bench.check", 1000, 2000, "python"),
             E("bench.solve", 2000, 3000, "python"),
             E("PjitFunction(run_epochs)", 2000, 2100, "python")]
    return tr.Trace(ops, spans, 1)


def test_metrics_on_hand_made_trace():
    ctx = _ctx(_hand())
    assert ctx.window == [(0, 1000), (2000, 3000)]
    # run_epochs busy 100..900 and 2100..2900 = 1600 ns over 4 epochs
    assert load("epoch_scan_ms").read(ctx) == pytest.approx(1600 / 4 / 1e6)
    # bound 819 B at 819e9 B/s = 1 ns per epoch, over 400 ns per epoch
    assert load("epoch_scan_roofline").read(ctx) == pytest.approx(0.25)
    assert load("mfu_hbm").read(ctx) == pytest.approx(0.1)
    assert load("device_idle").read(ctx) == pytest.approx(20.0)
    assert load("epochs_to_gap").read(ctx) == 2
    # self time: the loop's own time is what its body ops leave (none)
    assert tr.top_ops(ctx.trace, ctx.window) == [
        ["jit_run_epochs/gather", 1.3e-6], ["jit_run_epochs/scatter", 3e-7]]
    assert tr.op_label(ctx.trace.ops[0]) == \
        "jit_run_epochs/while.3 = (f32[4]) while(f32[4]), condition=%c, body=%b"
    assert tr.idle_gaps(ctx.trace, ctx.window) == [
        ["bench.solve", 1e-7], ["bench.solve", 1e-7],
        ["PjitFunction(run_epochs)", 1e-7], ["bench.solve", 1e-7]]


def test_peaks_by_device_kind_only():
    from bench import work

    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="TPU v4"):
        work.peaks("TPU v4")
    # real-sim's lower bound: 4 B per value, 16 B per row and column, 4 B
    # per label
    assert work.epoch_hbm_bytes(72309, 20958, 3687759) == 16532544


def test_metrics_absent_without_events():
    ctx = _ctx(tr.Trace([], [], 0), window=[], whole_epochs=[])
    for name in ("epoch_scan_ms", "epoch_scan_roofline", "device_idle",
                 "epochs_to_gap"):
        assert load(name).read(ctx) is None


def test_recorded_cpu_trace():
    trace = tr.load(RECORDED)
    window = tr.span_intervals(trace, "bench.solve")
    assert trace.devices == 0 and len(window) == 8
    assert {e.module for e in trace.ops} >= {"jit_run_epochs"}
    ctx = _ctx(trace, window=window, epochs=8)
    ms = load("epoch_scan_ms").read(ctx)
    busy = tr.busy_seconds_per_device(trace, window)
    assert 0 < ms * 8 / 1e3 <= busy <= tr.length(window) / 1e9
    idle = load("device_idle").read(ctx)
    assert idle == pytest.approx(100 * (1 - busy / (tr.length(window) / 1e9)))
    assert tr.top_ops(trace, window)[0][1] > 0
    labels = {g[0] for g in tr.idle_gaps(trace, window)}
    assert labels <= {"bench.solve", "epoch_chunk", "eval",
                      "(no host span)"} | {s.name for s in trace.spans}
