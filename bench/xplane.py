"""Reduction of a JAX profiler trace (``.xplane.pb``) to device op
intervals and host spans, read with ``jax.profiler.ProfileData``.

Device ops: on a TPU, the events of the ``XLA Ops`` line of every
``/device:TPU:<n>`` plane, each attributed to the program (``XLA Modules``
line) whose execution contains it.  On the CPU backend, whose ops run on
host threads, the host events that carry an ``hlo_op`` stat.  Host spans:
every other event of the ``/host:CPU`` plane (``TraceAnnotation`` names,
``PjitFunction(...)`` dispatches).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import warnings
from typing import NamedTuple

DEVICE_PREFIX = "/device:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Event(NamedTuple):
    name: str
    start: int   # ns
    end: int     # ns
    where: str   # device plane, or host thread, of the event
    module: str = ""


class Trace(NamedTuple):
    ops: list      # device op Events, sorted by start
    spans: list    # host Events, sorted by start
    devices: int   # device planes seen (0 on the CPU backend)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _stats(ev) -> dict:
    with warnings.catch_warnings():    # the stats type warns on iteration
        warnings.simplefilter("ignore", DeprecationWarning)
        return dict(ev.stats)


def _module_name(name: str) -> str:
    """``jit_run_epochs(123)`` -> ``jit_run_epochs``."""
    return name.split("(", 1)[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    ops, spans, devices = [], [], 0
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines:
                continue
            devices += 1
            mods = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                           _module_name(e.name))
                          for e in (lines[MODULES_LINE].events
                                    if MODULES_LINE in lines else ()))
            starts = [s for s, _, _ in mods]
            for e in lines[OPS_LINE].events:
                s = int(e.start_ns)
                i = bisect.bisect_right(starts, s) - 1
                mod = mods[i][2] if i >= 0 and s < mods[i][1] else ""
                ops.append(Event(e.name, s, s + int(e.duration_ns),
                                 plane.name, mod))
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                for e in ln.events:
                    s = int(e.start_ns)
                    ev = Event(e.name, s, s + int(e.duration_ns), ln.name)
                    st = _stats(e)
                    if "hlo_op" in st:
                        ops.append(ev._replace(
                            module=str(st.get("hlo_module", ""))))
                    else:
                        spans.append(ev)
    ops.sort(key=lambda e: e.start)
    spans.sort(key=lambda e: e.start)
    return Trace(ops, spans, devices)


# ------------------------------------------------------------ intervals --


def merge(intervals) -> list:
    """Union of (start, end) intervals, sorted and disjoint."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals, window) -> list:
    """Parts of ``intervals`` (disjoint, sorted) inside ``window`` (same)."""
    out, j = [], 0
    for s, e in intervals:
        while j < len(window) and window[j][1] <= s:
            j += 1
        k = j
        while k < len(window) and window[k][0] < e:
            lo, hi = max(s, window[k][0]), min(e, window[k][1])
            if hi > lo:
                out.append((lo, hi))
            k += 1
    return out


def span_intervals(trace: Trace, name: str) -> list:
    return merge((e.start, e.end) for e in trace.spans if e.name == name)


def busy(trace: Trace, window, module: str | None = None) -> list:
    """Device-busy intervals inside ``window``, per device plane then
    merged over planes; ``module`` keeps only that program's ops."""
    ops = [e for e in trace.ops if module is None or e.module == module]
    return clip(merge((e.start, e.end) for e in ops), window)


def busy_seconds_per_device(trace: Trace, window) -> float:
    """Seconds some op ran, averaged over the device planes."""
    planes = sorted({e.where for e in trace.ops}) if trace.devices else [""]
    tot = 0
    for pl in planes:
        iv = merge((e.start, e.end) for e in trace.ops
                   if not trace.devices or e.where == pl)
        tot += length(clip(iv, window))
    return tot / len(planes) / 1e9


_LAYOUT = re.compile(r"\{[^{}]*\}|/\*[^*]*\*/")
_OPERAND = re.compile(r" %[\w.\-]+")


def op_label(e: Event, width: int = 160) -> str:
    """Program, op name and shapes, without layouts, operand names and
    attributes: ``jit_run_epochs/fusion.174 = f32[4049472] fusion(f32[4,
    5240], s32[4049472])`` from the TPU's full HLO text."""
    text = _OPERAND.sub("", _LAYOUT.sub("", e.name))
    text = " ".join(text.split(", kind=")[0].split(", calls=")[0].split())
    text = text.lstrip("%")
    return ((e.module + "/" if e.module else "") + text)[:width]


def _inside(s: int, e: int, window, ends) -> int:
    """ns of [s, e) inside ``window`` (disjoint, sorted; ``ends`` its
    ends)."""
    tot, i = 0, bisect.bisect_right(ends, s)
    while i < len(window) and window[i][0] < e:
        tot += max(0, min(e, window[i][1]) - max(s, window[i][0]))
        i += 1
    return tot


def top_ops(trace: Trace, window, n: int = 10) -> list:
    """[[op, self seconds inside window], ...], the n largest.  An op's
    self time leaves out the ops nested in it (a loop's body ops), so no
    time is counted twice."""
    ends = [w[1] for w in window]
    tot: dict = {}
    for where in sorted({e.where for e in trace.ops}):
        stack: list = []     # (label, end) of the ops that enclose this one
        for e in sorted((e for e in trace.ops if e.where == where),
                        key=lambda e: (e.start, -e.end)):
            while stack and stack[-1][1] <= e.start:
                stack.pop()
            label = op_label(e)
            t = _inside(e.start, e.end, window, ends)
            tot[label] = tot.get(label, 0) + t
            if stack:
                parent, p_end = stack[-1]
                tot[parent] -= _inside(e.start, min(e.end, p_end), window,
                                       ends)
            stack.append((label, e.end))
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n] if v > 0]


def idle_gaps(trace: Trace, window, n: int = 10) -> list:
    """[[what the host was doing, seconds], ...] for the n longest gaps in
    ``window`` when no device op ran; the label is the shortest host span
    that covers the gap's middle."""
    gaps, j = [], 0
    busy_in = clip(merge((e.start, e.end) for e in trace.ops), window)
    for ws, we in window:
        t = ws
        while j < len(busy_in) and busy_in[j][0] < we:
            s, e = busy_in[j]
            if s > t:
                gaps.append((t, s))
            t, j = max(t, e), j + 1
        if we > t:
            gaps.append((t, we))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        mid = (s + e) // 2
        cover = [sp for sp in trace.spans if sp.start <= mid < sp.end]
        label = min(cover, key=lambda sp: sp.end - sp.start).name \
            if cover else "(no host span)"
        out.append([label, (e - s) / 1e9])
    return out
