"""Device ops of a JAX profiler trace with the scope path each came from.

``bench.xplane.load`` reads the trace with ``jax.profiler.ProfileData``,
which gives an event its name and its own stats only.  On a TPU the scope
path of an ``XLA Ops`` event (``jit(run_epochs)/while/body/.../
vmap(tile_step)/...``, the HLO ``op_name`` that ``jax.named_scope`` writes)
is a stat of the event's *metadata*, which ``ProfileData`` does not
expose.  So this module decodes the device planes of the ``.xplane.pb``
itself, with ``google.protobuf`` and the few fields of the XSpace schema
(``tsl/profiler/protobuf/xplane.proto``) it needs, and keeps each op's
scope.  Times are in ns on the clock ``bench.xplane`` uses (the line's
``timestamp_ns`` plus the event's offset), so the ops clip against the
``bench.solve`` spans that ``bench.xplane`` reads.  On the CPU backend
the ops are host events that carry no scope (only ``hlo_op``,
``hlo_module`` and ``program_id``), and none are returned.

The compiler leaves some ops without a scope path: on the TPU the sparse
tile step's scatter-add is a custom fusion with no ``op_name``, and so
are some reshapes, copies and loops.  ``intervals`` gives such an op the
scope of the ops around it (``_inferred``).
"""

from __future__ import annotations

import bisect
import functools
import os
import re
from typing import NamedTuple

from bench import xplane as tr

#: the event-metadata stat that holds an op's scope path on the TPU
OP_NAME_STAT = "tf_op"


class ScopedOp(NamedTuple):
    start: int      # ns
    end: int        # ns
    where: str      # device plane
    module: str     # program (``XLA Modules`` line)
    run: int        # start (ns) of that program's execution, else -1
    op_name: str    # scope path, e.g. ``jit(f)/vmap(tile_step)/gather``;
                    # "" where the compiler left the op without one


def trace_dir(module_file: str) -> str:
    """``<root>/.bench_trace``, where ``bench/run.py`` writes a traced
    run, for a file ``<root>/bench/<dir>/<name>.py``."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(module_file))))
    return os.path.join(root, ".bench_trace")


@functools.lru_cache(maxsize=1)
def _xspace_class():
    """The XSpace message, declared with the fields read here only
    (unknown fields are skipped on parse)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    opt, rep = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_scopes_xplane.proto", package="bench_scopes")

    def msg(name, *fields):
        m = fdp.message_type.add(name=name)
        for fname, num, label, ftype, tname in fields:
            f = m.field.add(name=fname, number=num, label=label, type=ftype)
            if tname:
                f.type_name = ".bench_scopes." + tname

    i64, s, m = F.TYPE_INT64, F.TYPE_STRING, F.TYPE_MESSAGE
    msg("XStat", ("metadata_id", 1, opt, i64, ""),
        ("str_value", 5, opt, s, ""))
    msg("XEvent", ("metadata_id", 1, opt, i64, ""),
        ("offset_ps", 2, opt, i64, ""), ("duration_ps", 3, opt, i64, ""))
    msg("XLine", ("name", 2, opt, s, ""), ("timestamp_ns", 3, opt, i64, ""),
        ("events", 4, rep, m, "XEvent"))
    msg("XEventMetadata", ("id", 1, opt, i64, ""), ("name", 2, opt, s, ""),
        ("stats", 5, rep, m, "XStat"))
    msg("XStatMetadata", ("id", 1, opt, i64, ""), ("name", 2, opt, s, ""))
    # map<int64, ...> fields, as their wire form: repeated (key, value)
    msg("EventMetadataEntry", ("key", 1, opt, i64, ""),
        ("value", 2, opt, m, "XEventMetadata"))
    msg("StatMetadataEntry", ("key", 1, opt, i64, ""),
        ("value", 2, opt, m, "XStatMetadata"))
    msg("XPlane", ("name", 2, opt, s, ""), ("lines", 3, rep, m, "XLine"),
        ("event_metadata", 4, rep, m, "EventMetadataEntry"),
        ("stat_metadata", 5, rep, m, "StatMetadataEntry"))
    msg("XSpace", ("planes", 1, rep, m, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_scopes.XSpace"))


def _events(line):
    """(start ns, end ns, metadata id) of a line's events, rounded as
    ``ProfileData`` rounds them for ``bench.xplane``."""
    for e in line.events:
        s = line.timestamp_ns + e.offset_ps // 1000
        yield s, s + e.duration_ps // 1000, e.metadata_id


def _plane_ops(plane) -> list:
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}
    meta = {}
    for entry in plane.event_metadata:
        md = entry.value
        op = next((st.str_value for st in md.stats
                   if stat_names.get(st.metadata_id) == OP_NAME_STAT), "")
        meta[entry.key] = (md.name, op)
    lines = {ln.name: ln for ln in plane.lines}
    if tr.OPS_LINE not in lines:
        return []
    mods = sorted((s, e, tr._module_name(meta.get(k, ("", ""))[0]))
                  for s, e, k in (_events(lines[tr.MODULES_LINE])
                                  if tr.MODULES_LINE in lines else ()))
    starts = [s for s, _, _ in mods]
    out = []
    for s, e, k in _events(lines[tr.OPS_LINE]):
        i = bisect.bisect_right(starts, s) - 1
        inside = i >= 0 and s < mods[i][1]
        out.append(ScopedOp(s, e, plane.name, mods[i][2] if inside else "",
                            mods[i][0] if inside else -1,
                            meta.get(k, ("", ""))[1]))
    return out


def load(path: str) -> list:
    """Every device op of the trace at ``path`` (a file, or a directory
    holding one), sorted by start."""
    if os.path.isdir(path):
        path = tr.find_xplane(path)
    with open(path, "rb") as f:
        space = _xspace_class().FromString(f.read())
    ops = [op for plane in space.planes
           if plane.name.startswith(tr.DEVICE_PREFIX)
           for op in _plane_ops(plane)]
    ops.sort(key=lambda o: o.start)
    return ops


_WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")


def in_scope(op_name: str, scope: str) -> bool:
    """Whether ``scope`` is a component of the path ``op_name``, bare or
    inside transform wrappers (``vmap(tile_step)``,
    ``jvp(vmap(tile_step))``)."""
    for part in op_name.split("/"):
        while part != scope:
            m = _WRAPPED.match(part)
            if m is None:
                break
            part = m.group(1)
        if part == scope:
            return True
    return False


def _inferred(run: list, scope: str) -> list:
    """The ops of one program execution on one device that lie inside
    ``scope``.  An op with a scope path is inside when ``scope`` is one of
    its components.  An op the compiler left without one (on the TPU:
    custom fusions such as the sparse tile step's scatter-add, and some
    reshapes, copies and loops) takes the scope of the ops around it: it
    is inside when the last op with a path that ended before it started
    and the first that started after it ended are both inside."""
    known = [(o, in_scope(o.op_name, scope)) for o in run if o.op_name]
    by_end = sorted(known, key=lambda k: k[0].end)
    ends = [o.end for o, _ in by_end]
    starts = [o.start for o, _ in known]        # ``run`` is sorted by start
    out = []
    for o in run:
        if o.op_name:
            inside = in_scope(o.op_name, scope)
        else:
            i = bisect.bisect_right(ends, o.start) - 1
            j = bisect.bisect_left(starts, o.end)
            inside = (i >= 0 and j < len(known) and by_end[i][1]
                      and known[j][1])
        if inside:
            out.append(o)
    return out


def intervals(ops, scope: str, module: str) -> list:
    """Union of the intervals of ``module``'s ops inside ``scope``, each
    program execution on each device read on its own (``_inferred``)."""
    runs: dict = {}
    for o in ops:
        if o.module == module:
            runs.setdefault((o.where, o.run), []).append(o)
    return tr.merge((o.start, o.end) for run in runs.values()
                    for o in _inferred(run, scope))


def seconds_per_epoch(ctx, path: str, scope: str, module: str):
    """Device seconds per epoch of ``module``'s ops inside ``scope``,
    clipped to the solver's intervals; None when the trace at ``path``
    has no such op."""
    if not ctx.epochs or not os.path.isdir(path):
        return None
    ns = tr.length(tr.clip(intervals(load(path), scope, module),
                           ctx.window))
    return ns / 1e9 / ctx.epochs if ns else None
