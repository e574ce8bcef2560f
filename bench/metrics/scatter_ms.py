"""Tile step and kernels: device time of XLA's scatter-add of X^T alpha in
the sparse tile step (``engine/update.sparse_tile_step``, the
``xta_scatter`` scope inside ``tile_step``: the product ``vals * alpha``
and the ``.at[].add``), the union of the epoch scan's (``jit_run_epochs``)
ops it counts clipped to the solver's intervals, per epoch, in ms.  None
where no op carries the scope, as on the one-hot kernel's path.

On the TPU the compiler leaves the scatter-add itself, a custom fusion,
without a scope path, and the op that follows it (the division of the
result by m) lies outside ``xta_scatter``: ``bench.scopes``' rule, which
counts such an op only when the ops with a path on both sides of it are
inside, would leave the scatter-add out.  So this reader has a rule of
its own for ops without a path: one is counted when the last op with a
path that ended before it started is inside ``xta_scatter`` and the
first that started after it ended is inside ``tile_step``.  It continues
the scope of the op before it and stays inside the tile step, so the
metric counts no time that ``tile_step_ms`` leaves out."""

import bisect
import os

from bench import scopes
from bench import xplane as tr

SCOPE, TILE, PROGRAM = "xta_scatter", "tile_step", "jit_run_epochs"


def counted(run: list) -> list:
    """The ops of one program execution on one device, sorted by start,
    that this metric counts."""
    known = [o for o in run if o.op_name]
    by_end = sorted(known, key=lambda o: o.end)
    ends = [o.end for o in by_end]
    starts = [o.start for o in known]
    out = []
    for o in run:
        if o.op_name:
            inside = scopes.in_scope(o.op_name, SCOPE)
        else:
            i = bisect.bisect_right(ends, o.start) - 1
            j = bisect.bisect_left(starts, o.end)
            inside = (i >= 0 and j < len(known)
                      and scopes.in_scope(by_end[i].op_name, SCOPE)
                      and scopes.in_scope(known[j].op_name, TILE))
        if inside:
            out.append(o)
    return out


def read(ctx):
    path = scopes.trace_dir(__file__)
    if not ctx.epochs or not os.path.isdir(path):
        return None
    runs: dict = {}
    for o in scopes.load(path):
        if o.module == PROGRAM:
            runs.setdefault((o.where, o.run), []).append(o)
    ns = tr.length(tr.clip(tr.merge((o.start, o.end) for run in runs.values()
                                    for o in counted(run)), ctx.window))
    return ns / 1e6 / ctx.epochs if ns else None
