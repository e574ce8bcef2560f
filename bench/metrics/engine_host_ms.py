"""Chunk loop (``engine/driver.solve``): time inside the solver's
intervals in which no op ran on the device while the host was in one of
the program's own host steps — ``solve_setup``, ``chunk_schedule``,
``chunk_dispatch`` or ``eval_gather`` spans — per epoch, in ms.  The
device waiting on the engine's host code, as the trace's host spans
name it; None when the program records none of those spans."""

from bench import xplane as tr

SPANS = ("solve_setup", "chunk_schedule", "chunk_dispatch", "eval_gather")


def read(ctx):
    if ctx.trace is None or not ctx.epochs:
        return None
    host = tr.clip(tr.merge((e.start, e.end) for e in ctx.trace.spans
                            if e.name in SPANS), ctx.window)
    if not host:
        return None
    busy = tr.clip(tr.busy(ctx.trace, ctx.window), host)
    return (tr.length(host) - tr.length(busy)) / 1e6 / ctx.epochs
