"""Device: share of the solver's intervals (``bench.solve`` spans, gap
checks cut out) in which no op ran on the device, in %, averaged over the
chips."""

from bench import xplane as tr


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    window = tr.length(ctx.window) / 1e9
    if not window:
        return None
    return 100.0 * (1.0 - tr.busy_seconds_per_device(ctx.trace, ctx.window)
                    / window)
