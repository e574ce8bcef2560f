"""Epoch scan (engine/driver.run_epochs): device time of that program's
ops inside the solver's intervals, per epoch, in ms.  Reads the ops of the
program ``jit_run_epochs`` (XLA Modules line on a TPU, the ``hlo_module``
stat on the CPU)."""

from bench import xplane as tr

PROGRAM = "jit_run_epochs"


def seconds_per_epoch(ctx):
    if ctx.trace is None or not ctx.epochs:
        return None
    ns = tr.length(tr.busy(ctx.trace, ctx.window, PROGRAM))
    return ns / 1e9 / ctx.epochs if ns else None


def read(ctx):
    s = seconds_per_epoch(ctx)
    return None if s is None else s * 1e3
