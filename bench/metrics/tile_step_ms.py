"""Tile step and kernels (``engine/driver.staged_step``, the
``tile_step`` scope around ``backend.block_step``): device time of the
epoch scan's (``jit_run_epochs``) ops inside that scope, their union
clipped to the solver's intervals, per epoch, in ms.  Reads the scope
path from the trace ``bench/run.py`` writes (``bench.scopes``)."""

from bench import scopes

SCOPE, PROGRAM = "tile_step", "jit_run_epochs"


def read(ctx):
    s = scopes.seconds_per_epoch(ctx, scopes.trace_dir(__file__), SCOPE,
                                 PROGRAM)
    return None if s is None else s * 1e3
