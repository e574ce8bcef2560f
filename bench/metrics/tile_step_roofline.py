"""Tile step and kernels: the least time an epoch could take at the HBM
peak, for the lower-bound bytes of ``bench.work.epoch_hbm_bytes``, over
the device time of the ops in the ``tile_step`` scope per epoch (the time
``tile_step_ms`` reads), in %.  Bandwidth bounds it: the epoch does a few
float operations per byte."""

from bench import scopes

SCOPE, PROGRAM = "tile_step", "jit_run_epochs"


def read(ctx):
    s = scopes.seconds_per_epoch(ctx, scopes.trace_dir(__file__), SCOPE,
                                 PROGRAM)
    if s is None or ctx.peak is None:
        return None
    return 100.0 * ctx.hbm_bytes_per_epoch / ctx.peak["hbm_bytes_per_s"] / s
