"""Tile step and kernels (engine/backends.py, kernels/): the least time an
epoch could take at the HBM peak, for the lower-bound bytes of
``bench.work.epoch_hbm_bytes``, over the device time of the epoch scan
(``jit_run_epochs``) per epoch, in %.  Bandwidth bounds it: the epoch does
a few float operations per byte."""

from bench.metrics.epoch_scan_ms import seconds_per_epoch


def read(ctx):
    s = seconds_per_epoch(ctx)
    if s is None or ctx.peak is None:
        return None
    return 100.0 * ctx.hbm_bytes_per_epoch / ctx.peak["hbm_bytes_per_s"] / s
