"""Whole step: the lower-bound bytes per epoch at the HBM peak over the
solver's wall time per epoch (host clock, traced window), in %."""


def read(ctx):
    if not ctx.epochs or not ctx.solver_s or ctx.peak is None:
        return None
    per_epoch = ctx.solver_s / ctx.epochs
    return (100.0 * ctx.hbm_bytes_per_epoch / ctx.peak["hbm_bytes_per_s"]
            / per_epoch)
