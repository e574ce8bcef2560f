"""Update rule (engine/update.py, engine/schedules.py): epochs a whole
solve needs to reach the gap target, the mean over the traced window's
whole solves.  The epoch is the count the program hands the benchmark's
gap check (``eval_hook(t, w, alpha)``) at the check that met the
target."""


def read(ctx):
    e = ctx.whole_epochs
    return sum(e) / len(e) if e else None
