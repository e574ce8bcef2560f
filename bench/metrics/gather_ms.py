"""Tile step and kernels: device time of XLA's gather of w in the sparse
tile step (``engine/update.sparse_tile_step``, the ``xw_gather`` scope
inside ``tile_step``: the ``take`` of the w block and the row sums of
``vals * w``), the union of the epoch scan's (``jit_run_epochs``) ops in
that scope clipped to the solver's intervals, per epoch, in ms.  Reads
the scope path from the trace ``bench/run.py`` writes (``bench.scopes``);
None where no op carries the scope, as on the one-hot kernel's path."""

from bench import scopes

SCOPE, PROGRAM = "xw_gather", "jit_run_epochs"


def read(ctx):
    s = scopes.seconds_per_epoch(ctx, scopes.trace_dir(__file__), SCOPE,
                                 PROGRAM)
    return None if s is None else s * 1e3
