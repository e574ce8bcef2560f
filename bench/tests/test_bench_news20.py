"""The news20 cell's pieces at sizes a test run holds: the plain reference
against the program on blocks wider than the one-hot kernel takes, and the
readers of ``gather_ms`` and ``scatter_ms`` on hand-made events and on
recorded traces.

``news20_spans.xplane.pb``: one TPU v5 lite chip, the cell
``news20-logistic.gap`` itself (19,996 x 1,355,191, ``sparse_jnp``,
logistic): the harness's driver (``bench/drivers/solve.py``, traced) set
up and warmed as ``bench/run.py`` does, then the first epoch of its window
(``Driver.window(0.1)``) under ``jax.profiler``, host tracer level 0.
The trace's ``/host:metadata`` plane (the compiled programs' HLO, which no
reader uses) was dropped to keep the file small; every other plane is as
recorded."""

import os

import pytest

from bench import check, gen, scopes
from bench import xplane as tr
from bench.metrics import load
from bench.reference import Reference
from bench.tests.test_bench_reference import _program
from bench.tests.test_bench_scopes import _ctx, _root

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
NEWS20 = os.path.join(DATA, "news20_spans.xplane.pb")
READERS = ("gather_ms", "scatter_ms")


@pytest.mark.parametrize("loss,alpha0", [("logistic", 0.0005),
                                         ("hinge", 0.0)])
def test_reference_follows_program_on_wide_blocks(loss, alpha0):
    """64 rows over 66,000 columns: the program's column blocks are 16,500
    wide, more than ``ONEHOT_MAX_DB``, the widest block ``auto`` gives the
    one-hot kernel on a TPU."""
    from repro.sparse.format import ONEHOT_MAX_DB

    csr = gen.powerlaw_csr(64, 4 * 16_500, 40, 1.0, 15)
    assert -(-csr.d // 4) > ONEHOT_MAX_DB
    got = _program(csr, loss, alpha0, 6, "sparse")
    ref = Reference(csr, loss=loss, lam=1e-3, p=4, eta0=0.5, alpha0=alpha0)
    for w, a in got:
        ref.epoch()
        assert check.rel_err(w, ref.w) < 1e-5
        assert check.rel_err(a, ref.alpha) < 1e-5


TS = "jit(run_epochs)/while/body/vmap(tile_step)/while/body/"
OUT = "jit(run_epochs)/while/body/vmap()/"


def _op(start, end, op_name, run=0):
    return scopes.ScopedOp(start, end, "/device:TPU:0", "jit_run_epochs",
                           run, op_name)


def test_scatter_counts_the_unscoped_fusion_after_its_scope():
    """The order the TPU compiler gives the tile step: the scatter-add's
    index and product ops, then its scope-less fusion, then the division
    of its result outside ``xta_scatter``."""
    run = [_op(0, 10, TS + "xw_gather/reduce_sum"),
           _op(10, 20, TS + "xta_scatter/select_n"),
           _op(20, 25, ""),                 # before a slice outside: out
           _op(25, 30, OUT + "gather"),
           _op(30, 40, TS + "xta_scatter/mul"),
           _op(40, 90, ""),                 # the scatter-add fusion: in
           _op(90, 95, TS + "div"),
           _op(95, 99, ""),                 # after a tile-step op: out
           _op(99, 100, OUT + "scatter")]
    got = [(o.start, o.end) for o in load("scatter_ms").counted(run)]
    assert got == [(10, 20), (30, 40), (40, 90)]
    # the shared rule leaves the fusion out: its next neighbour is outside
    assert (40, 90) not in [(o.start, o.end)
                            for o in scopes._inferred(run, "xta_scatter")]
    assert [(o.start, o.end) for o in scopes._inferred(run, "xw_gather")] \
        == [(0, 10)]


@pytest.mark.parametrize("name", ["cpu_spans.xplane.pb",
                                  "tpu_spans.xplane.pb"])
def test_readers_read_nothing_without_the_scopes(tmp_path, name):
    """The CPU's ops carry no scope; the TPU trace of ``sparse_jnp`` kept
    beside this file was recorded before the two scopes existed, as a
    program without them (the one-hot kernel's, the parent's) runs."""
    path = os.path.join(DATA, name)
    metrics = _root(tmp_path, path)
    ctx = _ctx(tr.load(path), epochs=3)
    for reader in READERS:
        assert load(reader, metrics).read(ctx) is None, reader


def test_recorded_news20_trace(tmp_path):
    """Both readers find their ops on the chip's trace of the cell: the
    gather's own fusion carries ``xw_gather``; the scatter-add's fusion
    carries no scope and is counted by ``scatter_ms``' rule.  Together
    they are the tile step, and never more than ``tile_step_ms`` reads."""
    metrics = _root(tmp_path, NEWS20)
    trace = tr.load(NEWS20)
    ctx = _ctx(trace, epochs=1)
    assert trace.devices == 1 and len(ctx.window) == 1
    gather, scatter = (load(r, metrics).read(ctx) for r in READERS)
    tile = load("tile_step_ms", metrics).read(ctx)
    assert gather > 0.1 * tile and scatter > 0.1 * tile
    assert 0.9 * tile <= gather + scatter <= tile
    runs = {}
    for o in scopes.load(NEWS20):
        if o.module == "jit_run_epochs":
            runs.setdefault(o.run, []).append(o)
    assert len(runs) == 1
    (run,) = runs.values()
    unscoped = [o for o in load("scatter_ms").counted(run) if not o.op_name]
    assert sum(o.end - o.start for o in unscoped) / 1e6 > 0.9 * scatter
