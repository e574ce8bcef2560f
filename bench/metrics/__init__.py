"""Per-layer metrics, one file each, found by the metric's name.

Each file defines ``read(ctx) -> float | None``.  ``ctx`` carries the
traced run's reduction: ``trace`` (``bench.xplane.Trace``), ``window`` (the
solver's intervals, ``bench.solve`` spans), ``epochs`` and ``solver_s`` of
the traced window, ``whole_epochs`` (epochs of each whole solve),
``hbm_bytes_per_epoch`` (``bench.work``) and ``peak`` (the device's row of
``bench/peaks.json``).  A reader that finds nothing to read returns None
and the metric is left out of the line.
"""

from __future__ import annotations

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str, directory: str = HERE):
    path = os.path.join(directory, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
