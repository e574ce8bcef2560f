"""Run one benchmark cell once, on the accelerator of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); the traffic names its driver
(``bench/drivers/<driver>.py``); each per-layer metric is read by
``bench/metrics/<metric>.py``; limits of the comparison that decides
``correct`` are in ``bench/limits/<cell>.json``.

Set-up (generation, tiling, warm-up of the cell's shapes) is timed as
``setup_s``; then the driver measures ``--seconds`` of solver time.  With
``--trace 1`` the window runs under the profiler and the line carries the
per-layer metrics instead of the end-to-end ones.  After the window the
program's iterates are compared with the plain reference.  The last line
of standard output is one JSON object; every line before it is a log.
Without a TPU, or with fewer chips than the cell asks for, the run fails
and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(msg: str) -> None:
    print(msg, flush=True)


def _read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, workload: str):
    """(benchmark, cell, config, traffic, limits), all found by name."""
    bench = _read(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}: have {sorted(cells)}")
    cell = cells[workload]
    d = os.path.join(root, "bench")
    return (bench, cell,
            _read(os.path.join(d, "configs", cell["config"] + ".json")),
            _read(os.path.join(d, "traffic", cell["traffic"] + ".json")),
            _read(os.path.join(d, "limits", workload + ".json")))


def load_driver(root: str, name: str):
    import importlib.util

    path = os.path.join(root, "bench", "drivers", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_driver_" + name,
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class CompileCounter:
    """Lowerings and backend compiles, from JAX's monitoring events."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n = {"lowered": 0, "compiled": 0, "cache_hits": 0,
                  "cache_misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == self.LOWER:
            self.n["lowered"] += 1
        elif event == self.COMPILE:
            self.n["compiled"] += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.n["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.n["cache_misses"] += 1

    def snapshot(self) -> dict:
        return dict(self.n)

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def per_layer(bench: dict, workload: str, ctx, root: str) -> dict:
    from bench.metrics import load

    out = {}
    for spec in bench["per_layer"]:
        if workload not in spec.get("workloads", [workload]):
            continue
        v = load(spec["name"], os.path.join(root, "bench", "metrics")) \
            .read(ctx)
        if v is not None:
            out[spec["name"]] = {"value": float(v), "unit": spec["unit"]}
    return out


def main(argv=None, *, root: str = ROOT,
         require_accelerator: bool = True) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for p in (os.path.join(root, "src"), root):
        if p not in sys.path:
            sys.path.insert(0, p)
    bench, cell, cfg, traffic, limits = load_cell(root, args.workload)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_accelerator and (dev.platform != "tpu"
                                or len(devices) < cell["chips"]):
        print(f"run.py: the cell needs {cell['chips']} TPU chip(s); JAX "
              f"found {len(devices)} {dev.platform} device(s)",
              file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache(os.path.join(root, ".jax_cache"))
    counter = CompileCounter()
    try:
        return _run_cell(args, root, bench, cell, cfg, traffic, limits,
                         counter, t_start, cache, require_accelerator)
    finally:
        counter.close()


def _run_cell(args, root, bench, cell, cfg, traffic, limits, counter,
              t_start, cache, require_accelerator) -> int:
    import jax

    from bench import check, work, xplane as tr

    devices = jax.devices()
    dev = devices[0]
    used = devices[:cell["chips"]]
    log(f"cell {args.workload} seed {args.seed} seconds {args.seconds} "
        f"trace {args.trace}: {dev.platform} {dev.device_kind} "
        f"x{len(devices)}, jax {jax.__version__}, compile cache {cache}; "
        f"devices up {time.perf_counter() - t_start:.3f} s after start")
    drv = load_driver(root, traffic["driver"]).Driver(
        cfg, traffic, args.seed, traced=bool(args.trace), log=log)
    drv.warm()
    setup_s = time.perf_counter() - t_start
    at_setup = counter.snapshot()
    log(f"set-up {setup_s:.3f} s; compiles in set-up {at_setup}")

    trace_dir = os.path.join(root, ".bench_trace")
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # host spans: annotations, dispatch
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        res = drv.window(args.seconds)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    in_window = {k: v - at_setup[k] for k, v in counter.snapshot().items()}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    log(f"window: {len(res['solves'])} solves ({res['whole']} whole, "
        f"{res['failed']} failed), {res['epochs']} epochs, solver "
        f"{res['solver_s']:.6f} s, ended {res['late_s']:.6f} s late; "
        f"whole-solve epochs {res['whole_epochs']}; compiles in window "
        f"{in_window}")
    drv.release()

    t = time.perf_counter()
    values = check.readings(drv.csr, cfg, traffic, drv.checks)
    log(f"reference compared in {time.perf_counter() - t:.3f} s")
    verdict = check.judge(values, limits)
    correct = (all(ok for *_, ok in verdict) and res["failed"] == 0
               and res["whole"] >= 1)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    out = {"correct": correct, "attempted": res["attempted"],
           "failed": res["failed"]}
    if args.trace:
        trace = tr.load(trace_dir)
        window = tr.span_intervals(trace, "bench.solve")
        try:
            peak_row = work.peaks(dev.device_kind)
        except KeyError:
            if require_accelerator:
                raise
            peak_row = None
        ctx = SimpleNamespace(
            trace=trace, window=window, epochs=res["epochs"],
            solver_s=res["solver_s"], whole_epochs=res["whole_epochs"],
            hbm_bytes_per_epoch=work.epoch_hbm_bytes(cfg["m"], cfg["d"],
                                                     drv.csr.nnz),
            peak=peak_row)
        out["metrics"] = per_layer(bench, args.workload, ctx, root)
        device["busy_s"] = tr.busy_seconds_per_device(trace, window)
        device["window_s"] = tr.length(window) / 1e9
        out["device"] = device
        out["breakdown"] = {"device_ops": tr.top_ops(trace, window),
                            "idle_gaps": tr.idle_gaps(trace, window)}
    else:
        m = {"setup_s": (setup_s, "s"), "peak_hbm_bytes": (peak, "bytes")}
        if res["time_to_gap_s"] is not None:
            m["time_to_gap_s"] = (res["time_to_gap_s"], "s")
        if res["epoch_s"] is not None:
            m["epoch_s"] = (res["epoch_s"], "s")
        out["metrics"] = {k: {"value": float(v), "unit": u}
                          for k, (v, u) in m.items()}
        out["device"] = device
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, v, lim, _ in verdict}
    out["checks"]["failed_solves"] = {"value": res["failed"], "limit": 0}
    out["checks"]["whole_solves"] = {"value": res["whole"], "limit": ">=1"}
    for k, v, lim, ok in verdict:
        print(f"check {k} {v!r} limit {lim!r} {'ok' if ok else 'FAIL'}",
              file=sys.stderr)
    print(f"check failed_solves {res['failed']} limit 0", file=sys.stderr)
    print(f"check whole_solves {res['whole']} limit >=1", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    # this file's directory is not a package root: keep its modules from
    # shadowing top-level ones
    sys.path = [p for p in sys.path if os.path.abspath(p) != HERE]
    sys.exit(main())
