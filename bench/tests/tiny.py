"""A checkout-shaped directory with tiny cells, for CPU tests of the
harness: ``BENCHMARK.json`` naming only tiny cells, a copy of ``bench/``
with their configuration and limit files, and ``src`` linked in."""

from __future__ import annotations

import contextlib
import io
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY = {"tiny-hinge": dict(m=500, d=300, nnz_per_row=8, loss="hinge",
                           alpha0=0.0, gap_target=1.0),
        "tiny-logistic": dict(m=400, d=900, nnz_per_row=12,
                              loss="logistic", alpha0=0.0005,
                              gap_target=0.35)}
LIMITS = {"w_rel_err": 1e-4, "alpha_rel_err": 1e-4, "gap_rel_err": 1e-4}


def make_root(path: str) -> str:
    """Lay out the tiny checkout at ``path`` and return it."""
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(path, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(path, "src"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    base = json.load(open(os.path.join(REPO, "bench", "configs",
                                       "realsim-hinge.json")))
    bench["configs"], bench["workloads"] = [], []
    for name, over in TINY.items():
        cfg = dict(base, name=name, **over)
        write(path, f"bench/configs/{name}.json", cfg)
        write(path, f"bench/limits/{name}.gap.json", LIMITS)
        bench["configs"].append({"name": name, "source": base["source"],
                                 "file": f"bench/configs/{name}.json",
                                 "reduced": [], "why": "CPU test"})
        bench["workloads"].append({"name": f"{name}.gap", "config": name,
                                   "traffic": "gap", "chips": 1,
                                   "why": "CPU test"})
    cells = [w["name"] for w in bench["workloads"]]
    for m in bench["per_layer"]:
        m["workloads"] = cells
    write(path, "BENCHMARK.json", bench)
    return path


def write(root: str, rel: str, obj) -> None:
    with open(os.path.join(root, rel), "w") as f:
        json.dump(obj, f)


def run(root: str, *args: str) -> tuple[int, str, str]:
    """``bench/run.py`` in this process on the CPU, the accelerator check
    skipped; returns (exit code, stdout, stderr)."""
    import jax

    from bench import run as bench_run

    keep = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    jax.config.update("jax_enable_compilation_cache", False)
    out, err = io.StringIO(), io.StringIO()
    try:
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            rc = bench_run.main(list(args), root=root,
                                require_accelerator=False)
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
    return rc, out.getvalue(), err.getvalue()


def result(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])
