"""The harness end to end on the CPU at tiny sizes: found by name, refuses
to run without a TPU, and decides ``correct`` against broken programs."""

import json
import os
import re
import subprocess
import sys

import pytest

from bench.tests import tiny

REPO = tiny.REPO


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("checkout")))


def _cli(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "realsim-hinge.gap",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_run_refuses_without_tpu():
    p = _cli(REPO)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_run_refuses_with_only_benchmark_files(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench")
    p = _cli(str(tmp_path))
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


@pytest.mark.parametrize("cell", ["tiny-hinge.gap", "tiny-logistic.gap"])
def test_sound_run(root, cell):
    rc, out, err = tiny.run(root, "--workload", cell, "--seed",
                            str(2**31 + 3), "--seconds", "0.5")
    res = tiny.result(out)
    assert rc == 0 and res["correct"] is True, err
    assert set(res["metrics"]) == {"time_to_gap_s", "epoch_s",
                                   "peak_hbm_bytes", "setup_s"}
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "checks"
    window = re.search(r"compiles in window (\{.*\})", out).group(1)
    assert json.loads(window.replace("'", '"'))["lowered"] == 0
    assert err.splitlines()[-1].startswith("check ")


def test_files_dropped_in_are_found(root):
    # a metric, a configuration, a traffic mix and a driver, each a new
    # file, and their entries in BENCHMARK.json: no other file changes
    with open(os.path.join(root, "bench", "metrics", "solves_seen.py"),
              "w") as f:
        f.write("def read(ctx):\n    return float(len(ctx.whole_epochs))\n")
    with open(os.path.join(root, "bench", "drivers", "solve_logged.py"),
              "w") as f:
        f.write("from bench.drivers import solve\n\n\n"
                "class Driver(solve.Driver):\n"
                "    def warm(self):\n"
                "        self.log('driver solve_logged')\n"
                "        super().warm()\n")
    cfg = json.load(open(os.path.join(root, "bench", "configs",
                                      "tiny-hinge.json")))
    tiny.write(root, "bench/configs/tiny-wide.json",
               dict(cfg, name="tiny-wide", m=300, d=2000, nnz_per_row=30,
                    gap_target=1.3))
    tiny.write(root, "bench/limits/tiny-wide.gap2.json", tiny.LIMITS)
    traffic = json.load(open(os.path.join(root, "bench", "traffic",
                                          "gap.json")))
    tiny.write(root, "bench/traffic/gap2.json",
               dict(traffic, eval_every=2, driver="solve_logged"))
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["workloads"].append({"name": "tiny-wide.gap2", "config":
                               "tiny-wide", "traffic": "gap2", "chips": 1,
                               "why": "CPU test"})
    bench["per_layer"].append({"name": "solves_seen", "unit": "solves",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "update rule",
                               "moves": "time_to_gap_s",
                               "workloads": ["tiny-wide.gap2"]})
    for m in bench["per_layer"]:
        m["workloads"] = sorted(set(m["workloads"]) | {"tiny-wide.gap2"})
    tiny.write(root, "BENCHMARK.json", bench)
    rc, out, err = tiny.run(root, "--workload", "tiny-wide.gap2", "--seed",
                            "9", "--seconds", "0.5", "--trace", "1")
    res = tiny.result(out)
    assert rc == 0 and res["correct"] is True, err
    assert res["metrics"]["solves_seen"]["value"] >= 1
    assert "epochs_to_gap" in res["metrics"]
    assert "driver solve_logged" in out
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    assert res["breakdown"]["device_ops"]
    assert all(e % 2 == 0 for e in re.findall(
        r"check solve \d+ epoch (\d+)", out) for e in [int(e)])


def _fault(kind):
    import jax.numpy as jnp

    from repro.engine import driver

    run = driver.run_epochs

    def broken(tile, state, *args, **kw):
        if kind == "unchanged":
            return state
        if kind == "half_rows":
            vals = tile.arrays[1]
            half = vals.shape[-2] // 2
            tile = tile._replace(arrays=(tile.arrays[0],
                                         vals.at[..., half:, :].set(0.0),
                                         *tile.arrays[2:]))
            return run(tile, state, *args, **kw)
        if kind == "no_exchange":
            # no block rotation: worker q updates block q at every inner
            # iteration, so the off-diagonal tiles are never visited
            perms, *rest = args
            own = jnp.broadcast_to(jnp.arange(perms.shape[-1]), perms.shape)
            return run(tile, state, own.astype(perms.dtype), *rest, **kw)
        new = run(tile, state, *args, **kw)           # "altered"
        return new._replace(w_grid=new.w_grid.at[0, 0].add(0.5))

    return broken


@pytest.mark.parametrize("kind", ["unchanged", "half_rows", "no_exchange",
                                  "altered"])
def test_broken_program_is_not_correct(root, monkeypatch, kind):
    from repro.engine import driver

    monkeypatch.setattr(driver, "run_epochs", _fault(kind))
    rc, out, err = tiny.run(root, "--workload", "tiny-hinge.gap", "--seed",
                            "4", "--seconds", "0.5")
    assert rc == 0
    assert tiny.result(out)["correct"] is False, err
