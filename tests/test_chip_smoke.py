"""``chip_smoke.py``'s phases on the CPU at a tiny size.

The script itself refuses to run anywhere but on a TPU; its phase
functions take the device(s) to run on, so here they run on the CPU (both
sides of Phase A on the same CPU device, Pallas kernels in interpret mode)
to pin paths, arguments and checks before any chip time is spent.
"""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

TINY = dict(m=512, d=256, nnz_per_row=8, alpha=1.1, loss="hinge", reg="l2",
            lam=1e-4, p=4)


def _tiny_problems():
    from repro.data.synthetic import make_classification, make_regression

    return [
        ("hinge/l2 m=64 d=32", lambda: make_classification(
            m=64, d=32, density=0.2, loss="hinge", lam=1e-3, seed=0), 1),
        ("square/l1 m=64 d=32", lambda: make_regression(
            m=64, d=32, density=0.2, lam=1e-3, seed=2, reg="l1"), 2),
    ]


def test_phase_a_agrees_on_cpu():
    cpu = jax.devices("cpu")[0]
    rows = chip_smoke.phase_a(cpu, cpu, epochs=2, problems=_tiny_problems())
    assert len(rows) == 2 * len(chip_smoke.PHASE_A_BACKENDS)
    assert all(r["ok"] and r["max_rel"] == 0.0 for r in rows)


def test_phase_a_sparse_pallas_runs_in_interpreter_on_cpu():
    """Off the chip the one-hot kernel agrees with itself and the probe
    lowers (interpreter), so the bucketed Pallas backend runs instead of
    being refused."""
    cpu = jax.devices("cpu")[0]
    out = chip_smoke.phase_a_sparse_pallas(cpu, cpu, epochs=2,
                                           problems=_tiny_problems())
    assert out == {"sparse_pallas": "agrees",
                   "sparse_bucketed_pallas": "ran"}


def test_phase_b_primal_falls_on_cpu(capsys):
    res = chip_smoke.phase_b(**TINY, epochs=2, eval_every=1)
    assert res["backend"] in ("sparse_jnp", "sparse_bucketed_jnp")
    assert len(res["primals"]) == 3 and res["primals"][0] == 1.0
    assert "phase B  primal at epochs [0, 1, 2]" in capsys.readouterr().out


def test_powerlaw_csr_rows_have_distinct_columns():
    csr, y = chip_smoke.powerlaw_csr(300, 50, 20, 1.1, seed=3)
    cols = csr.indices.reshape(300, 20)
    assert csr.nnz == 300 * 20 and set(y.tolist()) <= {-1.0, 1.0}
    assert all(len(set(r)) == 20 for r in cols.tolist())
    assert (cols[:, 1:] > cols[:, :-1]).all() and cols.max() < 50
    again, _ = chip_smoke.powerlaw_csr(300, 50, 20, 1.1, seed=3)
    assert (again.indices == csr.indices).all()


def test_main_refuses_without_tpu(capsys):
    """No chip, no run: exit non-zero and print no result line."""
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


FOUR_SCRIPT = """
import json, chip_smoke
rows = chip_smoke.phase_four_chips(**json.loads(%r), epochs=2)
print("FOUR_OK", len(rows))
"""


@pytest.mark.parametrize("alpha,backend", [(1.1, "sparse_jnp"),
                                           (2.0, "sparse_bucketed_jnp")])
def test_phase_four_chips_on_host_devices(alpha, backend):
    """The --four-chips phase on 4 host devices (a subprocess, so this
    process keeps one device): the cyclic ring and lpt/p2p both match the
    grid simulator and hold 4 distinct shards.  alpha=2.0 skews the tiles
    enough that ``auto`` takes the bucketed layout."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(REPO, "src"), REPO])
    spec = json.dumps(dict(TINY, alpha=alpha))
    out = subprocess.run([sys.executable, "-c", FOUR_SCRIPT % spec],
                         env=env, capture_output=True, text=True,
                         timeout=600, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "FOUR_OK 2" in out.stdout
    assert out.stdout.count(f"backend={backend} ") == 2
    assert out.stdout.count(" ok\n") == 2


def test_four_chip_uniform_case_takes_the_onehot_kernel(monkeypatch):
    """The second --four-chips case (real-sim's shape, uniform column
    popularity) keeps ``auto`` on the uniform layout, and on a TPU its
    blocks are narrow enough for the one-hot kernel (platform mocked)."""
    from repro.engine.backends import (resolve_backend,
                                       resolve_backend_for_layout)
    from repro.kernels import ops
    from repro.sparse.format import (csr_k_per_tile, pad_to_multiple,
                                     tile_k_skew)

    cfg = chip_smoke.REALSIM_UNIFORM
    csr, _ = chip_smoke.powerlaw_csr(cfg["m"], cfg["d"], cfg["nnz_per_row"],
                                     cfg["alpha"], seed=0)
    skew = tile_k_skew(csr_k_per_tile(csr, cfg["p"]))
    layout = resolve_backend("auto", csr.density, k_skew=skew).layout
    assert layout == "sparse", skew
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    db = pad_to_multiple(cfg["d"], cfg["p"]) // cfg["p"]
    assert resolve_backend_for_layout("auto", layout, db).name \
        == "sparse_pallas"


def test_dso_perf_child_phases_fail_loudly(monkeypatch):
    """The benchmark phases that start host-mesh children refuse to start
    off the CPU, and a failed child raises (the run exits non-zero)
    instead of being recorded as a failed gate."""
    import types

    from benchmarks import dso_perf

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for bench in (dso_perf.bench_overlap, dso_perf.bench_chaos):
        with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
            bench()
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    failed = types.SimpleNamespace(returncode=1, stdout="", stderr="boom")
    monkeypatch.setattr(subprocess, "run", lambda *a, **k: failed)
    for bench in (dso_perf.bench_overlap, dso_perf.bench_chaos):
        with pytest.raises(RuntimeError, match="child process failed"):
            bench()


CACHE_SCRIPT = """
import sys
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
print("CACHE_DIR", enable_compile_cache(sys.argv[1]))
jax.jit(lambda x: jnp.sin(x) * 2.0)(jnp.ones(8)).block_until_ready()
"""


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_directory(tmp_path, env_dir):
    """Compiles land in ``JAX_COMPILATION_CACHE_DIR`` when it is set, and
    in the fixed default directory otherwise."""
    default, chosen = tmp_path / "default", tmp_path / "from_env"
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(chosen)
    out = subprocess.run([sys.executable, "-c", CACHE_SCRIPT, str(default)],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    want, other = (chosen, default) if env_dir else (default, chosen)
    assert f"CACHE_DIR {want}" in out.stdout
    assert any(want.iterdir())
    assert not other.exists()
