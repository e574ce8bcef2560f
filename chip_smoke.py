"""Smoke run of the DSO solver on a TPU chip, through its normal entry points.

    python chip_smoke.py                 # Phase A + Phase B on one chip
    python chip_smoke.py --four-chips    # the sharded ring on four chips

Phase A (agreement at small size): ``engine.solve`` on the chip and on the
in-process CPU device for the dense, sparse and bucketed XLA backends and
the dense Pallas backends, on a hinge/L2, a logistic/L2 and a square/L1
problem; primal, dual, gap and saddle value must agree to 1e-4 relative.  The
one-hot sparse Pallas kernel (``sparse_pallas``) runs compiled on the chip
against the same backend in the CPU's interpreter, to the same tolerance.
The bucketed sparse Pallas backend must be refused with ``ValueError``
while the TPU compiler cannot lower its in-kernel gather and scatter-add.

Phase B (the deployment): real-sim's published shape (72,309 rows x 20,958
features, 51 nonzeros per row, power-law column popularity alpha=1.1, hinge
loss, L2, lambda=1e-4, p=4), generated from ``--seed``, tiled in the layout
``backend="auto"`` picks, solved for 15 epochs with a blocked CSR primal
evaluation every 5.  The primal must be finite and fall.

``--four-chips`` runs only ``ShardedDSO`` on a 4-chip mesh (cyclic ring,
then lpt over point-to-point routes) against the grid simulator on one chip
of the same process: max|dw| and max|dalpha| <= 1e-5 (Lemma 2).  It does so
twice at real-sim's shape: with the power-law columns above (skewed tiles,
so ``auto`` takes the K-bucketed layout) and with uniform column popularity
(tile-K skew near 1, so ``auto`` keeps the uniform block-ELL layout, whose
kernel on a TPU is the one-hot ``sparse_pallas``).

Everything runs in this one process (a chip belongs to one process).  The
script fails, printing no result, when JAX finds no TPU.  Its last line of
output is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
Timings it prints are one-off readings, not benchmarks.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

#: real-sim (LIBSVM binary collection): 72,309 x 20,958, ~3.7M nonzeros;
#: hinge/L2 at lambda=1e-4 as ``configs.dso_problems.SVM_REALSIM``
REALSIM = dict(m=72_309, d=20_958, nnz_per_row=51, alpha=1.1, loss="hinge",
               reg="l2", lam=1e-4, p=4)
#: the same shape with uniform column popularity: every tile has about the
#: same K, so ``auto`` keeps the uniform block-ELL layout
REALSIM_UNIFORM = dict(REALSIM, alpha=0.0)

PHASE_A_BACKENDS = ("dense_jnp", "sparse_jnp", "sparse_bucketed_jnp",
                    "dense_pallas_fused", "dense_pallas_block")
SPARSE_PALLAS_BACKENDS = ("sparse_pallas",)
#: refused on the chip while Mosaic cannot lower their gather/scatter-add
REFUSED_BACKENDS = ("sparse_bucketed_pallas",)
RTOL = 1e-4          # Phase A: chip vs CPU, relative
LEMMA2_ATOL = 1e-5   # four chips: sharded vs grid simulator, max |diff|


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ data --


def powerlaw_csr(m: int, d: int, nnz_per_row: int, alpha: float, seed: int):
    """Sparse classification data in CSR form, drawn in bulk.

    Column j is drawn with probability ~ (j+1)^-alpha and no column repeats
    within a row: the first ``nnz_per_row`` distinct columns of a
    with-replacement stream, which is successive sampling without
    replacement, the skew model of ``data.synthetic.powerlaw_columns``.
    Values are normal, rows scaled to unit norm; labels are the signs of a
    planted linear model plus noise, as in ``make_classification``.
    Returns ``(CSRMatrix, y)``.
    """
    from repro.sparse.format import CSRMatrix

    rng = np.random.default_rng(seed)
    k = nnz_per_row
    cdf = np.cumsum(np.arange(1, d + 1, dtype=np.float64) ** -alpha)
    cdf /= cdf[-1]
    cols = np.empty((m, k), np.int32)
    todo = np.arange(m)
    draws = 4 * k
    while todo.size:
        n = todo.size
        c = np.minimum(np.searchsorted(cdf, rng.random((n, draws)),
                                       side="right"), d - 1)
        key = (np.arange(n, dtype=np.int64)[:, None] * d + c).ravel()
        uniq, first = np.unique(key, return_index=True)
        uk = uniq[np.argsort(first, kind="stable")]   # draw order, row-major
        row = uk // d
        start = np.searchsorted(row, np.arange(n))
        keep = np.arange(row.size) - start[row] < k
        done = np.bincount(row, minlength=n) >= k
        sel = keep & done[row]
        cols[todo[done]] = np.sort((uk[sel] % d).reshape(-1, k), axis=1)
        todo = todo[~done]
        draws *= 2
    vals = rng.normal(0.0, 1.0, (m, k)).astype(np.float32)
    vals /= np.maximum(np.linalg.norm(vals, axis=1, keepdims=True), 1e-8)
    csr = CSRMatrix(indptr=np.arange(m + 1, dtype=np.int64) * k,
                    indices=cols.ravel(), values=vals.ravel(), shape=(m, d))
    w_star = rng.normal(0.0, 1.0, d).astype(np.float32)
    margin = csr.matvec(w_star) + 0.1 * rng.normal(0.0, 1.0, m)
    y = np.where(margin >= 0, 1.0, -1.0).astype(np.float32)
    return csr, y


def problem_from_csr(csr, y, lam: float, loss: str, reg: str):
    """A dense ``Problem`` whose X stays in host memory (numpy).

    ``ShardedDSO`` takes only a dense Problem; its tilers read X through
    numpy, so X need not go to a device.  Nothing here evaluates the dense
    objectives (they would move X to the device)."""
    from repro.core.saddle import Problem

    return Problem(X=csr.toarray(), y=np.asarray(y, np.float32),
                   lam=float(lam),
                   row_nnz=np.maximum(csr.row_nnz(), 1.0),
                   col_nnz=np.maximum(csr.col_nnz(), 1.0),
                   nnz=float(csr.nnz), loss_name=loss, reg_name=reg)


# --------------------------------------------------------------- phase A --


def phase_a_problems():
    """(label, factory, row_batches).  Factories build on the default
    device in effect, so each run's data lives on the device it runs on.
    The m=256 cases run row batches of 16 rows, the shape on which
    ``dense_pallas_block`` takes its one-launch block kernel."""
    from repro.data.synthetic import make_classification, make_regression

    return [
        ("hinge/l2 m=200 d=80", lambda: make_classification(
            m=200, d=80, density=0.15, loss="hinge", lam=1e-3, seed=0), 1),
        ("logistic/l2 m=256 d=96", lambda: make_classification(
            m=256, d=96, density=0.15, loss="logistic", lam=1e-3, seed=1),
         4),
        ("square/l1 m=256 d=96", lambda: make_regression(
            m=256, d=96, density=0.15, lam=1e-3, seed=2, reg="l1"), 4),
    ]


def objectives_hook(prob):
    """P(w), D(alpha), their gap and the saddle value f(w, alpha).  Under
    L1, D is -inf (and the gap +inf) until ||X^T alpha / m||_inf <= lambda,
    so the saddle value is the finite coupled check there."""
    from repro.core.saddle import (dual_objective, primal_objective,
                                   saddle_objective)

    def hook(t, w, alpha):
        p = float(primal_objective(prob, w))
        d = float(dual_objective(prob, alpha))
        return dict(epoch=t, primal=p, dual=d, gap=p - d,
                    saddle=float(saddle_objective(prob, w, alpha)))

    return hook


def _solve_on(device, factory, backend: str, *, row_batches: int,
              epochs: int, use_adagrad: bool = True):
    import jax

    from repro.engine import solve

    with jax.default_device(device):
        prob = factory()
        res = solve(prob, backend=backend, p=4, epochs=epochs, eta0=0.5,
                    use_adagrad=use_adagrad, row_batches=row_batches,
                    eval_every=epochs, eval_hook=objectives_hook(prob))
    ran_on = {d.platform for d in res.w.devices()}
    if ran_on != {device.platform}:
        raise SmokeFailure(f"{backend} was asked to run on "
                           f"{device.platform} but its result is on {ran_on}")
    return res.history[-1]


def _rel(a: float, b: float) -> float:
    """Relative difference; two equal infinities (an L1 dual) agree."""
    if not (math.isfinite(a) and math.isfinite(b)):
        return 0.0 if a == b else math.inf
    return abs(a - b) / max(abs(b), 1e-30)


def phase_a(chip, cpu, *, backends=PHASE_A_BACKENDS, epochs: int = 8,
            problems=None, use_adagrad: bool = True) -> list[dict]:
    """Solve every (problem, backend) pair on ``chip`` and on ``cpu``;
    raise ``SmokeFailure`` unless primal, dual and gap agree to ``RTOL``."""
    rows = []
    kw = dict(epochs=epochs, use_adagrad=use_adagrad)
    step = "" if use_adagrad else " (plain step)"
    for label, factory, rb in problems or phase_a_problems():
        for be in backends:
            got = _solve_on(chip, factory, be, row_batches=rb, **kw)
            ref = _solve_on(cpu, factory, be, row_batches=rb, **kw)
            rel = {k: _rel(got[k], ref[k])
                   for k in ("primal", "dual", "gap", "saddle")}
            worst = max(rel.values())
            ok = (math.isfinite(got["primal"]) and math.isfinite(
                got["saddle"]) and worst <= RTOL)
            log(f"phase A  {label:24s} rb={rb} {be:20s}{step} "
                f"{chip.platform}: primal={got['primal']:.9g} "
                f"dual={got['dual']:.9g} gap={got['gap']:.9g} "
                f"saddle={got['saddle']:.9g} | cpu: "
                f"primal={ref['primal']:.9g} dual={ref['dual']:.9g} "
                f"gap={ref['gap']:.9g} saddle={ref['saddle']:.9g} | "
                f"max rel diff={worst:.3e} {'ok' if ok else 'FAIL'}")
            rows.append(dict(problem=label, backend=be, chip=got, cpu=ref,
                             max_rel=worst, ok=ok))
    bad = [(r["problem"], r["backend"], r["max_rel"]) for r in rows
           if not r["ok"]]
    if bad:
        raise SmokeFailure(f"phase A: chip and CPU disagree beyond "
                           f"{RTOL} relative: {bad}")
    return rows


def phase_a_sparse_pallas(chip, cpu, *, epochs: int = 8,
                          problems=None) -> dict:
    """The sparse Pallas backends on the chip: the one-hot kernel compiled
    against the CPU's interpreter (as ``phase_a``), with the AdaGrad step
    and with the plain step; then the Mosaic probe's verdict, and where it
    refuses, asking for the bucketed kernel must raise its ``ValueError``
    (nothing else may run in its place)."""
    import jax

    from repro.engine import solve
    from repro.kernels import ops

    problems = problems or phase_a_problems()
    out = {r["backend"]: "agrees"
           for use_adagrad in (True, False)
           for r in phase_a(chip, cpu, backends=SPARSE_PALLAS_BACKENDS,
                            epochs=epochs, problems=problems,
                            use_adagrad=use_adagrad)}
    label, factory, _ = problems[0]
    with jax.default_device(chip):
        verdict = ops.mosaic_sparse_gather_error()
        first = "lowers" if verdict is None else verdict.splitlines()[0]
        log(f"phase A  mosaic gather/scatter probe on {chip.platform}: "
            f"{first}")
        for be in REFUSED_BACKENDS:
            try:
                solve(factory(), backend=be, p=4, epochs=1, eta0=0.5,
                      eval_hook=None)
            except ValueError as e:
                out[be] = "refused"
                log(f"phase A  {be:22s} refused with ValueError: "
                    f"{str(e).splitlines()[0][:160]}")
                continue
            if verdict is not None and not ops._resolve_interpret(None):
                raise SmokeFailure(f"{be} ran although the probe refused "
                                   f"its ops: {first}")
            out[be] = "ran"
            log(f"phase A  {be:22s} ran ({label}, "
                f"interpret={ops._resolve_interpret(None)})")
    return out


# --------------------------------------------------------------- phase B --


def phase_b(*, m: int, d: int, nnz_per_row: int, alpha: float, loss: str,
            reg: str, lam: float, p: int, epochs: int = 15,
            eval_every: int = 5, eta0: float = 0.5, seed: int = 0) -> dict:
    """The deployment-shaped run: generate, tile in the layout ``auto``
    picks, ``engine.solve(data, backend="auto")``, evaluate P(w) with the
    blocked CSR evaluation every ``eval_every`` epochs."""
    import jax
    import jax.numpy as jnp

    from repro.engine import (as_tile_data, make_csr_primal_eval,
                              resolve_backend, resolve_backend_for_layout,
                              solve)
    from repro.sparse.format import (bucketed_grid_from_csr, csr_k_per_tile,
                                     sparse_grid_from_csr, tile_k_skew)

    t0 = time.perf_counter()
    csr, y = powerlaw_csr(m, d, nnz_per_row, alpha, seed)
    t_gen = time.perf_counter() - t0
    skew = tile_k_skew(csr_k_per_tile(csr, p))
    layout = resolve_backend("auto", csr.density, k_skew=skew).layout
    builders = {"sparse": sparse_grid_from_csr,
                "bucketed": bucketed_grid_from_csr}
    if layout not in builders:
        raise SmokeFailure(f"auto picked the {layout} layout for "
                           f"density {csr.density:.2e}")
    t0 = time.perf_counter()
    data = builders[layout](csr, y, p)
    jax.block_until_ready(as_tile_data(data))
    t_tile = time.perf_counter() - t0
    picked = resolve_backend_for_layout("auto", layout, data.db)
    log(f"phase B  m={m} d={d} nnz={csr.nnz} ({nnz_per_row}/row) "
        f"alpha={alpha} {loss}/{reg} lam={lam} p={p} seed={seed}: "
        f"generated in {t_gen:.2f} s, tiled in {t_tile:.2f} s; "
        f"tile-K skew {skew:.2f} -> backend {picked.name} (db {data.db})")

    evaluate = make_csr_primal_eval(csr, y, lam, loss, reg)
    p0 = float(evaluate.primal(jnp.zeros(d, jnp.float32)))
    primals, stamps = [p0], []

    def hook(t, w, alpha_):
        jax.block_until_ready(w)
        done = time.perf_counter()
        entry = evaluate(t, w, alpha_)
        primals.append(entry["primal"])
        stamps.append((done, time.perf_counter()))
        return entry

    t_start = time.perf_counter()
    solve(data, backend="auto", p=p, epochs=epochs, eta0=eta0,
          eval_every=eval_every, eval_hook=hook, seed=seed, loss_name=loss,
          reg_name=reg, lam=lam, m=m, d=d)
    starts = [t_start] + [s[1] for s in stamps[:-1]]
    chunks = [s[0] - b for s, b in zip(stamps, starts)]
    warm = float(np.median(chunks[1:])) if len(chunks) > 1 else float("nan")
    compile_s = chunks[0] - warm
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    at = [0] + list(range(eval_every, epochs + 1, eval_every))
    log(f"phase B  primal at epochs {at}: "
        + ", ".join(f"{v:.9g}" for v in primals))
    log(f"phase B  chunk seconds ({eval_every} epochs each): "
        + ", ".join(f"{c:.4f}" for c in chunks))
    log(f"phase B  compile seconds (first chunk minus a warm one): "
        f"{compile_s:.3f}")
    log(f"phase B  warm seconds per epoch (one-off reading on "
        f"{jax.devices()[0].device_kind}, not a benchmark): "
        f"{warm / eval_every:.6f}")
    log(f"phase B  peak_bytes_in_use: {peak}")
    if not all(math.isfinite(v) for v in primals):
        raise SmokeFailure(f"phase B: non-finite primal {primals}")
    if not all(b < a for a, b in zip(primals, primals[1:])):
        raise SmokeFailure(f"phase B: primal not falling {primals}")
    return dict(backend=picked.name, skew=skew, primals=primals,
                chunks=chunks, compile_s=compile_s,
                s_per_epoch=warm / eval_every, peak_bytes=peak)


# ------------------------------------------------------------ four chips --


def phase_four_chips(*, m: int, d: int, nnz_per_row: int, alpha: float,
                     loss: str, reg: str, lam: float, p: int = 4,
                     epochs: int = 3, eta0: float = 0.5, seed: int = 0,
                     devices=None) -> list[dict]:
    """``ShardedDSO(impl="auto")`` on a p-device mesh — the cyclic ring,
    then lpt over point-to-point routes — against ``engine.solve`` on one
    device of the same process (Lemma 2: max |diff| <= LEMMA2_ATOL)."""
    import jax
    from jax.sharding import Mesh

    from repro.core.dso_dist import ShardedDSO
    from repro.engine import solve

    devices = list(devices or jax.devices())[:p]
    if len(devices) < p:
        raise SmokeFailure(f"need {p} devices, found {len(devices)}")
    csr, y = powerlaw_csr(m, d, nnz_per_row, alpha, seed)
    prob = problem_from_csr(csr, y, lam, loss, reg)
    log(f"four chips  m={m} d={d} nnz={csr.nnz} {loss}/{reg} lam={lam} "
        f"p={p} epochs={epochs} seed={seed}; dense host X "
        f"{prob.X.nbytes / 2**30:.2f} GiB")
    mesh = Mesh(np.array(devices), ("dso",))
    rows = []
    for label, kw in (("cyclic ring (overlap)",
                       dict(schedule="cyclic", overlap=True)),
                      ("lpt p2p", dict(schedule="lpt", comm="p2p"))):
        t0 = time.perf_counter()
        opt = ShardedDSO(prob, mesh, impl="auto", seed=seed, **kw)
        opt.run_epochs(epochs, eta0)
        opt.wait()
        t_sharded = time.perf_counter() - t0
        owners = [s.device.id for s in opt.w.addressable_shards]
        w_s, a_s = np.asarray(opt.w_full()), np.asarray(opt.alpha_full())
        with jax.default_device(devices[0]):
            ref = solve(prob, backend="auto", schedule=kw["schedule"], p=p,
                        epochs=epochs, eta0=eta0, seed=seed, eval_hook=None)
        dw = float(np.abs(w_s - np.asarray(ref.w)).max())
        da = float(np.abs(a_s - np.asarray(ref.alpha)).max())
        peaks = [(dv.memory_stats() or {}).get("peak_bytes_in_use")
                 for dv in devices]
        ok = (dw <= LEMMA2_ATOL and da <= LEMMA2_ATOL
              and len(set(owners)) == p and np.isfinite(w_s).all())
        log(f"four chips  alpha={alpha} {label:22s} "
            f"backend={opt.backend.name} "
            f"shard devices={owners} max|dw|={dw:.3e} max|dalpha|={da:.3e} "
            f"(bound {LEMMA2_ATOL}) sharded set-up+run {t_sharded:.2f} s "
            f"(one-off) peak bytes per chip={peaks} "
            f"{'ok' if ok else 'FAIL'}")
        rows.append(dict(label=label, backend=opt.backend.name,
                         owners=owners, dw=dw, da=da, peaks=peaks, ok=ok))
    bad = [r["label"] for r in rows if not r["ok"]]
    if bad:
        raise SmokeFailure(f"four chips: sharded run disagrees with the grid "
                           f"simulator or shards overlap: {bad}")
    return rows


# ------------------------------------------------------------------ main --


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only ShardedDSO on a 4-chip mesh against the "
                         "grid simulator")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated data")
    args = ap.parse_args(argv)

    src = os.path.join(REPO, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        # Phase A's reference runs on the in-process CPU device
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (first device: {dev.platform}); "
              f"nothing is run on another platform", file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache(os.path.join(REPO, ".jax_cache"))
    log(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache}")
    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(**REALSIM, seed=args.seed)
        phase_four_chips(**REALSIM_UNIFORM, seed=args.seed)
    else:
        cpu = jax.devices("cpu")[0]
        phase_a(dev, cpu)
        phase_a_sparse_pallas(dev, cpu)
        phase_b(**REALSIM, seed=args.seed)
    log(f"wall seconds {time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
