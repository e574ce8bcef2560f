"""Driver ``solve``: back-to-back ``repro.engine.solve`` calls on grid data
built once in set-up, each from the zero state, each stopped at the first
gap check that meets the traffic's target.

The solver clock runs while the solver works and stops for every gap
check: the hook waits for the iterates, stops the clock, copies them to
the host, computes the relative duality gap there in float64 with the
benchmark's own code, and starts the clock again.  A solve that reaches the traffic's epoch cap has
failed.  The window closes at the first check after ``seconds`` of solver
time; the solve it cuts counts its epochs but is neither whole nor failed.
"""

from __future__ import annotations

import time
from contextlib import nullcontext

import numpy as np

from bench import gen, objective


class _TargetMet(Exception):
    pass


class _WindowClosed(Exception):
    pass


def _annotation(name: str, on: bool):
    if not on:
        return nullcontext()
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


class _Clock:
    """Solver time; each running interval is a ``bench.solve`` span in a
    traced run."""

    def __init__(self, annotate: bool):
        self.total = 0.0
        self._annotate = annotate
        self._t = None
        self._ann = None

    def start(self) -> None:
        self._ann = _annotation("bench.solve", self._annotate)
        self._ann.__enter__()
        self._t = time.perf_counter()

    def stop(self) -> float:
        dt = time.perf_counter() - self._t
        self._ann.__exit__(None, None, None)
        self.total += dt
        return dt


class Driver:
    """Set-up in the constructor: data from the seed, the program's own
    skew probe and tiler, the gap check's float64 CSR on the host."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, *, traced: bool,
                 log):
        import jax

        from repro.engine import as_tile_data, resolve_backend
        from repro.sparse.format import (CSRMatrix, bucketed_grid_from_csr,
                                         csr_k_per_tile, sparse_grid_from_csr,
                                         tile_k_skew)

        objective.check_loss(cfg["loss"], cfg["reg"])
        self.cfg, self.traffic, self.log = cfg, traffic, log
        self.traced = traced
        k, cap = int(traffic["eval_every"]), int(traffic["epoch_cap"])
        if cap % k:
            raise ValueError(f"epoch_cap {cap} is not a multiple of "
                             f"eval_every {k}: a ragged chunk would compile")
        with _annotation("bench.generate", traced):
            t = time.perf_counter()
            self.csr = gen.permute_rows(
                gen.powerlaw_csr(cfg["m"], cfg["d"], cfg["nnz_per_row"],
                                 cfg["alpha"], cfg["data_seed"]), seed,
                int(cfg["p"]))
            log(f"generated {cfg['m']} x {cfg['d']}, {self.csr.nnz} nonzeros "
                f"in {time.perf_counter() - t:.3f} s")
        with _annotation("bench.tile", traced):
            t = time.perf_counter()
            prog = CSRMatrix(indptr=self.csr.indptr,
                             indices=self.csr.indices,
                             values=self.csr.values,
                             shape=(self.csr.m, self.csr.d))
            p = int(cfg["p"])
            skew = tile_k_skew(csr_k_per_tile(prog, p))
            picked = resolve_backend("auto", prog.density, k_skew=skew)
            builders = {"sparse": sparse_grid_from_csr,
                        "bucketed": bucketed_grid_from_csr}
            if picked.layout not in builders:
                raise ValueError(f"auto picked the {picked.layout} layout")
            self.data = builders[picked.layout](prog, self.csr.y, p,
                                                int(traffic["row_batches"]))
            tile = as_tile_data(self.data)
            jax.block_until_ready(tile)
            shapes = [tuple(a.shape) for a in tile.arrays]
            widths = getattr(self.data, "bucket_ks", None)
            log(f"tiled in {time.perf_counter() - t:.3f} s: skew {skew:.3f}, "
                f"backend {picked.name}, bucket widths {widths}, "
                f"payload shapes {shapes}")
        with _annotation("bench.check_setup", traced):
            self.gap = objective.HostGap(self.csr, cfg["loss"], cfg["lam"])
        self.obs = None
        if traced:
            from repro.obs import RunRecorder

            self.obs = RunRecorder(jax_annotations=True)
        self.solve_kw = dict(
            backend="auto", schedule=traffic["schedule"], p=p,
            row_batches=int(traffic["row_batches"]), eta0=traffic["eta0"],
            alpha0=cfg["alpha0"], eval_every=k, seed=int(seed) % (1 << 31),
            loss_name=cfg["loss"], reg_name=cfg["reg"], lam=cfg["lam"],
            m=cfg["m"], d=cfg["d"], obs=self.obs)
        # (solve, epoch, gap, w, alpha) of every check of the first solve
        # and of the check that ended each whole solve, for the comparison
        self.checks = []

    def _solve(self, epochs: int, hook) -> None:
        from repro.engine import solve

        solve(self.data, epochs=epochs, eval_hook=hook, **self.solve_kw)

    def warm(self) -> None:
        """One chunk and one gap check, at the window's shapes."""
        import jax

        def hook(t, w, alpha):
            return {"gap": self.gap(jax.block_until_ready(w), alpha)}

        with _annotation("bench.warm", self.traced):
            t = time.perf_counter()
            self._solve(int(self.traffic["eval_every"]), hook)
            self.log(f"warmed in {time.perf_counter() - t:.3f} s")

    def window(self, seconds: float) -> dict:
        import jax

        target = float(self.cfg["gap_target"])
        cap = int(self.traffic["epoch_cap"])
        clock = _Clock(self.traced)
        solves = []          # per solve: {"epochs", "solver_s", "end"}
        cur = {}

        def hook(t, w, alpha):
            jax.block_until_ready((w, alpha))
            cur["solver_s"] += clock.stop()
            cur["epochs"] = t
            with _annotation("bench.check", self.traced):
                w, alpha = np.asarray(w), np.asarray(alpha)
                g = self.gap(w, alpha)
            self.log(f"check solve {len(solves)} epoch {t} gap {g:.9g}")
            if g <= target or not solves:
                self.checks.append((len(solves), t, g, w, alpha))
            if g <= target:
                cur["end"] = "whole"
                raise _TargetMet
            if t >= cap:
                cur["end"] = "failed"
            elif clock.total >= seconds:
                cur["end"] = "cut"
                raise _WindowClosed
            clock.start()
            return {"epoch": t, "gap": g}

        while clock.total < seconds:
            cur = {"epochs": 0, "solver_s": 0.0, "end": None}
            clock.start()
            try:
                self._solve(cap, hook)
                cur["solver_s"] += clock.stop()   # returned at the cap
            except _TargetMet:
                pass
            except _WindowClosed:
                solves.append(cur)
                break
            solves.append(cur)
        whole = [s for s in solves if s["end"] == "whole"]
        failed = [s for s in solves if s["end"] == "failed"]
        epochs = sum(s["epochs"] for s in solves)
        return dict(
            solves=solves, whole=len(whole), failed=len(failed),
            attempted=len(whole) + len(failed), epochs=epochs,
            solver_s=clock.total, late_s=clock.total - seconds,
            whole_epochs=[s["epochs"] for s in whole],
            time_to_gap_s=(sum(s["solver_s"] for s in whole) / len(whole)
                           if whole else None),
            epoch_s=clock.total / epochs if epochs else None)

    def release(self) -> None:
        """Free the program's device state before the reference runs."""
        self.data = None
