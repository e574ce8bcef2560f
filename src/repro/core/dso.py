"""DSO — Distributed Stochastic Optimization of the saddle objective (Alg. 1).

API-compatibility surface over :mod:`repro.engine` (the layered
backend/schedule/driver implementation — see ``repro/engine/__init__.py``
for the architecture diagram).  Three execution modes, in increasing order
of hardware realism; all share the Eq.-(8) update math from
``engine.update``:

1. ``run_dso_serial``      — the paper-exact pointwise algorithm: one (i,j)
   nonzero per update, sequential ``lax.scan``. Ground truth for
   faithfulness (``engine.solve_serial``).
2. ``run_dso_grid``        — a single-device simulator of the p-processor
   block-cyclic schedule with *tile* (minibatch) updates: every
   anti-diagonal block of the p x p grid is updated simultaneously, exactly
   as the p devices would (``engine.solve``).  This is bit-identical to the
   ``shard_map`` version in ``dso_dist.py`` and is what the tests compare
   against.
3. ``dso_dist.run_dso_sharded`` — the real distributed version:
   ``shard_map`` over a ring mesh axis, ``lax.ppermute`` moving w-shards
   (the paper's bulk synchronization), one device per processor.

``impl`` selects a registered engine backend — the canonical names
(``engine.registered_backends()``) or the legacy selectors below; unknown
names raise ``ValueError``.
"""

from __future__ import annotations

from repro.core.saddle import Problem
from repro.engine.backends import (LEGACY_IMPLS,  # noqa: F401
                                   resolve_backend,
                                   resolve_backend_for_layout)
# re-exports: the legacy flat-module surface of the layered engine
from repro.engine.data import (DSOState, GridData, as_tile_data,  # noqa: F401
                               check_tile_stats, gather_alpha, gather_w,
                               init_state, init_state_data, make_grid_data,
                               tile_dims)
from repro.engine.data import eta_schedule as _eta_schedule  # noqa: F401
from repro.engine.data import prob_meta as _prob_meta  # noqa: F401
from repro.engine.driver import (SolveResult, run_epoch,  # noqa: F401
                                 run_epochs, solve, solve_serial)
from repro.engine.schedules import cyclic_perms
from repro.engine.update import (block_tile_step,  # noqa: F401
                                 sparse_tile_step)
from repro.engine.update import eq8_apply as _eq8_apply  # noqa: F401

#: run_dso_grid / ShardedDSO layout-and-kernel selectors: dense jnp tile
#: steps, dense fused Pallas kernel, sparse (block-ELL) gather tile steps,
#: the sparse one-hot Pallas kernel, and density-based automatic choice.
#: Canonical engine backend names are accepted everywhere too.
IMPLS = ("jnp", "pallas", "sparse", "sparse_pallas", "auto")


def resolve_impl(impl: str, density: float) -> tuple[str, str]:
    """(layout, kernel) for an ``impl`` selector.

    ``auto`` picks the sparse layout when the problem density is below
    ``sparse.format.SPARSE_DENSITY_THRESHOLD`` (the paper's datasets are
    well below it; dense synthetic ones are not), with the layout's jnp
    kernel: ``auto``'s kernel is chosen from the built grid
    (``engine.resolve_backend_for_layout``).  Unknown selectors raise
    ``ValueError`` naming the registered backends.
    """
    backend = resolve_backend(impl, density)
    return backend.layout, ("pallas" if "pallas" in backend.name else "jnp")


def run_dso_serial(prob: Problem, epochs: int = 10, eta0: float = 0.1,
                   seed: int = 0, use_adagrad: bool = True,
                   alpha0: float = 0.0, eval_every: int = 1):
    """Paper-exact Algorithm 1 with p=1 (sequential pointwise updates)."""
    res = solve_serial(prob, epochs=epochs, eta0=eta0, seed=seed,
                       use_adagrad=use_adagrad, alpha0=alpha0,
                       eval_every=eval_every)
    return res.w, res.alpha, res.history


def run_dso_grid(prob: Problem, p: int = 4, epochs: int = 10,
                 eta0: float = 0.1, use_adagrad: bool = True,
                 row_batches: int = 1, alpha0: float = 0.0,
                 eval_every: int = 1, impl: str = "jnp",
                 scan_epochs: bool = True, schedule: str = "cyclic"):
    """Single-device simulation of Algorithm 1 with p processors.

    ``impl`` selects layout and kernel (see ``IMPLS`` / the engine backend
    registry): dense ``"jnp"`` / ``"pallas"``, nnz-proportional
    ``"sparse"`` / ``"sparse_pallas"`` (block-ELL tiles + gather tile
    steps, same trajectory to float32 reduction order), or ``"auto"``
    picking the sparse layout below the density threshold.  ``schedule``
    is any registered engine schedule ("cyclic" is Algorithm 1).

    ``scan_epochs=True`` (default) runs each evaluation chunk of epochs as
    one donated ``lax.scan`` dispatch; ``False`` keeps the legacy
    one-dispatch-per-epoch loop (benchmark baseline). Identical math.
    Each distinct chunk length traces once, so when ``eval_every`` does not
    divide ``epochs`` the ragged final chunk costs one extra compile —
    prefer ``epochs % eval_every == 0`` for long runs (the driver warns).
    """
    res = solve(prob, backend=impl, schedule=schedule, p=p, epochs=epochs,
                eta0=eta0, use_adagrad=use_adagrad, row_batches=row_batches,
                alpha0=alpha0, eval_every=eval_every,
                scan_epochs=scan_epochs)
    return res.w, res.alpha, res.history


def run_dso_grid_from_data(data, *, loss_name: str, reg_name: str,
                           lam: float, m: int, d: int, epochs: int = 10,
                           eta0: float = 0.1, use_adagrad: bool = True,
                           row_batches: int = 1, alpha0: float = 0.0,
                           impl: str = "jnp", eval_every: int | None = None,
                           eval_hook=None):
    """Algorithm 1 on pre-built grid data — the out-of-core entry point.

    Takes dense ``GridData`` or sparse ``SparseGridData`` directly (e.g.
    from ``sparse.ingest.ingest_libsvm`` + ``sparse_grid_from_csr``), so no
    dense ``Problem`` — and no (m, d) dense matrix — ever exists.  ``m``/
    ``d`` are the real (unpadded) problem sizes; ``impl`` is the *kernel*
    ("jnp"/"pallas", or a canonical backend name matching the data's
    layout), the layout being fixed by the data's type.

    Returns (w, alpha) — or, when an ``eval_hook`` is supplied (e.g.
    ``engine.make_csr_primal_eval``: a jitted chunked CSR matvec, so the
    evaluation loop stays device-side and nnz-proportional),
    (w, alpha, history) with the hook called every ``eval_every`` epochs.
    """
    res = solve(data, backend=impl, schedule="cyclic", epochs=epochs,
                eta0=eta0, use_adagrad=use_adagrad, row_batches=row_batches,
                alpha0=alpha0,
                eval_every=epochs if eval_every is None else eval_every,
                eval_hook=eval_hook if eval_hook is not None else "auto",
                loss_name=loss_name, reg_name=reg_name, lam=lam, m=m, d=d)
    if eval_hook is not None:
        return res.w, res.alpha, res.history
    return res.w, res.alpha


# ------------------------------------------------------------------------
# legacy jitted-epoch shims (benchmarks/dso_perf.py times these directly)
# ------------------------------------------------------------------------


def _impl_kw(data, impl, kw):
    layout = as_tile_data(data).layout
    backend = resolve_backend_for_layout(impl, layout, tile_dims(data)[2])
    out = dict(kw)
    out["backend"] = backend.name
    return backend, out


def _grid_epoch(data, state, eta_t, lam, m, w_lo, w_hi, *, impl="jnp",
                **kw):
    """One epoch, one dispatch (legacy path; see ``_grid_epochs``)."""
    backend, kw = _impl_kw(data, impl, kw)
    perm = cyclic_perms(1, kw["p"])[0]
    return run_epoch(as_tile_data(data, bucketed_payload=backend.payload),
                     state, perm, eta_t, lam, m, w_lo, w_hi, **kw)


def _grid_epochs(data, state, etas, lam, m, w_lo, w_hi, *, impl="jnp",
                 **kw):
    """``len(etas)`` cyclic epochs in ONE donated-scan dispatch."""
    backend, kw = _impl_kw(data, impl, kw)
    perms = cyclic_perms(etas.shape[0], kw["p"])
    return run_epochs(as_tile_data(data, bucketed_payload=backend.payload),
                      state, perms, etas, lam, m, w_lo, w_hi, **kw)
