r"""Unified DSO engine: pluggable tile backends, a schedule layer, and one
epoch driver behind the serial / grid / sharded / async execution modes.

The paper's convergence argument (Lemma 2) only needs an *equivalent
serial sequence of updates*: the same Eq.-(8) saddle-point block update,
driven by any per-inner-iteration block permutation, on any data layout.
The engine expresses that once, as three orthogonal layers, turning the
old {dense,sparse} x {jnp,pallas} x {cyclic,random} x {grid,sharded}
code-path *product* into a *sum*:

      Problem ----------------------+        libsvm file
        | make_grid_data /          |          | sparse.ingest (2-pass,
        | make_sparse_grid_data /   |          |  pass 1 records k_per_tile)
        | make_bucketed_grid_data   |          v
        v                           |        CSRMatrix -- sparse_grid_from_csr
   GridData | SparseGridData <------+------------+    \- bucketed_grid_from_csr
            | BucketedGridData (K-bucketed ragged tiles: <= MAX_K_BUCKETS
        |     pow2 widths packed into ONE flat buffer of K_CHUNK-wide
        |     column chunks + an int32 chunk_lut/chunk_cnt table mapping
        |     tile (q, b) -> its chunk list; impl="auto" picks it when
        |     tile_k_skew >= BUCKET_SKEW_THRESHOLD in the sparse regime)
        |  as_tile_data
        v
   TileData  (the common pytree: arrays=(Xg,) | (cols_g, vals_g) |
        |     flat (cols_fl, vals_fl, chunk_lut, chunk_cnt) — labels,
        |     nnz stats, padding masks)
        |
   +----+------------------- ENGINE ---------------------------------+
   |                                                                 |
   |  backends.py — TileBackend registry      schedules.py           |
   |    dense_jnp              \                cyclic  (sigma_r,    |
   |    dense_pallas_fused      \                       ring=True)   |
   |    dense_pallas_block       \              random  (NOMAD-ish)  |
   |    sparse_jnp                > block_step  lpt     (greedy LPT  |
   |    sparse_pallas            /                      Latin square |
   |    sparse_bucketed_jnp     /                       over per-tile|
   |    sparse_bucketed_pallas /                        nnz costs;   |
   |      (ONE kernel: scalar- |                        balanced=True|
   |       prefetched index    |                        -> draw gets |
   |       map walks chunk_lut)|                        tile_nnz)    |
   |         |                 |                fixed(perms)         |
   |         |                 |                  |  draw(key,t0,n,p |
   |         |                 |                  |       [,tile_nnz])
   |         v                                    v                  |
   |    inner_iteration(backend, ...)  <---  perms (n_epochs, p, p)  |
   |         |     (driver.py: the ONE Eq.-8 inner iteration)        |
   |         v                                                       |
   |    epoch_body --> run_epochs: jitted lax.scan over epochs,      |
   |                   DSOState DONATED (in-place epoch state)       |
   +--------+-----------------------+----------------------+--------+
            |                       |                       |
        solve()                solve_serial()          ShardedDSO
     (grid simulator,        (paper-exact p=1         (shard_map ring;
      cyclic/random/fixed     pointwise reference)     double-buffered
      schedules, out-of-core                           pipelined cyclic:
      grids, eval hooks)                               stage_block prefetch
            |                       |                  + ONE fused (w, gw)
            |                       |                  ppermute per step;
            |                       |                  static ppermute-pair
            |                       |                  p2p routes for
            |                       |                  general perms,
            |                       |                  all-gather fallback)
            +-----------+-----------+-----------+------+
                        v
                  SolveResult(w, alpha, history, state)
                        ^
                        |  evaluation hooks (evaluate.py):
                        |  problem_eval_hook (dense objectives) |
                        |  pd_gap_eval_hook (P(w) - D(alpha), the
                        |  paper's convergence certificate) |
                        |  make_csr_primal_eval (jitted chunked
                        |  CSR matvec — out-of-core, no host numpy)

   +------------------ OBSERVABILITY (repro/obs) ----------------------+
   |  solve(..., obs=rec) / solve_serial(..., obs=rec): duck-typed     |
   |  RunRecorder — per-chunk epoch_chunk spans (synced with           |
   |  block_until_ready so they time completed epochs), rows/s, nnz/s, |
   |  packed-bytes/s and eta gauges, eval.* gauges from every history  |
   |  entry, snapshot_save / restore / eval spans; obs=None (default)  |
   |  adds NO calls and NO allocations to the chunk loop and keeps     |
   |  trajectories bit-identical (the metrics-off contract, pinned by  |
   |  tests/test_obs.py)                                               |
   +-------------------------------------------------------------------+

   +--------------------- RUNTIME (repro/runtime) ---------------------+
   |  elastic execution around the engine (see runtime/__init__.py     |
   |  for the full data flow):                                         |
   |                                                                   |
   |  solve(..., checkpoint_every=k, store=S,   ShardedDSO             |
   |        health=guard)                         .solver_state()      |
   |    every k epochs the COMPLETE solver        .snapshot_config()   |
   |    state (w, alpha, gw/ga, RNG key,          .restore()  .wait()  |
   |    cursor, history, config) crosses the                           |
   |    seam as one DSOSnapshot; the health                            |
   |    guard gates every chunk boundary                               |
   |       |                                                           |
   |  snapshot.py (flat-npz codec + per-leaf CRC32 / file digest +     |
   |       |       SnapshotStore: latest-VALID-wins, quarantine of     |
   |       |       corrupt files, keep_last/keep_every retention GC;   |
   |       |       async_writes=True: save() fetches to host and       |
   |       |       returns, the npz + atomic rename drain on a writer  |
   |       |       thread; flush() is the durability barrier and all   |
   |       |       read paths barrier automatically;                   |
   |       |       the one codec — training/checkpoint.py delegates)   |
   |       +-> health.py     all_finite probe + objective-regression   |
   |       |                 monitor -> HealthGuard rollback-with-eta  |
   |       |                 -backoff (solve(health=)); WallClock      |
   |       |                 straggler EWMA; typed LedgerEvent ledger  |
   |       +-> resume.py     solve(..., init=snap): bit-identical      |
   |       |                 (schedules.draw chunk-invariance)         |
   |       +-> reshard.py    p -> p' live resharding: direct tile->    |
   |       |                 tile re-blocking when p/p' divide evenly  |
   |       |                 (regrid_direct — no CSR round-trip),      |
   |       |                 grid_to_csr + the tilers otherwise;       |
   |       |                 reshard_state repartitions                |
   |       +-> supervisor.py crash/nan/corrupt/straggler fault plans   |
   |                         over ShardedDSO, auto-resume from store,  |
   |                         wall-clock replanning (lpt -> reshard),   |
   |                         returns the recovery ledger               |
   +-------------------------------------------------------------------+

Legacy entry points (``core.dso.run_dso_serial`` / ``run_dso_grid`` /
``run_dso_grid_from_data``, ``core.dso_async.run_dso_random``,
``core.dso_dist.ShardedDSO``) are thin wrappers over these layers and
keep their exact trajectories.  New schedules register in
``schedules.SCHEDULES``; new layouts/kernels register a ``TileBackend``
— nothing else changes.  The runtime layer holds NO solver math: it
persists exactly what the epoch driver threads between chunks, which is
why resume promises 0.0 drift.
"""

from repro.engine.backends import (LEGACY_IMPLS, TileBackend, get_backend,
                                   register_backend, registered_backends,
                                   resolve_backend,
                                   resolve_backend_for_layout)
from repro.engine.data import (DSOState, GridData, TileData, as_tile_data,
                               check_tile_stats, eta_schedule, gather_alpha,
                               gather_w, init_state, init_state_data,
                               make_grid_data, prob_meta, tile_dims)
from repro.engine.driver import (SolveResult, inner_iteration, run_epochs,
                                 solve, solve_serial, stage_block,
                                 staged_step, warn_ragged_eval)
from repro.engine.evaluate import (make_csr_primal_eval, pd_gap_eval_hook,
                                   problem_eval_hook)
from repro.engine.schedules import (SCHEDULES, Schedule, cyclic_perms,
                                    fixed_schedule, get_schedule,
                                    lpt_latin_square)
from repro.engine.update import block_tile_step, eq8_apply, sparse_tile_step

__all__ = [
    "LEGACY_IMPLS", "TileBackend", "get_backend", "register_backend",
    "registered_backends", "resolve_backend", "resolve_backend_for_layout",
    "DSOState", "GridData", "TileData", "as_tile_data", "check_tile_stats",
    "eta_schedule", "gather_alpha", "gather_w", "init_state",
    "init_state_data", "make_grid_data", "prob_meta", "tile_dims",
    "SolveResult", "inner_iteration", "run_epochs", "solve",
    "solve_serial", "stage_block", "staged_step", "warn_ragged_eval",
    "make_csr_primal_eval",
    "pd_gap_eval_hook", "problem_eval_hook",
    "SCHEDULES", "Schedule", "cyclic_perms",
    "fixed_schedule", "get_schedule", "lpt_latin_square",
    "block_tile_step", "eq8_apply", "sparse_tile_step",
]
