"""Schedule layer: per-inner-iteration block permutations.

Algorithm 1's convergence proof only needs an *equivalent serial sequence
of updates* (Lemma 2), which holds for ANY schedule that assigns, at each
inner iteration, a permutation of blocks to processors (no shared row or
column).  A schedule therefore reduces to a ``(n_epochs, p, p)`` int32
array ``perms`` with ``perms[e, r, q]`` = the block processor q owns at
inner iteration r of epoch e — each ``perms[e, r]`` a permutation of
0..p-1.  The epoch driver consumes that array; the schedule only *draws*
it, chunk by chunk, threading a PRNG key:

  cyclic  — Algorithm 1's sigma_r(q) = (q + r) mod p; deterministic, and
            ``ring=True``: the owner map advances by one ring step per
            inner iteration, so the sharded driver can move w with a
            ``ppermute`` (the paper's communication pattern).
  random  — a uniformly random permutation per inner iteration, the
            NOMAD-style execution of ``§6`` (previously ``dso_async.py``);
            a general shuffle, so the sharded driver falls back to
            all-gather + select.
  lpt     — load-balanced: a greedy LPT (longest-processing-time-first)
            Latin square over the per-tile nnz costs, co-scheduling the
            heavy tiles of different workers in the same inner iteration
            so the per-iteration straggler max (what every bulk-sync
            inner iteration waits on) tracks the MEAN tile cost instead
            of each round inheriting one worst tile.  ``balanced=True``:
            the drivers pass ``tile_nnz`` (the (p, p) per-tile nonzero
            counts, ``tile_row_nnz_g.sum(-1)``) into ``draw``.  A general
            permutation, so the sharded driver uses the all-gather path.
  fixed   — any explicit ``perms`` array (property tests, replaying a
            recorded NOMAD trace).

Resume contract (the elastic runtime, ``repro.runtime``, relies on this):
``draw(key, t0, n, p)`` must be CHUNK-INVARIANT — drawing n1 epochs and
then n2 more while threading the returned key must produce the same
``(n1 + n2, p, p)`` permutation stream as one draw of n1 + n2.  Cyclic and
lpt are pure functions of (t0, p, costs); random splits its key exactly
once per epoch (never per chunk), so the stream depends only on the key at
the epoch boundary.  A snapshot therefore only needs ``(key, t0)`` to
resume the schedule bit-identically; a schedule that violates this (e.g.
one drawing from chunk-shaped batched keys) would silently break
deterministic resume — keep the per-epoch key discipline when adding new
schedules.  (Replaying a "fixed" schedule across a resume needs the
caller to pass the same ``fixed_schedule(perms)`` object again: the
snapshot config records only the name.)

Overlap invariant (the pipelined sharded driver, ``core.dso_dist`` with
``overlap=True``, relies on this alongside the resume contract): the
block CONSUMED by processor q at inner iteration r of epoch e is always
``perms[e, r, q]`` — prefetch depth never changes WHAT is computed, only
when the block's statistics are staged.  The double-buffered cyclic
epoch stages block sigma(q, r+1) while the fused (w, gw) ppermute for
step r is in flight, threading the staged slot across epoch and chunk
boundaries (the last iteration of epoch e prefetches epoch e+1's first
block, sigma(q, p) = q); the p2p transport likewise fetches along the
inverse permutation before consuming ``perms[e, r, q]``.  Trajectories
are therefore bit-identical to the serial-shift driver under ANY
schedule drawn here — a schedule change affects the pipeline only
through the permutation stream itself.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Schedule(NamedTuple):
    name: str
    #: (key, t0, n, p) -> (key', perms (n, p, p)); t0 = epochs already run.
    #: Balanced schedules additionally take the keyword ``tile_nnz``.
    draw: Callable
    #: True when consecutive owner maps differ by one ring step (cyclic),
    #: letting the sharded driver use ppermute instead of all-gather
    ring: bool
    #: True when ``draw`` needs the per-tile nnz costs: the drivers pass
    #: ``tile_nnz=data.tile_row_nnz_g.sum(-1)`` (host numpy, (p, p))
    balanced: bool = False


@functools.lru_cache(maxsize=64)
def cyclic_perms(n: int, p: int):
    """(n, p, p) cyclic schedule: perms[e, r, q] = (q + r) mod p.

    Cached: the array is deterministic in (n, p) and the cyclic schedule
    asks for it every chunk — the cache keeps the chunk loop free of
    repeated device dispatches.
    """
    r = jnp.arange(p, dtype=jnp.int32)
    perm = (r[:, None] + r[None, :]) % p
    return jnp.broadcast_to(perm, (n, p, p))


def _draw_cyclic(key, t0, n, p):
    return key, cyclic_perms(n, p)


def _draw_random(key, t0, n, p):
    # one vmapped draw for the chunk's (n, p) schedule keys — the SAME RNG
    # stream as the legacy dso_async per-epoch permutation() calls, without
    # n*p dispatches
    chunk_keys = []
    for _ in range(n):
        key, sk = jax.random.split(key)
        chunk_keys.append(jax.random.split(sk, p))
    perms = jax.vmap(jax.vmap(
        lambda k: jax.random.permutation(k, p)))(jnp.stack(chunk_keys))
    return key, perms


def fixed_schedule(perms, name: str = "fixed") -> Schedule:
    """Schedule replaying an explicit ``(n_epochs, p, p)`` (or single-epoch
    ``(p, p)``) permutation array — epoch t draws ``perms[t]``."""
    perms = jnp.asarray(perms)
    if perms.ndim == 2:
        perms = perms[None]

    def draw(key, t0, n, p):
        if t0 + n > perms.shape[0]:
            raise ValueError(
                f"fixed schedule has {perms.shape[0]} epochs of "
                f"permutations, epochs {t0}..{t0 + n} requested")
        if perms.shape[1:] != (p, p):
            raise ValueError(f"fixed schedule is for p={perms.shape[1]}, "
                             f"grid has p={p}")
        return key, perms[t0:t0 + n]

    return Schedule(name, draw, ring=False)


# ------------------------------------------------------ load balancing --


def lpt_latin_square(tile_nnz) -> np.ndarray:
    """Greedy LPT Latin square over the (p, p) per-tile costs.

    Round by round (inner iteration r), workers are served in descending
    order of their heaviest *remaining* tile and each takes its costliest
    block still free this round — so the expensive tiles of different
    workers land in the SAME inner iteration instead of each round
    inheriting one straggler.  Conflicts are repaired with augmenting
    paths (Kuhn): after r rounds the remaining worker-block graph is
    (p - r)-regular bipartite, so a perfect matching always exists and
    every round is a valid permutation (no two workers share a block,
    Lemma 2's only requirement).  Returns ``perms (p, p)`` with
    ``perms[r, q]`` = block worker q owns at inner iteration r; each
    worker sees every block exactly once per epoch, like cyclic.
    """
    cost = np.asarray(tile_nnz, np.float64)
    p = cost.shape[0]
    if cost.shape != (p, p):
        raise ValueError(f"tile_nnz must be (p, p), got {cost.shape}")
    remaining = [set(range(p)) for _ in range(p)]
    perms = np.empty((p, p), np.int32)
    for r in range(p):
        assign: dict[int, int] = {}       # block -> worker

        def try_assign(q, visited):
            for b in sorted(remaining[q], key=lambda b: (-cost[q, b], b)):
                if b in visited:
                    continue
                visited.add(b)
                if b not in assign or try_assign(assign[b], visited):
                    assign[b] = q
                    return True
            return False

        order = sorted(range(p),
                       key=lambda q: (-max(cost[q, b]
                                           for b in remaining[q]), q))
        for q in order:
            matched = try_assign(q, set())
            assert matched, "regular bipartite graph must match (Hall)"
        for b, q in assign.items():
            perms[r, q] = b
            remaining[q].remove(b)
    return perms


def _draw_lpt(key, t0, n, p, *, tile_nnz=None):
    if tile_nnz is None:
        raise ValueError(
            "schedule 'lpt' needs the per-tile nnz costs: pass "
            "tile_nnz=data.tile_row_nnz_g.sum(-1) to draw() (the engine "
            "drivers do this automatically for balanced schedules)")
    sq = jnp.asarray(lpt_latin_square(tile_nnz))
    return key, jnp.broadcast_to(sq[None], (n, p, p))


SCHEDULES = {
    "cyclic": Schedule("cyclic", _draw_cyclic, ring=True),
    "random": Schedule("random", _draw_random, ring=False),
    "lpt": Schedule("lpt", _draw_lpt, ring=False, balanced=True),
}


def get_schedule(schedule) -> Schedule:
    """Name or ``Schedule`` instance -> ``Schedule`` (ValueError on unknown)."""
    if isinstance(schedule, Schedule):
        return schedule
    try:
        return SCHEDULES[schedule]
    except KeyError:
        raise ValueError(
            f"unknown schedule {schedule!r}: registered schedules are "
            f"{sorted(SCHEDULES)} (or pass a Schedule, e.g. "
            f"fixed_schedule(perms))") from None
