"""The benchmark's data generator: sparse classification data in CSR form.

The power-law model of the repository's smoke run, drawn in row chunks on
a few threads.  The column of popularity rank j is drawn with probability
~ (j+1)^-alpha and no column repeats within a row: the first
``nnz_per_row`` distinct columns of a with-replacement stream (successive
sampling without replacement).  Values are normal, rows scaled to unit
norm; labels are the signs of a planted linear model plus noise.

A column's id is independent of its popularity: ranks map to ids through
a permutation drawn from the seed, so popular columns are spread over the
id range (and so over the column blocks of the p x p grid) as they would
be in a data set whose feature ids do not follow frequency.

Every chunk of rows has its own random stream spawned from the seed, so
the data depends on the seed alone, not on the number of threads.

A cell draws one data set from its configuration's ``data_seed`` and
gives each run's ``--seed`` the same rows in another order within each
row shard (``permute_rows``): every seed does the same work, laid out
differently.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple

import numpy as np

#: rows per independently seeded chunk (part of the data's definition:
#: changing it changes the data drawn for a seed)
CHUNK_ROWS = 2048


class CSR(NamedTuple):
    """Host CSR matrix plus labels, in plain numpy."""

    indptr: np.ndarray   # (m + 1,) int64
    indices: np.ndarray  # (nnz,) int32, ascending within each row
    values: np.ndarray   # (nnz,) float32
    y: np.ndarray        # (m,) float32, +-1
    m: int
    d: int

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])

    def row_ids(self) -> np.ndarray:
        return np.repeat(np.arange(self.m, dtype=np.int64),
                         np.diff(self.indptr))


def seed_sequence(seed: int) -> np.random.SeedSequence:
    """Any whole number, negative or above 64 bits included, as entropy."""
    return np.random.SeedSequence(int(seed) % (1 << 128))


def _distinct_columns(rng, n: int, k: int, d: int, cdf) -> np.ndarray:
    """(n, k) sorted distinct columns per row, successive sampling."""
    cols = np.empty((n, k), np.int32)
    todo = np.arange(n)
    draws = 4 * k
    while todo.size:
        t = todo.size
        c = np.minimum(np.searchsorted(cdf, rng.random((t, draws)),
                                       side="right"), d - 1)
        key = (np.arange(t, dtype=np.int64)[:, None] * d + c).ravel()
        uniq, first = np.unique(key, return_index=True)
        uk = uniq[np.argsort(first, kind="stable")]   # draw order, row-major
        row = uk // d
        start = np.searchsorted(row, np.arange(t))
        keep = np.arange(row.size) - start[row] < k
        done = np.bincount(row, minlength=t) >= k
        sel = keep & done[row]
        cols[todo[done]] = np.sort((uk[sel] % d).reshape(-1, k), axis=1)
        todo = todo[~done]
        draws *= 2
    return cols


def powerlaw_csr(m: int, d: int, nnz_per_row: int, alpha: float, seed: int,
                 *, threads: int | None = None) -> CSR:
    """Draw the (m, d) matrix with ``nnz_per_row`` nonzeros in every row."""
    k = nnz_per_row
    if not 0 < k <= d:
        raise ValueError(f"nnz_per_row must be in 1..{d}, got {k}")
    cdf = np.cumsum(np.arange(1, d + 1, dtype=np.float64) ** -alpha)
    cdf /= cdf[-1]
    n_chunks = -(-m // CHUNK_ROWS)
    root = seed_sequence(seed)
    w_seq, id_seq, *chunk_seqs = root.spawn(n_chunks + 2)
    w_star = np.random.default_rng(w_seq).normal(0.0, 1.0, d) \
        .astype(np.float32)
    col_id = np.random.default_rng(id_seq).permutation(d).astype(np.int32)
    cols = np.empty((m, k), np.int32)
    vals = np.empty((m, k), np.float32)
    y = np.empty(m, np.float32)

    def chunk(i: int) -> None:
        rng = np.random.default_rng(chunk_seqs[i])
        r0, r1 = i * CHUNK_ROWS, min((i + 1) * CHUNK_ROWS, m)
        c = np.sort(col_id[_distinct_columns(rng, r1 - r0, k, d, cdf)],
                    axis=1)
        v = rng.normal(0.0, 1.0, (r1 - r0, k)).astype(np.float32)
        v /= np.maximum(np.linalg.norm(v, axis=1, keepdims=True), 1e-8)
        margin = (v * w_star[c]).sum(axis=1, dtype=np.float64) \
            + 0.1 * rng.normal(0.0, 1.0, r1 - r0)
        cols[r0:r1], vals[r0:r1] = c, v
        y[r0:r1] = np.where(margin >= 0, 1.0, -1.0)

    workers = threads or min(8, os.cpu_count() or 1)
    with ThreadPoolExecutor(max_workers=workers) as pool:
        list(pool.map(chunk, range(n_chunks)))
    return CSR(indptr=np.arange(m + 1, dtype=np.int64) * k,
               indices=cols.ravel(), values=vals.ravel(), y=y, m=m, d=d)


def permute_rows(csr: CSR, seed: int, p: int) -> CSR:
    """The same rows, in an order drawn from ``seed``, each kept in its
    shard: rows move only among the ``ceil(m / p)`` of their row block, so
    every worker of the p x p grid holds the same rows, and every tile the
    same nonzeros, whatever the seed.  The solver's arithmetic is then the
    same up to the order of sums, and so is the work to the gap target;
    the seed changes the layout (rows of ``powerlaw_csr`` all hold the same
    number of nonzeros)."""
    k = csr.nnz // csr.m
    mb = -(-csr.m // p)
    rng = np.random.default_rng(seed_sequence(seed))
    perm = np.concatenate([lo + rng.permutation(min(mb, csr.m - lo))
                           for lo in range(0, csr.m, mb)])
    return csr._replace(indices=csr.indices.reshape(-1, k)[perm].ravel(),
                        values=csr.values.reshape(-1, k)[perm].ravel(),
                        y=csr.y[perm])
