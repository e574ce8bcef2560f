"""Block-sparse data layouts for DSO: CSR + padded block-ELL grid tiles.

The paper's entire value proposition is stochastic saddle-point optimization
over *sparse* data (Table 2's datasets are well under 1% dense), and DSO's
per-epoch cost is proportional to |Omega| = nnz.  The dense ``GridData``
layout streams 4*mb*db bytes of X per tile step regardless of density; the
formats here keep both resident memory and per-step HBM traffic
nnz-proportional:

``CSRMatrix``
    Plain compressed-sparse-rows in numpy (indptr/indices/values), the
    interchange format produced by the streaming libsvm ingester
    (``repro.sparse.ingest``).  Column indices are ascending within each
    row, which makes the grid tiler below a pure vectorized pass and keeps
    sparse accumulation order identical to the dense matmul's (zeros add
    exactly, so the dense row dot product visits the same nonzeros in the
    same order).

``SparseTile``
    One (rows, db) grid tile packed as ELL: ``cols``/``vals`` of shape
    (rows, K) with per-tile K >= max row nnz.  Padding slots carry
    ``val = 0`` and ``col = 0`` so gathers contribute exactly zero and
    scatter-adds are no-ops.  K is padded up to the sublane multiple (8) by
    default — on TPU the lane (128) dimension is supplied by the row axis,
    so tiles stay nnz-proportional instead of ballooning to a 128-wide K;
    ``choose_k(..., pow2=True)`` gives power-of-two K for allocators that
    want it.

``SparseGridData``
    The p x p DSO grid in block-ELL: ``cols_g``/``vals_g`` of shape
    (p, p, mb, K) where ``[q, b]`` is processor q's tile of w-block b with
    *block-local* column indices (gathers index the travelling w block
    directly).  K is the max over tiles (uniform so the epoch vmaps over
    processors); the per-tile K values are kept in ``k_per_tile`` for
    inspection and the traffic model.  All scaling statistics (row_nnz,
    col_nnz, per-tile counts) match ``core.dso.make_grid_data`` exactly,
    so the sparse trajectory equals the dense one.

``BucketedGridData``
    The K-bucketed *ragged* grid: the p x p tiles are grouped into at most
    ``MAX_K_BUCKETS`` power-of-two packed widths chosen from the per-tile
    ``k_per_tile`` statistics, and each bucket is packed rectangularly as
    (p, slots, mb, K_bucket) so vmap/shard_map stay rectangular *per
    bucket*.  On power-law feature distributions (webspam/kdda-like: a few
    tiles 10-50x denser than the median) the uniform layout pays the worst
    tile's K everywhere — ``p^2 * mb * max-K`` resident and ``mb * max-K``
    streamed per tile step; the bucketed layout pays ``sum tiles *
    bucket-K``, tracking real nnz instead of max-K padding.  ``bucket_id``
    / ``bucket_pos`` (p, p) map tile (q, b) to its (bucket, slot) address;
    the shared scaling statistics are identical to the uniform layouts', so
    the bucketed trajectory equals the ``sparse_jnp`` one.  What actually
    lives on the device is the *flat chunk view* — every tile re-expressed
    as consecutive (mb, K_CHUNK) chunks of ONE ragged buffer plus a per-tile
    chunk offset table — which is what the one-kernel scalar-prefetch
    Pallas backend streams (``kernels/dso_sparse.py``).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def pad_to_multiple(n: int, p: int) -> int:
    # core.schedule.pad_to_multiple, duplicated one-liner: importing any
    # repro.core module here would close an import cycle (core.dso imports
    # this module for the SparseGridData dispatch)
    return ((n + p - 1) // p) * p

SUBLANE = 8    # float32 sublane multiple (second-to-last dim on TPU)
LANE = 128     # lane multiple (last dim on TPU)

#: below this nnz/(m*d) density the sparse layout wins (ELL padding + index
#: traffic overhead break even around 1/2 density; 0.1 leaves headroom for
#: row-nnz skew inflating K)
SPARSE_DENSITY_THRESHOLD = 0.1

#: widest block (columns) for which ``impl="auto"`` runs the uniform sparse
#: layout's one-hot Pallas kernel on a TPU: its MXU work per slot grows with
#: ceil(db / 128) and XLA's gather does not.  Provisional (128 lane rows):
#: the only width measured on a chip so far is db = 5,240 (PERF.md).
ONEHOT_MAX_DB = 16_384

#: above this per-tile-K skew (k_raw.max() / median) the uniform max-K
#: block-ELL grid wastes most of its padding on the few dense tiles and the
#: K-bucketed ragged layout wins — the ``impl="auto"`` bucketing trigger
BUCKET_SKEW_THRESHOLD = 4.0

#: rectangular K-buckets per grid: enough to track a power-law tail while
#: keeping the per-bucket vmap/shard_map arrays few and large
MAX_K_BUCKETS = 4


def choose_k(max_row_nnz: int, *, align: int = SUBLANE,
             pow2: bool = False) -> int:
    """Packed width K for a tile whose densest row has ``max_row_nnz``.

    Rounded up to ``align`` (sublane multiple by default — the lane-aligned
    128 dimension is the row axis, so K stays nnz-proportional); ``pow2``
    additionally rounds to the next power of two.
    """
    k = max(int(max_row_nnz), 1)
    k = -(-k // align) * align
    if pow2:
        k = 1 << (k - 1).bit_length()
    return k


class CSRMatrix(NamedTuple):
    """Compressed sparse rows (numpy, host-side interchange format)."""

    indptr: np.ndarray   # (m + 1,) int64
    indices: np.ndarray  # (nnz,) int32, ascending within each row
    values: np.ndarray   # (nnz,) float32
    shape: tuple[int, int]

    @property
    def m(self) -> int:
        return self.shape[0]

    @property
    def d(self) -> int:
        return self.shape[1]

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])

    @property
    def density(self) -> float:
        return self.nnz / float(max(1, self.m * self.d))

    def row_ids(self) -> np.ndarray:
        """(nnz,) row index of every stored entry."""
        return np.repeat(np.arange(self.m, dtype=np.int64),
                         np.diff(self.indptr))

    def row_nnz(self) -> np.ndarray:
        return np.diff(self.indptr).astype(np.float32)

    def col_nnz(self) -> np.ndarray:
        return np.bincount(self.indices, minlength=self.d) \
            .astype(np.float32)

    def matvec(self, w) -> np.ndarray:
        """X @ w without densifying."""
        w = np.asarray(w)
        contrib = self.values * w[self.indices]
        return np.bincount(self.row_ids(), weights=contrib,
                           minlength=self.m).astype(np.float32)

    def rmatvec(self, a) -> np.ndarray:
        """X.T @ a without densifying."""
        a = np.asarray(a)
        contrib = self.values * a[self.row_ids()]
        return np.bincount(self.indices, weights=contrib,
                           minlength=self.d).astype(np.float32)

    def toarray(self) -> np.ndarray:
        """Densify — tests/debugging only, defeats the whole point."""
        X = np.zeros(self.shape, np.float32)
        X[self.row_ids(), self.indices] = self.values
        return X

    @classmethod
    def from_dense(cls, X) -> "CSRMatrix":
        X = np.asarray(X)
        ii, jj = np.nonzero(X)
        indptr = np.zeros(X.shape[0] + 1, np.int64)
        np.cumsum(np.bincount(ii, minlength=X.shape[0]), out=indptr[1:])
        return cls(indptr=indptr, indices=jj.astype(np.int32),
                   values=X[ii, jj].astype(np.float32), shape=X.shape)

    @classmethod
    def from_shards(cls, shards, d: int) -> "CSRMatrix":
        """Concatenate row-shard CSRMatrices (all with ``d`` columns)."""
        indptr = [np.zeros(1, np.int64)]
        for s in shards:
            assert s.d == d, (s.d, d)
            indptr.append(s.indptr[1:] + indptr[-1][-1])
        m = sum(len(p) for p in indptr[1:])  # one entry per shard row
        return cls(indptr=np.concatenate(indptr),
                   indices=np.concatenate([s.indices for s in shards]),
                   values=np.concatenate([s.values for s in shards]),
                   shape=(m, d))


class SparseTile(NamedTuple):
    """One (rows, db) grid tile in padded ELL form."""

    cols: Array     # (rows, K) int32 tile-local column indices, 0 in pads
    vals: Array     # (rows, K) float32, 0.0 in pads
    row_nnz: Array  # (rows,) float32 — nnz per row *within this tile*
    db: int         # tile width (gather target size)

    @property
    def K(self) -> int:
        return self.cols.shape[1]

    def toarray(self) -> np.ndarray:
        dense = np.zeros((self.cols.shape[0], self.db), np.float32)
        cols = np.asarray(self.cols)
        vals = np.asarray(self.vals)
        rows = np.arange(cols.shape[0])[:, None]
        # pads carry val 0 at col 0 — scatter of 0 is a no-op even when a
        # real entry lives at column 0
        np.add.at(dense, (np.broadcast_to(rows, cols.shape), cols), vals)
        return dense

    @classmethod
    def from_dense(cls, X_tile, *, k_align: int = SUBLANE,
                   pow2: bool = False) -> "SparseTile":
        X_tile = np.asarray(X_tile)
        rows, db = X_tile.shape
        ii, jj = np.nonzero(X_tile)
        rn = np.bincount(ii, minlength=rows)
        K = choose_k(rn.max() if rows else 0, align=k_align, pow2=pow2)
        cols = np.zeros((rows, K), np.int32)
        vals = np.zeros((rows, K), np.float32)
        starts = np.zeros(rows + 1, np.int64)
        np.cumsum(rn, out=starts[1:])
        pos = np.arange(len(ii)) - starts[ii]
        cols[ii, pos] = jj
        vals[ii, pos] = X_tile[ii, jj]
        return cls(cols=jnp.asarray(cols), vals=jnp.asarray(vals),
                   row_nnz=jnp.asarray(rn.astype(np.float32)), db=db)


class SparseGridData(NamedTuple):
    """Problem data on the p x p DSO grid in block-ELL form.

    Mirrors ``core.dso.GridData`` field-for-field except that the dense
    ``Xg`` row shards are replaced by packed ``cols_g``/``vals_g`` tiles
    with block-local column indices.  The scaling statistics are identical
    to ``make_grid_data``'s, so the sparse trajectory matches the dense one
    to float32 reduction-order noise.
    """

    cols_g: Array    # (p, p, mb, K) int32 — [q, b]: proc q's tile of blk b
    vals_g: Array    # (p, p, mb, K) float32
    yg: Array        # (p, mb)
    row_nnz_g: Array  # (p, mb)   |Omega_i|, >= 1
    col_nnz: Array   # (d_pad,)   |Omega-bar_j|, >= 1
    row_valid: Array  # (p, mb)  1.0 for real rows, 0.0 padding
    p: int
    mb: int          # rows per processor
    db: int          # cols per block
    K: int           # uniform packed width (max over tiles)
    # [q, s, j]: nnz of column j within row batch s of processor q's shard
    tile_col_nnz_g: Array = None   # (p, row_batches, d_pad)
    # [q, b, i]: nnz of row i of processor q within block b's columns
    tile_row_nnz_g: Array = None   # (p, p, mb)
    # per-tile packed widths before uniform padding (host-side, stats only)
    k_per_tile: np.ndarray = None  # (p, p) int


#: flat-chunk granularity of the bucketed layout's packed view: every
#: bucket width is a multiple of the sublane, so a tile of width K_k is
#: exactly ``K_k // K_CHUNK`` consecutive (mb, K_CHUNK) chunks
K_CHUNK = SUBLANE


class BucketedGridData(NamedTuple):
    """The p x p DSO grid in K-bucketed ragged block-ELL form.

    Tiles are grouped into ``len(bucket_ks)`` packed widths; bucket k's
    ``cols_b[k]``/``vals_b[k]`` stack every processor's tiles of that width
    as (p, slots_k, mb, K_k) — rectangular per bucket.  Tile (q, b) lives
    at ``[q, bucket_pos[q, b]]`` of bucket ``bucket_id[q, b]``; unused
    trailing slots (processors with fewer tiles of that width) are
    all-padding tiles that no schedule ever addresses.  All scaling
    statistics match the uniform layouts' exactly.

    The per-bucket rectangles are HOST-side numpy (inspection,
    ``SparseTile`` access, ``grid_to_csr`` and the ref oracle).  What lives on DEVICE is the *flat chunk view*: every
    bucket width is a multiple of ``K_CHUNK``, so each tile is
    ``K_k // K_CHUNK`` consecutive (mb, K_CHUNK) chunks and the whole grid
    packs into ONE ragged buffer ``cols_fl``/``vals_fl`` of shape
    (p, n_chunks, mb, K_CHUNK) — byte-identical to the per-bucket
    rectangles, laid out bucket-major then slot-major so a tile's chunks
    are contiguous.  ``chunk_lut[q, b]`` is the tile's offset table: the
    n_kc (= max-K / K_CHUNK) chunk indices the one-kernel Pallas backend
    scalar-prefetches (entries past the tile's ``chunk_cnt[q, b]`` are
    clamped to its last chunk, so a revisited block index costs no DMA).
    """

    cols_b: tuple     # per bucket: (p, slots_k, mb, K_k) int32 numpy (host)
    vals_b: tuple     # per bucket: (p, slots_k, mb, K_k) float32 numpy
    bucket_id: Array  # (p, p) int32 — bucket of tile (q, b)
    bucket_pos: Array  # (p, p) int32 — slot of tile (q, b) in its bucket
    yg: Array         # (p, mb)
    row_nnz_g: Array  # (p, mb)   |Omega_i|, >= 1
    col_nnz: Array    # (d_pad,)  |Omega-bar_j|, >= 1
    row_valid: Array  # (p, mb)  1.0 for real rows, 0.0 padding
    p: int
    mb: int           # rows per processor
    db: int           # cols per block
    bucket_ks: tuple  # static per-bucket packed widths, ascending
    # [q, s, j]: nnz of column j within row batch s of processor q's shard
    tile_col_nnz_g: Array = None   # (p, row_batches, d_pad)
    # [q, b, i]: nnz of row i of processor q within block b's columns
    tile_row_nnz_g: Array = None   # (p, p, mb)
    # per-tile raw max row widths (host-side, stats only)
    k_per_tile: np.ndarray = None  # (p, p) int
    # flat chunk view (device-resident payload of the one-kernel backends)
    cols_fl: Array = None    # (p, n_chunks, mb, K_CHUNK) int32
    vals_fl: Array = None    # (p, n_chunks, mb, K_CHUNK) float32
    chunk_lut: Array = None  # (p, p, n_kc) int32 — clamped chunk indices
    chunk_cnt: Array = None  # (p, p) int32 — live chunks of tile (q, b)

    def tile(self, q: int, b: int) -> SparseTile:
        """The packed tile of processor q / block b (tests, inspection)."""
        k = int(np.asarray(self.bucket_id)[q, b])
        s = int(np.asarray(self.bucket_pos)[q, b])
        return SparseTile(cols=self.cols_b[k][q, s],
                          vals=self.vals_b[k][q, s],
                          row_nnz=None, db=self.db)

    def flat_tile(self, q: int, b: int):
        """Tile (q, b) reassembled from the flat chunk view — (mb, K_k)
        ``(cols, vals)`` that must equal ``tile(q, b)`` exactly (pinned by
        the round-trip tests)."""
        lut = np.asarray(self.chunk_lut)[q, b]
        cnt = int(np.asarray(self.chunk_cnt)[q, b])
        c = np.asarray(self.cols_fl)[q, lut[:cnt]]   # (cnt, mb, K_CHUNK)
        v = np.asarray(self.vals_fl)[q, lut[:cnt]]
        return (c.transpose(1, 0, 2).reshape(self.mb, cnt * K_CHUNK),
                v.transpose(1, 0, 2).reshape(self.mb, cnt * K_CHUNK))


def density(prob) -> float:
    """nnz / (m * d) of a ``Problem``."""
    return float(prob.nnz) / float(max(1, prob.m * prob.d))


class _ShardAddr(NamedTuple):
    """Packed ELL address of every stored entry of one processor shard."""

    idx: np.ndarray         # (nnz_q,) global column index
    local_rows: np.ndarray  # (nnz_q,) row within the shard
    blk: np.ndarray         # (nnz_q,) block column
    pos: np.ndarray         # (nnz_q,) rank within the (row, block) segment
    vals: np.ndarray        # (nnz_q,) float32


def _shard_addressing(idx, local_rows, vals, mb: int, p: int, db: int,
                      rb: int, n_rb: int, d_pad: int):
    """Per-shard addressing pass shared by ``_tile_csr`` and the direct
    tile->tile reshard: given one shard's stored entries in ascending
    (row, col) order, compute the packed ELL address of every entry plus
    the per-tile statistics.  Returns
    ``(addr, k_raw_q, tile_row_nnz_q, tile_col_nnz_q)``.
    """
    blk = idx // db
    seg = local_rows * p + blk               # ascending: rows asc, blk asc
    counts = np.bincount(seg, minlength=mb * p)
    k_raw_q = counts.reshape(mb, p).max(axis=0)
    trn_q = counts.reshape(mb, p).T.astype(np.float32)
    starts = np.zeros(mb * p + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    pos = np.arange(len(seg)) - starts[seg]
    # per-row-batch per-column counts (global column index)
    tc_q = np.zeros((n_rb, d_pad), np.float32)
    if idx.size:
        batch = local_rows // rb
        keep = batch < n_rb                  # trailing truncated rows
        tc_q = np.bincount(batch[keep] * d_pad + idx[keep],
                           minlength=n_rb * d_pad) \
            .reshape(n_rb, d_pad).astype(np.float32)
    addr = _ShardAddr(idx=idx, local_rows=local_rows, blk=blk, pos=pos,
                      vals=vals)
    return addr, k_raw_q, trn_q, tc_q


def _tile_csr(csr: CSRMatrix, y, p: int, row_batches: int):
    """Layout-independent half of the grid tilers: padding, every scaling
    statistic, the per-tile raw widths, and the packed ELL address of each
    stored entry.  One vectorized pass per processor shard (entries are
    ascending by (row, col), so the per-(row, block) segments are
    contiguous); both the uniform and the bucketed packers scatter from the
    same addresses, which is what makes their trajectories identical.
    """
    m, d = csr.shape
    m_pad, d_pad = pad_to_multiple(m, p), pad_to_multiple(d, p)
    mb, db = m_pad // p, d_pad // p
    rb = max(1, mb // row_batches)
    n_rb = mb // rb

    y_pad = np.zeros(m_pad, np.float32)
    y_pad[:m] = np.asarray(y, np.float32)
    row_nnz = np.ones(m_pad, np.float32)
    row_nnz[:m] = np.maximum(csr.row_nnz(), 1.0)
    col_nnz = np.ones(d_pad, np.float32)
    col_nnz[:d] = np.maximum(csr.col_nnz(), 1.0)
    row_valid = np.zeros(m_pad, np.float32)
    row_valid[:m] = 1.0

    tile_row_nnz = np.zeros((p, p, mb), np.float32)
    tile_col_nnz = np.zeros((p, n_rb, d_pad), np.float32)
    k_raw = np.zeros((p, p), np.int64)
    addrs: list[_ShardAddr] = []
    for q in range(p):
        # clamp to m: with heavy padding a whole trailing shard can start
        # past the last real row, where indptr has no entry
        r0, r1 = min(q * mb, m), min((q + 1) * mb, m)
        lo, hi = csr.indptr[r0], csr.indptr[r1]
        idx = csr.indices[lo:hi].astype(np.int64)
        local_rows = np.repeat(np.arange(r1 - r0, dtype=np.int64),
                               np.diff(csr.indptr[r0:r1 + 1])) \
            if r1 > r0 else np.zeros(0, np.int64)
        addr, k_raw[q], tile_row_nnz[q], tile_col_nnz[q] = \
            _shard_addressing(idx, local_rows, csr.values[lo:hi],
                              mb, p, db, rb, n_rb, d_pad)
        addrs.append(addr)

    shared = dict(
        yg=jnp.asarray(y_pad.reshape(p, mb)),
        row_nnz_g=jnp.asarray(row_nnz.reshape(p, mb)),
        col_nnz=jnp.asarray(col_nnz),
        row_valid=jnp.asarray(row_valid.reshape(p, mb)),
        p=p, mb=mb, db=db,
        tile_col_nnz_g=jnp.asarray(tile_col_nnz),
        tile_row_nnz_g=jnp.asarray(tile_row_nnz),
        k_per_tile=k_raw,
    )
    return shared, addrs


def sparse_grid_from_csr(csr: CSRMatrix, y, p: int, row_batches: int = 1,
                         *, k_align: int = SUBLANE,
                         pow2: bool = False) -> SparseGridData:
    """Tile a CSR matrix onto the p x p grid without ever densifying.

    Uniform max-K packing: every tile padded to the grid's widest tile so
    the epoch vmaps over one rectangular array.  Cost and memory are
    O(nnz + p*p*mb*K).  See ``bucketed_grid_from_csr`` for the ragged
    layout that drops the max-K padding on skewed data.
    """
    shared, addrs = _tile_csr(csr, y, p, row_batches)
    return _pack_uniform(shared, addrs, k_align=k_align, pow2=pow2)


def _pack_uniform(shared, addrs, *, k_align: int = SUBLANE,
                  pow2: bool = False) -> SparseGridData:
    """Scatter packed ELL addresses into the uniform max-K grid.  Shared by
    ``sparse_grid_from_csr`` and the direct tile->tile reshard — both hand
    it the same ``(shared, addrs)`` a fresh ``_tile_csr`` would produce, so
    the resulting grids are equal field-for-field by construction."""
    p, mb, db = shared["p"], shared["mb"], shared["db"]
    K = choose_k(int(shared["k_per_tile"].max()), align=k_align, pow2=pow2)
    cols_g = np.zeros((p, p, mb, K), np.int32)
    vals_g = np.zeros((p, p, mb, K), np.float32)
    for q, a in enumerate(addrs):
        if a.idx.size == 0:
            continue
        cols_g[q, a.blk, a.local_rows, a.pos] = \
            (a.idx - a.blk * db).astype(np.int32)
        vals_g[q, a.blk, a.local_rows, a.pos] = a.vals
    return SparseGridData(cols_g=jnp.asarray(cols_g),
                          vals_g=jnp.asarray(vals_g), K=K, **shared)


def assign_k_buckets(k_per_tile, *, max_buckets: int = MAX_K_BUCKETS,
                     align: int = SUBLANE):
    """Group per-tile raw widths into <= ``max_buckets`` packed widths.

    Each tile starts at its sublane-aligned ``choose_k`` width (not the
    power of two: rounding the widest bucket up to pow2 can hand back
    30-50% of the padding this layout exists to remove); while more than
    ``max_buckets`` distinct widths remain, the width whose promotion to
    the next one up wastes the fewest padded slots (tiles * width gap) is
    merged upward.  Returns ``(widths, bucket_id)`` with ``widths`` an
    ascending int tuple and ``bucket_id`` (p, p) int32 indices into it.
    """
    k_raw = np.asarray(k_per_tile, np.int64)
    w_t = np.vectorize(lambda k: choose_k(int(k), align=align))(k_raw)
    widths = sorted(set(int(w) for w in w_t.ravel()))
    while len(widths) > max_buckets:
        costs = [(int((w_t == widths[i]).sum()) * (widths[i + 1] - widths[i]),
                  i) for i in range(len(widths) - 1)]
        _, i = min(costs)
        w_t[w_t == widths[i]] = widths[i + 1]
        widths.pop(i)
    bucket_id = np.searchsorted(widths, w_t).astype(np.int32)
    return tuple(widths), bucket_id


def bucketed_grid_from_csr(csr: CSRMatrix, y, p: int, row_batches: int = 1,
                           *, k_align: int = SUBLANE,
                           max_buckets: int = MAX_K_BUCKETS,
                           ) -> BucketedGridData:
    """Tile a CSR matrix onto the p x p grid in K-bucketed ragged form.

    Same addressing pass (and identical statistics) as
    ``sparse_grid_from_csr``, but each tile is packed at its *bucket's*
    width instead of the global max: resident bytes drop from
    ``8 * p^2 * mb * max-K`` to ``8 * mb * sum_k slots_k * K_k``, and a
    tile step streams ``8 * mb * bucket-K`` instead of ``8 * mb * max-K``.

    The flat chunk view (``cols_fl``/``vals_fl`` + ``chunk_lut``/
    ``chunk_cnt``) is derived here from the same addresses: a pure reshape
    of the per-bucket rectangles into (mb, K_CHUNK) chunks, concatenated
    bucket-major / slot-major so every tile's chunks are contiguous.  It
    carries exactly the same elements (no byte growth); only the flat view
    and the index tables go to the device — the per-bucket rectangles stay
    host-side numpy.
    """
    shared, addrs = _tile_csr(csr, y, p, row_batches)
    return _pack_bucketed(shared, addrs, k_align=k_align,
                          max_buckets=max_buckets)


def _pack_bucketed(shared, addrs, *, k_align: int = SUBLANE,
                   max_buckets: int = MAX_K_BUCKETS) -> BucketedGridData:
    """Scatter packed ELL addresses into the K-bucketed ragged grid (+ its
    flat chunk view).  Shared by ``bucketed_grid_from_csr`` and the direct
    tile->tile reshard, like ``_pack_uniform``."""
    p, mb, db = shared["p"], shared["mb"], shared["db"]
    widths, bucket_id = assign_k_buckets(shared["k_per_tile"],
                                         max_buckets=max_buckets,
                                         align=k_align)
    n_b = len(widths)
    bucket_pos = np.zeros((p, p), np.int32)
    t_per = np.zeros((p, n_b), np.int64)    # tiles per (processor, bucket)
    for q in range(p):
        for b in range(p):
            k = bucket_id[q, b]
            bucket_pos[q, b] = t_per[q, k]
            t_per[q, k] += 1
    slots = t_per.max(axis=0)               # rectangular: max over q
    cols_b = [np.zeros((p, int(slots[k]), mb, widths[k]), np.int32)
              for k in range(n_b)]
    vals_b = [np.zeros((p, int(slots[k]), mb, widths[k]), np.float32)
              for k in range(n_b)]
    for q, a in enumerate(addrs):
        if a.idx.size == 0:
            continue
        for b in range(p):
            msk = a.blk == b
            if not msk.any():
                continue
            k, s = int(bucket_id[q, b]), int(bucket_pos[q, b])
            cols_b[k][q, s, a.local_rows[msk], a.pos[msk]] = \
                (a.idx[msk] - b * db).astype(np.int32)
            vals_b[k][q, s, a.local_rows[msk], a.pos[msk]] = a.vals[msk]
    cols_fl, vals_fl, chunk_lut, chunk_cnt = _flat_chunk_view(
        cols_b, vals_b, widths, bucket_id, bucket_pos)
    return BucketedGridData(
        cols_b=tuple(cols_b), vals_b=tuple(vals_b),
        bucket_id=jnp.asarray(bucket_id),
        bucket_pos=jnp.asarray(bucket_pos),
        bucket_ks=widths,
        cols_fl=jnp.asarray(cols_fl), vals_fl=jnp.asarray(vals_fl),
        chunk_lut=jnp.asarray(chunk_lut), chunk_cnt=jnp.asarray(chunk_cnt),
        **shared)


def _flat_chunk_view(cols_b, vals_b, widths, bucket_id, bucket_pos):
    """Pack per-bucket (p, slots_k, mb, K_k) rectangles into the flat
    (p, n_chunks, mb, K_CHUNK) chunk buffer + per-tile offset tables.

    Chunk order is bucket-major, then slot-major within a bucket, so tile
    (q, b)'s ``n_k = K_k // K_CHUNK`` chunks sit at consecutive indices
    ``base[k] + pos * n_k .. + n_k - 1``.  ``chunk_lut[q, b, j]`` holds
    that range, with entries past ``chunk_cnt[q, b]`` clamped to the last
    live chunk (the scalar-prefetch index map then re-reads an
    already-resident block instead of streaming a dead one).
    """
    p = cols_b[0].shape[0] if cols_b else 0
    mb = cols_b[0].shape[2] if cols_b else 0
    n_per = np.asarray([w // K_CHUNK for w in widths], np.int64)
    base = np.zeros(len(widths) + 1, np.int64)
    parts_c, parts_v = [], []
    for k, w in enumerate(widths):
        s_k, n_k = cols_b[k].shape[1], int(n_per[k])
        base[k + 1] = base[k] + s_k * n_k
        for arr, parts in ((cols_b[k], parts_c), (vals_b[k], parts_v)):
            parts.append(arr.reshape(p, s_k, mb, n_k, K_CHUNK)
                         .transpose(0, 1, 3, 2, 4)
                         .reshape(p, s_k * n_k, mb, K_CHUNK))
    cols_fl = np.concatenate(parts_c, axis=1)
    vals_fl = np.concatenate(parts_v, axis=1)
    bucket_id = np.asarray(bucket_id)
    bucket_pos = np.asarray(bucket_pos)
    cnt = n_per[bucket_id]                              # (p, p)
    off = base[bucket_id] + bucket_pos * cnt            # (p, p)
    n_kc = int(n_per.max())                             # max-K / K_CHUNK
    lut = off[..., None] + np.minimum(np.arange(n_kc), cnt[..., None] - 1)
    return (cols_fl, vals_fl, lut.astype(np.int32), cnt.astype(np.int32))


def make_sparse_grid_data(prob, p: int, row_batches: int = 1,
                          **kw) -> SparseGridData:
    """Sparse-layout equivalent of ``core.dso.make_grid_data`` — built from
    a dense ``Problem`` (tests / small data).  Out-of-core data should come
    through ``sparse_grid_from_csr`` on an ingested ``CSRMatrix`` instead.
    """
    csr = CSRMatrix.from_dense(np.asarray(prob.X))
    return sparse_grid_from_csr(csr, np.asarray(prob.y), p, row_batches,
                                **kw)


def make_bucketed_grid_data(prob, p: int, row_batches: int = 1,
                            **kw) -> BucketedGridData:
    """Bucketed-layout grid builder from a dense ``Problem`` (tests / small
    data); out-of-core data goes through ``bucketed_grid_from_csr``."""
    csr = CSRMatrix.from_dense(np.asarray(prob.X))
    return bucketed_grid_from_csr(csr, np.asarray(prob.y), p, row_batches,
                                  **kw)


def grid_to_csr(data, m: int, d: int):
    """Reconstruct the global ``(m, d)`` ``CSRMatrix`` + labels from any
    grid layout — the p -> p' resharding path (``repro.runtime.reshard``)
    re-blocks from the packed tiles themselves, no raw data file needed.

    Accepts ``SparseGridData``, ``BucketedGridData``, or a dense
    ``GridData``-like (anything with ``Xg``); ``m``/``d`` are the real
    (unpadded) problem sizes, trimming the tiler's padding rows/columns.
    Stored entries are recovered from ``vals != 0`` — the tilers' padding
    slots carry exactly 0, and explicit zeros were already dropped by
    ``CSRMatrix.from_dense`` / the libsvm ingester — and sorted back to
    (row, col) order, so round-tripping a grid through here and the tiler
    reproduces the grid (and all its statistics) exactly.
    """
    p, mb, db = data.p, data.mb, data.db
    if isinstance(data, BucketedGridData):
        qq, bb, ii, kk, vv = [], [], [], [], []
        bucket_id = np.asarray(data.bucket_id)
        bucket_pos = np.asarray(data.bucket_pos)
        for q in range(p):
            for b in range(p):
                k, s = int(bucket_id[q, b]), int(bucket_pos[q, b])
                vals = np.asarray(data.vals_b[k][q, s])
                i, pos = np.nonzero(vals)
                qq.append(np.full(i.shape, q, np.int64))
                bb.append(np.full(i.shape, b, np.int64))
                ii.append(i.astype(np.int64))
                kk.append(np.asarray(data.cols_b[k][q, s])[i, pos]
                          .astype(np.int64))
                vv.append(vals[i, pos])
        q_i, b_i, i_i = map(np.concatenate, (qq, bb, ii))
        local_cols, vals = np.concatenate(kk), np.concatenate(vv)
        rows, cols = q_i * mb + i_i, b_i * db + local_cols
    elif isinstance(data, SparseGridData):
        vals_g = np.asarray(data.vals_g)
        q_i, b_i, i_i, pos = np.nonzero(vals_g)
        rows = q_i.astype(np.int64) * mb + i_i
        cols = (b_i.astype(np.int64) * db
                + np.asarray(data.cols_g)[q_i, b_i, i_i, pos])
        vals = vals_g[q_i, b_i, i_i, pos]
    else:   # dense GridData-like
        X = np.asarray(data.Xg).reshape(p * mb, -1)[:m, :d]
        y = np.asarray(data.yg).reshape(-1)[:m]
        return CSRMatrix.from_dense(X), y
    keep = (rows < m) & (cols < d)   # belt-and-braces: pads carry val 0
    order = np.lexsort((cols[keep], rows[keep]))
    rows, cols, vals = rows[keep][order], cols[keep][order], vals[keep][order]
    indptr = np.zeros(m + 1, np.int64)
    np.cumsum(np.bincount(rows, minlength=m), out=indptr[1:])
    csr = CSRMatrix(indptr=indptr, indices=cols.astype(np.int32),
                    values=vals.astype(np.float32), shape=(m, d))
    return csr, np.asarray(data.yg).reshape(-1)[:m]


def _grid_entries(data):
    """Stored entries of every processor shard of a packed grid, each in
    ascending (local row, global col) order — the exact order ``_tile_csr``
    receives them in from a CSR.  Returns per shard
    ``(idx, local_rows, vals)`` with ``idx`` the GLOBAL column index."""
    p, mb, db = data.p, data.mb, data.db
    out = []
    if isinstance(data, SparseGridData):
        cols_g = np.asarray(data.cols_g)
        vals_g = np.asarray(data.vals_g)
        for q in range(p):
            # walk the tile cube row-major — (mb, p, K) — so nonzero emits
            # ascending (row, block, pos) = ascending (row, col), no sort
            vq = vals_g[q].transpose(1, 0, 2)
            i, b, pos = np.nonzero(vq)
            idx = b * db + cols_g[q, b, i, pos].astype(np.int64)
            out.append((idx, i.astype(np.int64), vq[i, b, pos]))
    elif isinstance(data, BucketedGridData):
        bucket_id = np.asarray(data.bucket_id)
        bucket_pos = np.asarray(data.bucket_pos)
        for q in range(p):
            idx_l, row_l, val_l = [], [], []
            for b in range(p):
                k, s = int(bucket_id[q, b]), int(bucket_pos[q, b])
                vals = np.asarray(data.vals_b[k][q, s])
                i, pos = np.nonzero(vals)
                idx_l.append(b * db + np.asarray(data.cols_b[k][q, s])
                             [i, pos].astype(np.int64))
                row_l.append(i.astype(np.int64))
                val_l.append(vals[i, pos])
            idx = np.concatenate(idx_l)
            rows = np.concatenate(row_l)
            vals = np.concatenate(val_l)
            # block-major -> row-major; a stable sort keeps blocks (and the
            # ascending cols within each block) in order inside each row
            order = np.argsort(rows, kind="stable")
            out.append((idx[order], rows[order], vals[order]))
    else:
        raise TypeError(f"packed grid expected, got {type(data).__name__}")
    return out


def regrid_direct(data, m: int, d: int, p_new: int, row_batches: int = 1,
                  *, layout: str | None = None, k_align: int = SUBLANE,
                  pow2: bool = False, max_buckets: int = MAX_K_BUCKETS):
    """Direct tile->tile re-blocking of a packed grid onto the p' grid,
    skipping the ``grid_to_csr`` round-trip (no global CSR, no global
    (row, col) lexsort, no indptr rebuild).

    Works when the padded problem sizes agree at both blockings
    (``pad_to_multiple(m, p) == pad_to_multiple(m, p')``, same for d) and
    one of p, p' divides the other: then a new shard is either a
    concatenation of r = p/p' old shards (merge) or a contiguous row slice
    of one old shard (split), both of which preserve the ascending
    (row, col) entry order ``_tile_csr`` relies on.  The remapped entries
    are fed through the SAME per-shard addressing pass and packers as a
    fresh tiling at p', so the result equals the round-trip grid
    field-for-field by construction (pinned by tests).

    Returns ``None`` when the preconditions fail — the caller
    (``runtime.reshard.retile``) falls back to the CSR round-trip.
    ``layout`` may differ from the input's (uniform <-> bucketed
    conversion is free: both pack from the same addresses).
    """
    if not isinstance(data, (SparseGridData, BucketedGridData)):
        return None
    p, mb, db = data.p, data.mb, data.db
    if (pad_to_multiple(m, p) != pad_to_multiple(m, p_new)
            or pad_to_multiple(d, p) != pad_to_multiple(d, p_new)
            or (p % p_new and p_new % p)):
        return None
    if layout is None:
        layout = "bucketed" if isinstance(data, BucketedGridData) \
            else "sparse"
    if layout not in ("sparse", "bucketed"):
        return None
    m_pad, d_pad = p * mb, p * db
    mb2, db2 = m_pad // p_new, d_pad // p_new
    rb = max(1, mb2 // row_batches)
    n_rb = mb2 // rb

    old = _grid_entries(data)
    ents = []
    if p_new <= p:       # merge: new shard q' = old shards q'*r .. +r-1
        r = p // p_new
        for q2 in range(p_new):
            grp = old[q2 * r:(q2 + 1) * r]
            ents.append((np.concatenate([g[0] for g in grp]),
                         np.concatenate([g[1] + j * mb
                                         for j, g in enumerate(grp)]),
                         np.concatenate([g[2] for g in grp])))
    else:                # split: old shard q -> s contiguous row slices
        s = p_new // p
        for q in range(p):
            idx, rows, vals = old[q]
            cut = np.searchsorted(rows, np.arange(s + 1) * mb2)
            for j in range(s):
                lo, hi = cut[j], cut[j + 1]
                ents.append((idx[lo:hi], rows[lo:hi] - j * mb2,
                             vals[lo:hi]))

    tile_row_nnz = np.zeros((p_new, p_new, mb2), np.float32)
    tile_col_nnz = np.zeros((p_new, n_rb, d_pad), np.float32)
    k_raw = np.zeros((p_new, p_new), np.int64)
    addrs = []
    for q2, (idx, rows, vals) in enumerate(ents):
        addr, k_raw[q2], tile_row_nnz[q2], tile_col_nnz[q2] = \
            _shard_addressing(idx, rows, vals, mb2, p_new, db2,
                              rb, n_rb, d_pad)
        addrs.append(addr)
    # global row/col orders are unchanged (equal padded sizes), so the
    # shard-shaped statistics re-block by pure reshape
    shared = dict(
        yg=jnp.asarray(np.asarray(data.yg).reshape(p_new, mb2)),
        row_nnz_g=jnp.asarray(np.asarray(data.row_nnz_g)
                              .reshape(p_new, mb2)),
        col_nnz=jnp.asarray(np.asarray(data.col_nnz)),
        row_valid=jnp.asarray(np.asarray(data.row_valid)
                              .reshape(p_new, mb2)),
        p=p_new, mb=mb2, db=db2,
        tile_col_nnz_g=jnp.asarray(tile_col_nnz),
        tile_row_nnz_g=jnp.asarray(tile_row_nnz),
        k_per_tile=k_raw,
    )
    if layout == "sparse":
        return _pack_uniform(shared, addrs, k_align=k_align, pow2=pow2)
    return _pack_bucketed(shared, addrs, k_align=k_align,
                          max_buckets=max_buckets)


def csr_k_per_tile(csr: CSRMatrix, p: int) -> np.ndarray:
    """(p, p) per-tile raw packed widths (max row nnz within each tile) —
    the ``impl="auto"`` skew probe, O(nnz) without building any grid."""
    m, d = csr.shape
    mb = pad_to_multiple(m, p) // p
    db = pad_to_multiple(d, p) // p
    k_raw = np.zeros((p, p), np.int64)
    for q in range(p):
        r0, r1 = min(q * mb, m), min((q + 1) * mb, m)
        lo, hi = csr.indptr[r0], csr.indptr[r1]
        if hi <= lo:
            continue
        local_rows = np.repeat(np.arange(r1 - r0, dtype=np.int64),
                               np.diff(csr.indptr[r0:r1 + 1]))
        seg = local_rows * p + csr.indices[lo:hi].astype(np.int64) // db
        k_raw[q] = np.bincount(seg, minlength=mb * p).reshape(mb, p) \
            .max(axis=0)
    return k_raw


def problem_k_per_tile(prob, p: int) -> np.ndarray:
    """``csr_k_per_tile`` for an in-memory dense ``Problem``."""
    X = np.asarray(prob.X)
    m, d = X.shape
    m_pad, d_pad = pad_to_multiple(m, p), pad_to_multiple(d, p)
    nz = np.zeros((m_pad, d_pad), bool)
    nz[:m, :d] = X != 0
    mb, db = m_pad // p, d_pad // p
    # [q, i, b] per-row-per-block counts -> max over the shard's rows
    return nz.reshape(p, mb, p, db).sum(axis=3).max(axis=1) \
        .astype(np.int64)


def tile_k_skew(k_per_tile) -> float:
    """``k_raw.max() / median`` — how much the uniform max-K layout
    overpays relative to the typical tile (>= 1.0)."""
    k = np.maximum(np.asarray(k_per_tile, np.float64), 1.0)
    return float(k.max() / max(float(np.median(k)), 1.0))


def grid_nbytes(data) -> int:
    """Resident bytes of the packed tile arrays (the nnz-proportional
    replacement for the dense grid's 4 * m_pad * d_pad).  Computed from
    shape/dtype — no device-to-host copy."""
    if isinstance(data, BucketedGridData):
        # device-resident = the flat chunk view + the index tables (the
        # per-bucket rectangles are host-side numpy, not counted); the flat
        # view carries exactly the per-bucket rectangles' elements
        return int(data.cols_fl.nbytes + data.vals_fl.nbytes
                   + data.bucket_id.nbytes + data.bucket_pos.nbytes
                   + data.chunk_lut.nbytes + data.chunk_cnt.nbytes)
    return int(data.cols_g.nbytes + data.vals_g.nbytes)


def packed_bytes_per_step(data) -> float:
    """Mean packed-tile bytes streamed per tile step (cols i32 + vals f32;
    one epoch touches every tile exactly once, so the mean over tiles is
    the per-step expectation under any full schedule)."""
    if isinstance(data, BucketedGridData):
        ks = np.asarray(data.bucket_ks)[np.asarray(data.bucket_id)]
        return float(8 * data.mb * ks.mean())
    return float(8 * data.mb * data.K)
