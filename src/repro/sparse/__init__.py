"""Block-sparse data subsystem: streaming libsvm ingestion, padded
block-ELL grid tiles, and the nnz-proportional DSO path.

Layout/format:      ``repro.sparse.format``   (CSRMatrix, SparseTile,
                                               SparseGridData, tilers)
Out-of-core ingest: ``repro.sparse.ingest``   (two-pass libsvm -> CSR)
Pallas kernel:      ``repro.kernels.dso_sparse`` (one-hot tile step)
Runners:            ``core.dso.run_dso_grid(impl='sparse')`` and
                    ``core.dso_dist.ShardedDSO(impl='sparse')``.
"""

from repro.sparse.format import (BUCKET_SKEW_THRESHOLD, BucketedGridData,
                                 CSRMatrix, K_CHUNK, MAX_K_BUCKETS,
                                 SparseGridData, SparseTile,
                                 SPARSE_DENSITY_THRESHOLD,
                                 assign_k_buckets, bucketed_grid_from_csr,
                                 choose_k, csr_k_per_tile, density,
                                 grid_nbytes, make_bucketed_grid_data,
                                 make_sparse_grid_data,
                                 packed_bytes_per_step, problem_k_per_tile,
                                 sparse_grid_from_csr, tile_k_skew)
from repro.sparse.ingest import (ScanStats, csr_primal_objective,
                                 ingest_libsvm, iter_csr_shards,
                                 scan_libsvm)

__all__ = [
    "BUCKET_SKEW_THRESHOLD", "BucketedGridData", "CSRMatrix", "K_CHUNK",
    "MAX_K_BUCKETS", "SparseGridData", "SparseTile",
    "SPARSE_DENSITY_THRESHOLD", "assign_k_buckets",
    "bucketed_grid_from_csr", "choose_k", "csr_k_per_tile", "density",
    "grid_nbytes", "make_bucketed_grid_data", "make_sparse_grid_data",
    "packed_bytes_per_step", "problem_k_per_tile", "sparse_grid_from_csr",
    "tile_k_skew",
    "ScanStats", "csr_primal_objective", "ingest_libsvm",
    "iter_csr_shards", "scan_libsvm",
]
