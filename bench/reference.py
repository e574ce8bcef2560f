"""Plain reference of the solver the benchmark drives: DSO epochs on the
p x p grid (arXiv:1406.4363, Algorithm 1 with tile-aggregated Eq. (8)).

Rows are cut into p shards of ceil(m/p) rows and columns into p blocks of
ceil(d/p) columns.  In inner iteration r of an epoch, worker q updates
block (q + r) mod p (the cyclic schedule).  One tile step reads w and
alpha as they were before the step and aggregates Eq. (8) over every
nonzero of the tile:

    g_w[j] = lam * 2 w_j * n_qj / |col j|  - (X_t^T alpha)_j / m
    g_a[i] = -dl*(-a_i) * n_bi / (m |row i|) - (X_t w)_i / m

with n_qj the nonzeros of column j in shard q, n_bi those of row i in
block b, and |row i|, |col j| the global counts (at least 1).  Then
AdaGrad (accumulators gw, ga; step eta0 / sqrt(acc + 1e-8)), App. B's box
on w and the conjugate domain on alpha.  Tiles of one inner iteration are
disjoint (Lemma 2), so their steps run on p threads at once here; each
writes only its own rows of alpha and columns of w.

``round`` is applied to the result of every arithmetic step: the identity
in float64 for the reference, a rounding to bfloat16 for the control.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench import objective as obj

ADA_EPS = 1e-8


def _bf16(x):
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16) \
        .astype(np.float32)


PRECISIONS = {"float64": (np.float64, lambda x: x),
              "bfloat16": (np.float32, _bf16)}


class _Tile:
    __slots__ = ("urows", "ucols", "inv_r", "inv_c", "vals", "trn", "tcn")


def _tiles(csr, p: int):
    """Per (q, b): touched rows and columns, local indices, counts."""
    m, d = csr.m, csr.d
    mb, db = -(-m // p), -(-d // p)
    lut = np.empty(d, np.int64)
    all_rows = csr.row_ids()
    tiles = {}
    for q in range(p):
        lo, hi = csr.indptr[min(q * mb, m)], csr.indptr[min((q + 1) * mb, m)]
        rows = all_rows[lo:hi]
        cols = csr.indices[lo:hi].astype(np.int64)
        vals = csr.values[lo:hi]
        blk = cols // db
        for b in range(p):
            sel = np.flatnonzero(blk == b)
            t = _Tile()
            r, c = rows[sel], cols[sel]
            t.urows, t.inv_r = np.unique(r, return_inverse=True)
            present = np.zeros(d, bool)
            present[c] = True
            t.ucols = np.flatnonzero(present)
            lut[t.ucols] = np.arange(t.ucols.size)
            t.inv_c = lut[c]
            t.vals = vals[sel]
            t.trn = np.bincount(t.inv_r, minlength=t.urows.size)
            t.tcn = np.bincount(t.inv_c, minlength=t.ucols.size)
            tiles[q, b] = t
    return tiles


class Reference:
    """DSO iterates from the zero primal state, epoch by epoch."""

    def __init__(self, csr, *, loss: str, lam: float, p: int, eta0: float,
                 alpha0: float, precision: str = "float64"):
        obj.check_loss(loss, "l2")
        self.dtype, self.round = PRECISIONS[precision]
        self.csr, self.loss, self.p = csr, loss, p
        f = self.dtype
        self.lam, self.eta, self.m = f(lam), f(eta0), f(csr.m)
        self.box = f(obj.w_box(loss, lam))
        self.y = csr.y.astype(f)
        self.row_nnz = np.maximum(np.diff(csr.indptr), 1).astype(f)
        self.col_nnz = np.maximum(np.bincount(csr.indices, minlength=csr.d),
                                  1).astype(f)
        self.tiles = _tiles(csr, p)
        for t in self.tiles.values():
            t.vals = self.round(t.vals.astype(f))
            t.trn, t.tcn = t.trn.astype(f), t.tcn.astype(f)
        self.w = np.zeros(csr.d, f)
        self.gw = np.zeros(csr.d, f)
        self.alpha = self.round(obj.project_alpha(
            loss, np.full(csr.m, alpha0, f), self.y).astype(f))
        self.ga = np.zeros(csr.m, f)
        self.epochs = 0

    def _tile_step(self, t: _Tile) -> None:
        r, f = self.round, self.dtype
        wj, ai, yi = self.w[t.ucols], self.alpha[t.urows], self.y[t.urows]
        xw = r(np.bincount(t.inv_r, weights=r(t.vals * wj[t.inv_c]),
                           minlength=t.urows.size).astype(f))
        xta = r(np.bincount(t.inv_c, weights=r(t.vals * ai[t.inv_r]),
                            minlength=t.ucols.size).astype(f))
        g_w = r(r(r(r(self.lam * r(2 * wj)) * t.tcn) / self.col_nnz[t.ucols])
                - r(xta / self.m))
        g_a = r(r(r(-obj.dual_grad(self.loss, ai, yi).astype(f)
                    * t.trn) / r(self.m * self.row_nnz[t.urows]))
                - r(xw / self.m))
        gw = r(self.gw[t.ucols] + r(g_w * g_w))
        ga = r(self.ga[t.urows] + r(g_a * g_a))
        dw = r(r(self.eta * g_w) / r(np.sqrt(r(gw + f(ADA_EPS)))))
        da = r(r(self.eta * g_a) / r(np.sqrt(r(ga + f(ADA_EPS)))))
        self.w[t.ucols] = np.clip(r(wj - dw), -self.box, self.box)
        self.gw[t.ucols] = gw
        self.alpha[t.urows] = r(obj.project_alpha(self.loss, r(ai + da), yi)
                                .astype(f))
        self.ga[t.urows] = ga

    def epoch(self) -> None:
        p = self.p
        with ThreadPoolExecutor(max_workers=p) as pool:
            for step in range(p):
                list(pool.map(self._tile_step,
                              [self.tiles[q, (q + step) % p]
                               for q in range(p)]))
        self.epochs += 1

    def run_to(self, epochs: int) -> None:
        while self.epochs < epochs:
            self.epoch()
