import numpy as np
import pytest

from bench import gen


@pytest.mark.parametrize("m,d,k", [(300, 50, 5), (70, 2000, 40),
                                   (5000, 4000, 3)])
def test_generator_shape_and_repeat(m, d, k):
    a = gen.powerlaw_csr(m, d, k, 1.1, 2**31 + 7)
    assert (a.m, a.d, a.nnz) == (m, d, m * k)
    assert np.array_equal(np.diff(a.indptr), np.full(m, k))
    cols = a.indices.reshape(m, k)
    assert (np.diff(cols, axis=1) > 0).all()          # distinct, ascending
    assert cols.min() >= 0 and cols.max() < d
    assert np.allclose(np.linalg.norm(a.values.reshape(m, k), axis=1), 1,
                       atol=1e-5)
    assert set(np.unique(a.y)) <= {-1.0, 1.0}
    b = gen.powerlaw_csr(m, d, k, 1.1, 2**31 + 7, threads=1)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    c = gen.powerlaw_csr(m, d, k, 1.1, 3)
    assert not np.array_equal(a.indices, c.indices)


def test_popular_columns_spread_over_blocks():
    a = gen.powerlaw_csr(4000, 1000, 10, 1.0, 0)
    counts = np.bincount(a.indices, minlength=1000)
    ranked = np.sort(counts)[::-1]
    assert ranked[:250].sum() > 0.6 * a.nnz           # a power law by rank
    blocks = counts.reshape(4, 250).sum(axis=1)        # ids not by rank
    assert blocks.max() < 0.4 * a.nnz


def test_permute_rows_keeps_rows():
    a = gen.powerlaw_csr(203, 100, 6, 1.1, 5)
    b = gen.permute_rows(a, -12, 4)
    assert not np.array_equal(a.indices, b.indices)
    rows = lambda c, lo, hi: sorted(map(tuple, np.concatenate(
        [c.indices.reshape(c.m, -1), c.values.reshape(c.m, -1),
         c.y[:, None]], axis=1)[lo:hi].tolist()))
    for lo in range(0, 203, 51):          # each shard of ceil(203 / 4) rows
        assert rows(a, lo, lo + 51) == rows(b, lo, lo + 51)
    again = gen.permute_rows(a, -12, 4)
    assert np.array_equal(b.values, again.values)
