"""Per-kernel allclose vs the pure-jnp oracles (interpret mode), with
shape/dtype sweeps and hypothesis property tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# hypothesis is optional: without it the property tests collect as SKIPPED
from _hypothesis_compat import given, settings, st

from repro.kernels import ops
from repro.sparse.format import ONEHOT_MAX_DB
from repro.kernels.ref import dso_tile_step_ref, ssd_scan_ref, swa_attention_ref

RNG = np.random.default_rng(42)


# ------------------------------------------------------------ dso_update --


def _dso_inputs(M, D, density, seed=0):
    rng = np.random.default_rng(seed)
    X = (rng.random((M, D)) < density).astype(np.float32)
    X *= rng.normal(0, 1, (M, D)).astype(np.float32)
    y = np.where(rng.random(M) < 0.5, 1.0, -1.0).astype(np.float32)
    w = rng.normal(0, 0.1, D).astype(np.float32)
    alpha = (y * rng.random(M)).astype(np.float32)
    gw = np.abs(rng.normal(0, 0.01, D)).astype(np.float32)
    ga = np.abs(rng.normal(0, 0.01, M)).astype(np.float32)
    rn = np.maximum((X != 0).sum(1), 1).astype(np.float32)
    cn = np.maximum((X != 0).sum(0), 1).astype(np.float32)
    sc = np.array([0.5, 1e-3, M, -31.6, 31.6], np.float32)
    return tuple(jnp.asarray(a) for a in (X, y, w, alpha, gw, ga, rn, cn, sc))


@pytest.mark.parametrize("M,D,bm,bd", [
    (256, 512, 256, 512),    # single block
    (512, 1024, 256, 512),   # multi block both axes
    (300, 700, 128, 256),    # ragged -> padding path
    (64, 128, 32, 128),      # small
])
@pytest.mark.parametrize("loss", ["hinge", "logistic", "square"])
def test_dso_tile_step_matches_ref(M, D, bm, bd, loss):
    args = _dso_inputs(M, D, 0.1, seed=M + D)
    out_k = ops.dso_tile_step(*args, loss_name=loss, reg_name="l2",
                              bm=bm, bd=bd, interpret=True)
    out_r = dso_tile_step_ref(*args, loss_name=loss, reg_name="l2")
    for name, a, b in zip("w alpha gw ga".split(), out_k, out_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6, err_msg=name)


@pytest.mark.parametrize("reg", ["l1", "l2"])
def test_dso_tile_step_regularizers(reg):
    args = _dso_inputs(128, 256, 0.2, seed=9)
    out_k = ops.dso_tile_step(*args, loss_name="square", reg_name=reg,
                              interpret=True)
    out_r = dso_tile_step_ref(*args, loss_name="square", reg_name=reg)
    for a, b in zip(out_k, out_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


@given(m_exp=st.integers(4, 8), d_exp=st.integers(7, 9),
       density=st.floats(0.05, 0.9),
       loss=st.sampled_from(["hinge", "logistic", "square"]))
@settings(max_examples=10, deadline=None)
def test_dso_tile_step_property(m_exp, d_exp, density, loss):
    M, D = 2 ** m_exp, 2 ** d_exp
    args = _dso_inputs(M, D, density, seed=m_exp * 31 + d_exp)
    out_k = ops.dso_tile_step(*args, loss_name=loss, reg_name="l2",
                              interpret=True)
    out_r = dso_tile_step_ref(*args, loss_name=loss, reg_name="l2")
    for a, b in zip(out_k, out_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-5, atol=3e-6)
    # invariant: alpha stays in the conjugate domain
    _, alpha_new, _, _ = out_k
    if loss in ("hinge", "logistic"):
        ya = np.asarray(args[1]) * np.asarray(alpha_new)
        assert ya.min() >= -1e-6 and ya.max() <= 1 + 1e-6


# --------------------------------------------------------- swa_attention --


def _attn_inputs(B, Hq, Hkv, Tq, Tk, Dh, dtype=np.float32, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(0, 1, (B, Hq, Tq, Dh)).astype(dtype))
    k = jnp.asarray(rng.normal(0, 1, (B, Hkv, Tk, Dh)).astype(dtype))
    v = jnp.asarray(rng.normal(0, 1, (B, Hkv, Tk, Dh)).astype(dtype))
    return q, k, v


@pytest.mark.parametrize("B,Hq,Hkv,Tq,Tk,Dh,window", [
    (1, 2, 2, 256, 256, 64, 128),     # MHA
    (2, 4, 2, 256, 256, 64, 64),      # GQA
    (1, 8, 1, 128, 128, 32, 1024),    # MQA, window > T (= full causal)
    (1, 2, 1, 100, 100, 64, 50),      # ragged -> padding
])
def test_swa_matches_ref(B, Hq, Hkv, Tq, Tk, Dh, window):
    q, k, v = _attn_inputs(B, Hq, Hkv, Tq, Tk, Dh, seed=Tq)
    o1 = ops.swa_attention(q, k, v, window=window, interpret=True,
                           bq=64, bk=64)
    o2 = swa_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-5, atol=2e-5)


def test_swa_decode_offset():
    """Decode: 1 query row at the end of a long cache."""
    q, k, v = _attn_inputs(2, 4, 2, 8, 512, 64, seed=5)
    o1 = ops.swa_attention(q, k, v, window=256, q_offset=504,
                           interpret=True, bq=8, bk=128)
    o2 = swa_attention_ref(q, k, v, window=256, q_offset=504)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-5, atol=2e-5)


def test_swa_bf16():
    q, k, v = _attn_inputs(1, 2, 2, 128, 128, 64, dtype=np.float32, seed=7)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    o1 = ops.swa_attention(q, k, v, window=64, interpret=True, bq=64, bk=64)
    o2 = swa_attention_ref(q, k, v, window=64)
    assert o1.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), rtol=3e-2,
                               atol=3e-2)


@given(tq_tiles=st.integers(1, 3), win_frac=st.floats(0.1, 2.0),
       hq=st.sampled_from([1, 2, 4]))
@settings(max_examples=8, deadline=None)
def test_swa_property(tq_tiles, win_frac, hq):
    T = 64 * tq_tiles
    window = max(1, int(win_frac * T))
    q, k, v = _attn_inputs(1, hq, 1, T, T, 32, seed=T + hq)
    o1 = ops.swa_attention(q, k, v, window=window, interpret=True,
                           bq=64, bk=64)
    o2 = swa_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=3e-5, atol=3e-5)


# -------------------------------------------------------------- ssd_scan --


def _ssd_inputs(b, t, h, dh, n, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(0, 1, (b, t, h, dh)).astype(np.float32))
    dt = jnp.asarray((np.abs(rng.normal(0, 0.1, (b, t, h))) + 0.01)
                     .astype(np.float32))
    A = jnp.asarray(-np.abs(rng.normal(1, 0.3, (h,))).astype(np.float32))
    B = jnp.asarray((rng.normal(0, 1, (b, t, n)) / np.sqrt(n))
                    .astype(np.float32))
    C = jnp.asarray((rng.normal(0, 1, (b, t, n)) / np.sqrt(n))
                    .astype(np.float32))
    return x, dt, A, B, C


@pytest.mark.parametrize("b,t,h,dh,n,chunk", [
    (1, 128, 2, 32, 16, 64),
    (2, 256, 3, 32, 16, 64),
    (1, 100, 2, 16, 8, 32),     # ragged -> padding
    (1, 512, 1, 64, 32, 128),
])
def test_ssd_matches_ref(b, t, h, dh, n, chunk):
    x, dt, A, B, C = _ssd_inputs(b, t, h, dh, n, seed=t)
    y1 = ops.ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    y2 = ssd_scan_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-4, atol=2e-5)


@given(chunks=st.integers(1, 4), h=st.integers(1, 3),
       decay=st.floats(0.1, 3.0))
@settings(max_examples=8, deadline=None)
def test_ssd_property(chunks, h, decay):
    t = 64 * chunks
    x, dt, A, B, C = _ssd_inputs(1, t, h, 16, 8, seed=chunks * 7 + h)
    A = A * decay
    y1 = ops.ssd_scan(x, dt, A, B, C, chunk=64, interpret=True)
    y2 = ssd_scan_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=3e-4, atol=3e-5)


def test_ssd_state_decay_invariant():
    """With A -> -inf (total decay) each position only sees itself."""
    x, dt, A, B, C = _ssd_inputs(1, 128, 1, 16, 8, seed=3)
    A = jnp.full_like(A, -1e4)
    y = ops.ssd_scan(x, dt, A, B, C, chunk=64, interpret=True)
    # expected: y_t = C_t . (dt_t B_t x_t^T)
    want = jnp.einsum("btn,bth,btn,bthd->bthd", C, dt, B, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4,
                               atol=1e-5)


# ------------------------------------------- Mosaic scatter/gather gate --


def test_compiled_sparse_kernel_fails_loudly_without_mosaic_scatter(
        monkeypatch):
    """ROADMAP "Mosaic-native scatter/gather" step 2: requesting the
    bucketed sparse Pallas kernel COMPILED on a platform whose backend
    cannot lower its scatter-add / 2-D gather raises a ValueError naming
    the jnp fallback, not an opaque lowering error.  The uniform one-hot
    kernel needs neither op: it is handed to the compiler with no probe
    and no refusal.  Platform mocked: _on_tpu True makes interpret=None
    resolve to compiled, and the probe kernel then hits this container's
    real (CPU) backend, which lacks the lowering."""
    from repro.kernels import dso_sparse

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    ops._mosaic_sparse_gather_error.cache_clear()
    try:
        z8 = jnp.zeros(8, jnp.float32)
        uniform_args = (
            jnp.zeros((8, 8), jnp.int32), jnp.zeros((8, 8), jnp.float32),
            z8, z8, z8, z8, z8, jnp.ones(8), jnp.ones((1, 8)), jnp.ones(8),
            jnp.ones(8),
            jnp.asarray([0.5, 1e-3, 8.0, -31.6, 31.6], jnp.float32))
        uniform_kw = dict(row_batches=1, loss_name="hinge", reg_name="l2")

        # the uniform kernel: no probe is consulted, nothing is refused,
        # and the compiled (not interpreted) kernel is what gets called
        def no_probe():
            raise AssertionError("the uniform kernel consulted the probe")

        called = {}

        def compiled_kernel(*args, interpret, **kw):
            called["interpret"] = interpret
            return args[4], args[5], args[6], args[7]

        monkeypatch.setattr(ops, "mosaic_sparse_gather_error", no_probe)
        monkeypatch.setattr(dso_sparse, "dso_sparse_block_step_pallas",
                            compiled_kernel)
        ops.dso_sparse_block_step(*uniform_args, **uniform_kw)
        assert called == {"interpret": False}
        monkeypatch.undo()
        monkeypatch.setattr(ops, "_on_tpu", lambda: True)

        # the one-kernel bucketed wrapper keeps the gate (and names the
        # bit-identical jnp fallback)
        with pytest.raises(ValueError, match="sparse_bucketed_jnp"):
            ops.dso_bucketed_block_step(
                jnp.zeros((2, 8, 8), jnp.int32),
                jnp.zeros((2, 8, 8), jnp.float32),
                jnp.zeros(2, jnp.int32), jnp.int32(1),
                z8, z8, z8, z8, z8, jnp.ones(8), jnp.ones((1, 8)),
                jnp.ones(8), jnp.ones(8),
                jnp.asarray([0.5, 1e-3, 8.0, -31.6, 31.6], jnp.float32),
                row_batches=1, loss_name="hinge", reg_name="l2")
        # explicit interpret=True must keep working under the mock
        out = ops.dso_sparse_block_step(*uniform_args, interpret=True,
                                        **uniform_kw)
        assert np.isfinite(np.asarray(out[0])).all()
    finally:
        ops._mosaic_sparse_gather_error.cache_clear()


@pytest.mark.parametrize("on_tpu,db,want", [
    (True, 5_240, "sparse_pallas"),
    (True, ONEHOT_MAX_DB, "sparse_pallas"),
    (True, ONEHOT_MAX_DB + 1, "sparse_jnp"),
    (False, 5_240, "sparse_jnp"),
])
def test_auto_picks_onehot_kernel_on_tpu_for_narrow_blocks(
        monkeypatch, on_tpu, db, want):
    """``auto`` on a built uniform sparse grid: the one-hot Pallas kernel
    where the computation runs on a TPU and the block is at most
    ``ONEHOT_MAX_DB`` wide; XLA's gather (``sparse_jnp``) on any other
    platform and for wider blocks.  ``resolve_backend`` picks the layout
    only; the platform is mocked."""
    from repro.engine.backends import (resolve_backend,
                                       resolve_backend_for_layout)

    monkeypatch.setattr(ops, "_on_tpu", lambda: on_tpu)
    assert resolve_backend("auto", 0.001, k_skew=1.0).layout == "sparse"
    assert resolve_backend_for_layout("auto", "sparse", db).name == want
    # the other layouts and explicit kernels are untouched by the rule
    assert resolve_backend_for_layout("auto", "bucketed", db).name \
        == "sparse_bucketed_jnp"
    assert resolve_backend_for_layout("auto", "dense", db).name \
        == "dense_jnp"
    assert resolve_backend_for_layout("jnp", "sparse", db).name \
        == "sparse_jnp"


@pytest.mark.parametrize("use_adagrad", [True, False])
def test_auto_onehot_kernel_runs_both_step_rules_on_tpu(monkeypatch,
                                                        use_adagrad):
    """On a TPU, ``auto`` gives a narrow sparse problem the one-hot kernel
    for the AdaGrad step and for the plain eta0/sqrt(t) step alike:
    ``resolve_backend_and_build`` (behind ``solve`` and ``ShardedDSO``)
    picks it from the built grid, and ``solve`` runs it to
    ``sparse_jnp``'s trajectory.  Platform mocked as a TPU; the
    kernel runs in the interpreter."""
    from repro.data.synthetic import make_classification
    from repro.engine import solve
    from repro.engine.driver import resolve_backend_and_build
    from repro.obs import RunRecorder

    prob = make_classification(m=160, d=96, density=0.05, loss="hinge",
                               lam=1e-3, seed=4)
    kw = dict(p=4, epochs=3, eta0=0.5, use_adagrad=use_adagrad,
              eval_hook=None)
    want = solve(prob, backend="sparse_jnp", **kw)
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "_resolve_interpret", lambda interpret: True)
    be, _ = resolve_backend_and_build(prob, "auto", 4, 1)
    assert be.name == "sparse_pallas"
    rec = RunRecorder()
    got = solve(prob, backend="auto", obs=rec, **kw)
    setup = [e for e in rec.events
             if e["type"] == "span" and e["name"] == "solve_setup"]
    assert [e["attrs"] for e in setup] == [{"backend": "sparse_pallas"}]
    for a, b in ((got.w, want.w), (got.alpha, want.alpha)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.isfinite(a).all()
        assert np.abs(a - b).max() <= 1e-5 * max(np.abs(b).max(), 1.0)
