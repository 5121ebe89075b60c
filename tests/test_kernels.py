"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles in
ref.py, executed in interpret mode (Mosaic targets a real TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

K0 = jax.random.PRNGKey(0)


# -- staleness_agg -------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 3, 16, 2056])   # 2056: ragged row blocks
@pytest.mark.parametrize("n", [1024, 4096])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_staleness_agg_sweep(k, n, dtype):
    u = jax.random.normal(K0, (k, n), dtype)
    w = jax.random.uniform(jax.random.PRNGKey(1), (k,), jnp.float32)
    w = w / w.sum()
    out = ops.staleness_agg(u, w, interpret=True)
    expect = ref.staleness_agg(u, w)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


def test_staleness_agg_weights_delta():
    """Weight vector (1,0,...,0) must return the first update exactly."""
    u = jax.random.normal(K0, (4, 2048), jnp.float32)
    w = jnp.array([1.0, 0.0, 0.0, 0.0])
    out = ops.staleness_agg(u, w, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(u[0]), rtol=1e-6)


def test_aggregate_pytree_roundtrip():
    ups = [{"a": jax.random.normal(jax.random.PRNGKey(i), (37, 5)),
            "b": jax.random.normal(jax.random.PRNGKey(i + 9), (11,))}
           for i in range(3)]
    w = np.array([0.2, 0.5, 0.3], np.float32)
    out = ops.aggregate_pytree(ups, w, interpret=True)
    expect_a = sum(wi * np.asarray(u["a"]) for wi, u in zip(w, ups))
    np.testing.assert_allclose(np.asarray(out["a"]), expect_a, rtol=1e-5,
                               atol=1e-6)
    assert out["a"].shape == (37, 5) and out["b"].shape == (11,)


# -- quant8 ---------------------------------------------------------------------

@pytest.mark.parametrize("n_tiles", [1, 4])
def test_quant8_matches_ref(n_tiles):
    n = 8 * 256 * n_tiles
    x = jax.random.normal(K0, (n,), jnp.float32) * 3.0
    q, s = ops.quantize_q8(x, interpret=True)
    qr, sr = ref.quantize_q8(x)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    d = ops.dequantize_q8(q, s, interpret=True)
    np.testing.assert_allclose(np.asarray(d), np.asarray(ref.dequantize_q8(qr, sr)),
                               rtol=1e-6)


def test_quant8_error_bound():
    """Per-block error <= scale/2 = max|block| / 254."""
    x = jax.random.normal(jax.random.PRNGKey(3), (8 * 256,), jnp.float32)
    q, s = ops.quantize_q8(x, interpret=True)
    d = ops.dequantize_q8(q, s, interpret=True)
    err = np.abs(np.asarray(d) - np.asarray(x)).reshape(-1, 256)
    bound = np.asarray(s)[:, None] * 0.5 + 1e-7
    assert (err <= bound).all()


@pytest.mark.parametrize("n", [1, 255, 256, 257, 2048, 2049, 5000])
def test_quant8_arbitrary_n_round_trip(n):
    """N need not be tile-aligned: the pad-to-block is internal, outputs
    are trimmed, and zero padding never perturbs a block's max-abs scale
    — so tail-block values quantize exactly as in an aligned buffer."""
    x = jax.random.normal(jax.random.PRNGKey(n), (n,), jnp.float32) * 2.0
    q, s = ops.quantize_q8(x, interpret=True)
    nb = -(-n // 256)
    assert q.shape == (n,) and s.shape == (nb,)
    d = ops.dequantize_q8(q, s, interpret=True)
    assert d.shape == (n,)
    err = np.zeros(nb * 256, np.float32)
    err[:n] = np.abs(np.asarray(d) - np.asarray(x))
    bound = np.asarray(s)[:, None] * 0.5 + 1e-7
    assert (err.reshape(nb, 256) <= bound).all()
    # full prefix blocks must quantize identically to an aligned run
    n0 = (n // 256) * 256
    if n0:
        q0, s0 = ops.quantize_q8(x[:n0], interpret=True)
        np.testing.assert_array_equal(np.asarray(q[:n0]), np.asarray(q0))
        np.testing.assert_array_equal(np.asarray(s[:n0 // 256]),
                                      np.asarray(s0))


def test_compress_update_error_feedback():
    u = {"w": jax.random.normal(K0, (300, 7)), "b": jnp.ones((13,))}
    (q, s, meta), err = ops.compress_update(u, interpret=True)
    back = ops.decompress_update(q, s, meta, interpret=True)
    assert back["w"].shape == (300, 7) and back["b"].shape == (13,)
    # decompressed + error == original (error feedback is exact)
    flat_u = np.concatenate([np.asarray(u["b"]).ravel(),
                             np.asarray(u["w"]).ravel()])
    flat_b = np.concatenate([np.asarray(back["b"]).ravel(),
                             np.asarray(back["w"]).ravel()])
    np.testing.assert_allclose(flat_b + np.asarray(err), flat_u, atol=1e-5)


# -- fused_adam ------------------------------------------------------------------

@pytest.mark.parametrize("t", [1, 100])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_adam_sweep(t, dtype):
    n = 8 * 1024
    p = jax.random.normal(K0, (n,), dtype)
    m = jax.random.normal(jax.random.PRNGKey(1), (n,), jnp.float32) * 0.1
    v = jax.random.uniform(jax.random.PRNGKey(2), (n,), jnp.float32) * 0.01
    g = jax.random.normal(jax.random.PRNGKey(3), (n,), jnp.float32)
    po, mo, vo = ops.fused_adam(p, m, v, g, jnp.int32(t), lr=1e-3,
                                interpret=True)
    pr, mr, vr = ref.fused_adam(p, m, v, g, lr=1e-3, t=t)
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(po, np.float32),
                               np.asarray(pr, np.float32), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(mo), np.asarray(mr), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(vo), np.asarray(vr), rtol=1e-5,
                               atol=1e-6)


# -- flash attention --------------------------------------------------------------

@pytest.mark.parametrize("s,t", [(128, 128), (256, 128), (128, 256)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(s, t, causal):
    if causal and s > t:
        pytest.skip("causal requires S <= T in this harness")
    B, H, D = 1, 2, 64
    q = jax.random.normal(K0, (B, H, s, D), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, H, t, D), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, H, t, D), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=causal, interpret=True)
    expect = ref.flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_bf16():
    B, H, S, D = 1, 1, 128, 64
    q = jax.random.normal(K0, (B, H, S, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, H, S, D), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, H, S, D), jnp.bfloat16)
    out = ops.flash_attention(q, k, v, causal=True, interpret=True)
    expect = ref.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=3e-2, atol=3e-2)


# -- masked top-k (control-plane cohort selection) ----------------------------

@pytest.mark.parametrize("m,k,block", [
    (64, 5, 32),        # small fleets still hit the kernel via small blocks
    (1024, 1, 256),
    (3000, 17, 1024),   # ragged tail pads with -inf
    (4096, 100, 1024),
])
def test_masked_topk_pallas_matches_xla(m, k, block):
    rng = np.random.default_rng(m * 100 + k)
    s = jnp.asarray(rng.normal(size=m).astype(np.float32))
    v_x, i_x = ops.masked_topk(s, k, path="xla")
    v_p, i_p = ops.masked_topk(s, k, path="pallas", interpret=True,
                               block=block)
    np.testing.assert_array_equal(np.asarray(v_x), np.asarray(v_p))
    np.testing.assert_array_equal(np.asarray(i_x), np.asarray(i_p))


def test_masked_topk_masked_entries():
    """-inf-masked entries rank last and keep value -inf so the caller can
    filter invalid picks."""
    s = np.full(2048, -np.inf, np.float32)
    s[[5, 900, 1999]] = [3.0, 1.0, 2.0]
    v, i = ops.masked_topk(jnp.asarray(s), 8, path="pallas", interpret=True)
    v, i = np.asarray(v), np.asarray(i)
    assert list(i[:3]) == [5, 1999, 900]
    assert (v[3:] == -np.inf).all()


def test_masked_topk_ties_break_low_index():
    s = np.zeros(4096, np.float32)
    s[[7, 2000, 3000]] = 1.0              # equal scores across blocks
    for path in ("xla", "pallas"):
        _, i = ops.masked_topk(jnp.asarray(s), 3, path=path, interpret=True)
        assert list(np.asarray(i)) == [7, 2000, 3000]


def test_resolve_topk_path(monkeypatch):
    monkeypatch.delenv("REPRO_TOPK_PATH", raising=False)
    assert ops.resolve_topk_path("xla") == "xla"
    assert ops.resolve_topk_path("pallas") == "pallas"
    assert ops.resolve_topk_path(None) in ("xla", "pallas")  # auto: backend
    monkeypatch.setenv("REPRO_TOPK_PATH", "pallas")
    assert ops.resolve_topk_path(None) == "pallas"
    with pytest.raises(ValueError):
        ops.resolve_topk_path("mosaic")
