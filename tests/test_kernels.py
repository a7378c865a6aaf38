"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.compression import QTILE
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


@pytest.mark.parametrize("B,Sq,Sk,Hkv,G,hd,dt", [
    (2, 256, 256, 2, 2, 64, jnp.float32),
    (1, 512, 512, 1, 4, 128, jnp.bfloat16),
    (2, 256, 256, 4, 1, 64, jnp.float32),
    (1, 256, 256, 2, 2, 128, jnp.bfloat16),
    (1, 128, 128, 1, 1, 64, jnp.float32),
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_kernel_matches_oracle(B, Sq, Sk, Hkv, G, hd, dt, causal):
    H = Hkv * G
    q = jax.random.normal(KEY, (B, Sq, H, hd), dt)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, Sk, Hkv, hd), dt)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, Sk, Hkv, hd), dt)
    o = ops.flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    o_ref = ref.mha_reference(q, k, v, causal=causal)
    tol = 2e-2 if dt == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32), atol=tol)


@pytest.mark.parametrize("window", [64, 128])
def test_flash_kernel_sliding_window(window):
    B, S, Hkv, G, hd = 1, 256, 2, 2, 64
    q = jax.random.normal(KEY, (B, S, Hkv * G, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, Hkv, hd), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, Hkv, hd), jnp.float32)
    o = ops.flash_attention(q, k, v, causal=True, window=window,
                            block_q=128, block_k=128)
    o_ref = ref.mha_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=3e-5)


def test_flash_kernel_matches_model_flash_vjp_fwd():
    """The jnp custom-VJP flash in models.layers and the Pallas kernel are
    the same algorithm — cross-validate them directly."""
    from repro.models.layers import _flash
    B, S, Hkv, G, hd = 1, 256, 2, 2, 64
    q = jax.random.normal(KEY, (B, S, Hkv * G, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, Hkv, hd), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(KEY, 4), (B, S, Hkv, hd), jnp.float32)
    o_pallas = ops.flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    o_jnp = _flash(q, k, v, True, None, 128, 128, 0)
    np.testing.assert_allclose(np.asarray(o_pallas), np.asarray(o_jnp), atol=3e-5)


@settings(deadline=None, max_examples=20)
@given(st.integers(1, 6), st.floats(0.1, 100.0))
def test_quant_roundtrip_error_bound(ntiles, scale):
    """Property: blockwise int8 roundtrip error <= amax/127 per block half-ulp."""
    n = 256 * 32 * ntiles
    x = np.asarray(jax.random.normal(KEY, (n,), jnp.float32)) * scale
    q, s, pad = ops.quantize_int8(jnp.asarray(x))
    xd = np.asarray(ops.dequantize_int8(q, s, pad))
    blocks = x.reshape(-1, 256)
    bound = np.abs(blocks).max(axis=1, keepdims=True) / 127.0 * 0.5 + 1e-9
    assert (np.abs(xd.reshape(-1, 256) - blocks) <= bound + 1e-6).all()


def test_quant_matches_reference_exactly():
    x = jax.random.normal(KEY, (QTILE * 2,), jnp.float32) * 5
    q, s, pad = ops.quantize_int8(x)
    qr, sr = ref.quantize_int8_reference(x)
    assert pad == 0
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)


def test_quant_padding_path():
    x = jax.random.normal(KEY, (1000,), jnp.float32)
    q, s, pad = ops.quantize_int8(x)
    assert pad == QTILE - 1000
    xd = ops.dequantize_int8(q, s, pad)
    assert xd.shape == (1000,)
    assert float(jnp.abs(xd - x).max()) < 0.05


@pytest.mark.parametrize("B,S,H,hd,chunk", [
    (2, 64, 2, 16, 16), (1, 128, 4, 32, 16), (1, 64, 1, 64, 16),
])
def test_wkv_kernel_matches_chunk_scan(B, S, H, hd, chunk):
    """Pallas WKV kernel vs the jnp chunked-recurrence oracle."""
    from repro.kernels.wkv import wkv_chunked
    from repro.models.layers import _wkv_chunk_scan
    r = jax.random.normal(KEY, (B, S, H, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, H, hd), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, H, hd), jnp.float32)
    w = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(KEY, 3),
                                         (B, S, H, hd))) * 0.6 + 0.39
    u = jax.random.normal(jax.random.fold_in(KEY, 4), (H, hd), jnp.float32) * 0.5
    o_kernel = wkv_chunked(r, k, v, w, u, chunk=chunk)
    o_ref, _ = _wkv_chunk_scan(r, k, v, w, u, chunk)
    np.testing.assert_allclose(np.asarray(o_kernel), np.asarray(o_ref),
                               atol=1e-4)


# ---------------------------------------------------------------------- #
# Flash backward (Pallas custom-VJP) vs the jnp VJP oracle
# ---------------------------------------------------------------------- #

@pytest.mark.parametrize("B,Sq,Sk,Hkv,G,hd,causal,window,q_offset", [
    (1, 256, 256, 2, 2, 32, True, None, 0),      # GQA causal
    (1, 256, 256, 1, 1, 64, False, None, 0),     # full attention
    (1, 256, 256, 2, 1, 32, True, 64, 0),        # sliding window
    (1, 128, 256, 2, 2, 32, True, None, 128),    # Sq != Sk, offset (decode)
])
def test_flash_bwd_matches_jnp_vjp(B, Sq, Sk, Hkv, G, hd, causal, window,
                                   q_offset):
    """The Pallas backward (dq/dk/dv kernels behind jax.custom_vjp) against
    the blockwise-recompute jnp VJP in models.layers — same algorithm, so
    the grads should agree to float32 roundoff."""
    from repro.kernels import flash_attention as fa
    from repro.models.layers import _flash
    H = Hkv * G
    q = jax.random.normal(KEY, (B, Sq, H, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 5), (B, Sk, Hkv, hd), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(KEY, 6), (B, Sk, Hkv, hd), jnp.float32)

    def lp(q, k, v):
        return jnp.sum(jnp.sin(fa.flash_attention(
            q, k, v, causal, window, 64, 64, q_offset, None)))

    def lj(q, k, v):
        return jnp.sum(jnp.sin(_flash(q, k, v, causal, window, 64, 64,
                                      q_offset)))

    gp = jax.grad(lp, argnums=(0, 1, 2))(q, k, v)
    gj = jax.grad(lj, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), gp, gj):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   err_msg=name)


def test_flash_fwd_lse_matches_jnp():
    """fwd returns the log-sum-exp the backward recompute depends on — its
    layout (B,Hkv,G,Sq) and values must match the jnp online softmax."""
    from repro.kernels import flash_attention as fa
    from repro.models.layers import _flash_fwd_impl
    B, S, Hkv, G, hd = 1, 256, 2, 2, 64
    q = jax.random.normal(KEY, (B, S, Hkv * G, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 7), (B, S, Hkv, hd), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(KEY, 8), (B, S, Hkv, hd), jnp.float32)
    o, lse = fa.flash_attention_fwd(q, k, v, causal=True, block_q=64,
                                    block_k=64)
    oj, lsej = _flash_fwd_impl(q, k, v, True, None, 64, 64, 0)
    assert lse.shape == lsej.shape == (B, Hkv, G, S)
    np.testing.assert_allclose(np.asarray(o), np.asarray(oj), atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lsej), atol=1e-5)


def test_chunked_attention_impl_switch():
    """impl='pallas' routes chunked_attention through the Pallas kernels
    (fwd AND bwd) and must match impl='jnp' in both."""
    from repro.models.layers import chunked_attention
    B, S, Hkv, G, hd = 1, 256, 2, 2, 32
    q = jax.random.normal(KEY, (B, S, Hkv * G, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 9), (B, S, Hkv, hd), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(KEY, 10), (B, S, Hkv, hd), jnp.float32)

    def loss(impl):
        return lambda q: jnp.sum(jnp.sin(chunked_attention(
            q, k, v, chunk_q=128, chunk_k=128, impl=impl)))

    op = chunked_attention(q, k, v, chunk_q=128, chunk_k=128, impl="pallas")
    oj = chunked_attention(q, k, v, chunk_q=128, chunk_k=128, impl="jnp")
    np.testing.assert_allclose(np.asarray(op), np.asarray(oj), atol=1e-5)
    gp = jax.grad(loss("pallas"))(q)
    gj = jax.grad(loss("jnp"))(q)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gj), atol=2e-5)
    with pytest.raises(ValueError):
        chunked_attention(q, k, v, chunk_q=128, chunk_k=128, impl="bogus")


# ---------------------------------------------------------------------- #
# Fused quantise + error feedback
# ---------------------------------------------------------------------- #

@settings(deadline=None, max_examples=15)
@given(st.integers(1, 3), st.integers(0, 100), st.floats(0.05, 50.0))
def test_quantize_ef_fused_bitidentical_to_two_pass(ntiles, off, scale):
    """Property: the fused kernel's (q, scales, residual) are BIT-identical
    to quantise(x+ef) / dequantise / subtract through the same kernels —
    fusion removes HBM round trips, not a single bit of the arithmetic."""
    n = QTILE * ntiles - off
    x = jax.random.normal(jax.random.fold_in(KEY, n), (n,), jnp.float32) * scale
    ef = jax.random.normal(jax.random.fold_in(KEY, n + 1), (n,), jnp.float32) * 1e-3
    qf, sf, rf, pad = ops.quantize_ef_int8(x, ef)
    q2, s2, pad2 = ops.quantize_int8(x + ef)
    assert pad == pad2 == off % QTILE
    r2 = (x + ef) - ops.dequantize_int8(q2, s2, pad2)
    np.testing.assert_array_equal(np.asarray(qf), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(sf), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(rf), np.asarray(r2))


def test_apply_error_feedback_kernel_matches_jnp():
    """Kernel vs pure-jnp EF update: the corrected gradient is bit-identical
    (both compute g+ef in jnp); the residual agrees to 1 ulp (the jit'd
    kernel divides by 127 via reciprocal, the eager path by true division)."""
    from repro.core import compression
    for n in (QTILE, 4096, 333):
        g = jax.random.normal(jax.random.fold_in(KEY, n), (n,), jnp.float32)
        ef = jax.random.normal(jax.random.fold_in(KEY, n + 1), (n,), jnp.float32) * 1e-3
        gk, rk = compression.apply_error_feedback(g, ef, use_kernel=True)
        gj, rj = compression.apply_error_feedback(g, ef, use_kernel=False)
        np.testing.assert_array_equal(np.asarray(gk), np.asarray(gj))
        np.testing.assert_allclose(np.asarray(rk), np.asarray(rj), atol=1e-6)
        assert rk.shape == rj.shape == (n,)


def test_quant_constants_single_source():
    """The tiling constants live in core.compression; every consumer must
    read the same objects (satellite: no BLOCK/QBLOCK/TILE drift)."""
    from repro.core import compression
    from repro.kernels import quant
    assert quant.QBLOCK == ref.QBLOCK == compression.BLOCK
    assert quant.TILE == compression.TILE
    assert quant.QTILE == compression.QTILE == compression.BLOCK * compression.TILE
    assert compression.WIRE_BYTES_PER_ELEM == 1.0 + 4.0 / compression.BLOCK


def test_pad_to_block():
    from repro.core import compression
    p, pad = compression.pad_to_block(jnp.ones(5), 8)
    assert p.shape == (8,) and pad == 3
    assert float(p[5:].sum()) == 0.0
    p, pad = compression.pad_to_block(jnp.ones(8), 8)
    assert p.shape == (8,) and pad == 0
    with pytest.raises(ValueError):
        compression.pad_to_block(jnp.ones((2, 3)), 8)


def test_resolve_interpret_auto_detect():
    from repro.kernels.backend import on_tpu, resolve_interpret
    assert on_tpu() == (jax.default_backend() == "tpu")
    assert resolve_interpret(None) == (not on_tpu())
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


def test_compressed_psum_use_kernel_validation():
    from repro.core import compression
    with pytest.raises(ValueError):
        compression._resolve_use_kernel(True, 128)   # kernel tiled for BLOCK
    assert compression._resolve_use_kernel(False, 128) is False
    assert compression._resolve_use_kernel(None, 128) is False
