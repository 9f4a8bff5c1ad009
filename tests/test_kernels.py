"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp
oracles in repro.kernels.ref (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.gossip_mix import gossip_mix_pallas
from repro.kernels.mlstm_scan import mlstm_scan_pallas

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _attn_inputs(key, B, S, K, G, hd, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, K, G, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, K, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, K, hd), jnp.float32).astype(dtype)
    return q, k, v


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,K,G,hd,bq,bkv", [
    (1, 128, 1, 1, 64, 64, 64),
    (2, 256, 2, 2, 64, 128, 128),
    (1, 256, 4, 1, 128, 64, 128),
    (2, 128, 1, 4, 32, 32, 64),
])
def test_flash_attention_shapes_dtypes(B, S, K, G, hd, bq, bkv, dtype):
    q, k, v = _attn_inputs(jax.random.PRNGKey(B * S), B, S, K, G, hd, dtype)
    out = flash_attention_pallas(q, k, v, causal=True, window=None,
                                 block_q=bq, block_kv=bkv, interpret=True)
    expect = ref.attention_ref(q, k, v, causal=True, window=None)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("window", [32, 64, 100])
def test_flash_attention_sliding_window(window):
    q, k, v = _attn_inputs(jax.random.PRNGKey(7), 1, 256, 2, 2, 64, jnp.float32)
    out = flash_attention_pallas(q, k, v, causal=True, window=window,
                                 block_q=64, block_kv=64, interpret=True)
    expect = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5)


def test_flash_attention_matches_model_chunked_reference():
    """The model's chunked jnp attention and the Pallas kernel implement
    the same contract."""
    from repro.models.attention import chunked_attention

    q, k, v = _attn_inputs(jax.random.PRNGKey(3), 2, 256, 2, 2, 64, jnp.float32)
    pos = jnp.arange(256, dtype=jnp.int32)
    a = chunked_attention(q, k, v, pos, pos, causal=True, window=64)
    b = flash_attention_pallas(q, k, v, causal=True, window=64,
                               block_q=64, block_kv=64, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


@settings(max_examples=12, deadline=None)
@given(
    st.sampled_from([1, 2, 3]),           # K neighbours
    st.integers(1, 5),                    # size multiplier
    st.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_gossip_mix_property(k_extra, mult, dtype):
    K = k_extra + 1
    N = 1000 * mult + 13
    key = jax.random.PRNGKey(K * N)
    nb = jax.random.normal(key, (K, N), jnp.float32).astype(dtype)
    w = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(1), (K,)))
    out = gossip_mix_pallas(nb, w, block=512, interpret=True)
    expect = ref.gossip_mix_ref(nb, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_gossip_mix_convex_combination_preserves_constants():
    """Mixing identical replicas with a stochastic weight vector is the
    identity — the consensus fixed point."""
    K, N = 4, 5000
    w = jnp.array([0.25, 0.25, 0.25, 0.25])
    blocks = jnp.broadcast_to(jnp.arange(N, dtype=jnp.float32), (K, N))
    out = gossip_mix_pallas(blocks, w, block=1024, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.arange(N), rtol=1e-6)


@pytest.mark.parametrize("B,S,H,hd,chunk", [
    (1, 128, 2, 32, 32),
    (2, 256, 2, 64, 64),
    (1, 256, 4, 32, 128),
])
def test_mlstm_scan_vs_sequential_ref(B, S, H, hd, chunk):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (B, S, H, hd)) * 0.5
    k = jax.random.normal(ks[1], (B, S, H, hd)) * 0.5
    v = jax.random.normal(ks[2], (B, S, H, hd)) * 0.5
    li = jax.nn.log_sigmoid(jax.random.normal(ks[3], (B, S, H)))
    lf = jax.nn.log_sigmoid(jax.random.normal(ks[4], (B, S, H)) + 2.0)
    out = mlstm_scan_pallas(q, k, v, li, lf, chunk=chunk, interpret=True)
    expect = ref.mlstm_scan_ref(q, k, v, li, lf)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-4, rtol=2e-3)


def test_mlstm_kernel_matches_model_chunked_ref():
    from repro.models.ssm import mlstm_chunked_ref

    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    B, S, H, hd = 2, 256, 2, 32
    q = jax.random.normal(ks[0], (B, S, H, hd)) * 0.5
    k = jax.random.normal(ks[1], (B, S, H, hd)) * 0.5
    v = jax.random.normal(ks[2], (B, S, H, hd)) * 0.5
    li = jax.nn.log_sigmoid(jax.random.normal(ks[3], (B, S, H)))
    lf = jax.nn.log_sigmoid(jax.random.normal(ks[4], (B, S, H)) + 2.0)
    a = mlstm_scan_pallas(q, k, v, li, lf, chunk=64, interpret=True)
    b = mlstm_chunked_ref(q, k, v, li, lf, chunk=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-3)


def test_attention_chunked_equals_naive_small():
    """Model chunked attention == naive O(S^2) attention (both maskings)."""
    from repro.models.attention import chunked_attention, naive_attention

    q, k, v = _attn_inputs(jax.random.PRNGKey(5), 2, 96, 2, 2, 32, jnp.float32)
    pos = jnp.arange(96, dtype=jnp.int32)
    for window in (None, 17):
        a = chunked_attention(q, k, v, pos, pos, causal=True, window=window,
                              kv_block=32)
        b = naive_attention(q, k, v, pos, pos, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
