"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp
oracles in repro.kernels.ref (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import flash_attention as fa
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


def _rel_gap(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


# bf16 operands round at 2^-8 relative; the gaps of out, dq, dk and dv
# to the f32 reference stay within a few of those roundings
MXU_TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("mxu_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,K,G,hd,bq,bkv,window", [
    (1, 256, 2, 2, 64, 64, 64, None),
    (2, 256, 1, 1, 32, 128, 64, None),
    (1, 256, 1, 2, 32, 64, 128, None),
    (1, 256, 1, 2, 128, 128, 128, None),
    (1, 512, 1, 2, 32, 128, 128, 128),    # window of one block: 2 of 4 skipped
    (1, 512, 2, 1, 32, 128, 128, 128),
    (1, 256, 1, 2, 32, 64, 64, 100),      # band edge inside a block
    (1, 256, 1, 1, 32, 64, 32, 300),      # window longer than the sequence
])
def test_flash_attention_vjp_matches_reference(B, S, K, G, hd, bq, bkv, window,
                                               mxu_dtype):
    """Forward against naive attention, dq/dk/dv against autodiff of the
    model's chunked attention, both in f32; skipped blocks leave out only
    work of weight zero, so the windowed cases match as closely."""
    from repro.models.attention import chunked_attention, naive_attention

    q, k, v = _attn_inputs(jax.random.PRNGKey(S + bq + G), B, S, K, G, hd,
                           jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), q.shape)
    pos = jnp.arange(S, dtype=jnp.int32)

    def kernel(q, k, v):
        return flash_attention_pallas(q, k, v, causal=True, window=window,
                                      block_q=bq, block_kv=bkv,
                                      mxu_dtype=mxu_dtype, interpret=True)

    def chunked(q, k, v):
        return chunked_attention(q, k, v, pos, pos, causal=True, window=window,
                                 kv_block=64)

    def grads(attn):
        return jax.grad(lambda q, k, v: jnp.sum(attn(q, k, v) * w),
                        argnums=(0, 1, 2))(q, k, v)

    with jax.default_matmul_precision("highest"):
        want = naive_attention(q, k, v, pos, pos, causal=True, window=window)
        want_grads = grads(chunked)
    tol = MXU_TOL[mxu_dtype]
    assert _rel_gap(kernel(q, k, v), want) < tol
    for got, expect in zip(grads(kernel), want_grads):
        assert _rel_gap(got, expect) < tol


def _pairs_seen(q_blk, k_blk, bq, bkv, window):
    """How many of a tile's (query, key) pairs causal attention sees."""
    qp = np.arange(q_blk * bq, (q_blk + 1) * bq)[:, None]
    kp = np.arange(k_blk * bkv, (k_blk + 1) * bkv)[None, :]
    seen = kp <= qp
    if window is not None:
        seen &= qp - kp < window
    return int(seen.sum())


def _visits(spans):
    masked, plain = [], []
    for start, stop, is_masked in spans:
        (masked if is_masked else plain).extend(range(int(start), int(stop)))
    return masked, plain


@pytest.mark.parametrize("S,bq,bkv,window", [
    (1024, 128, 128, None), (1024, 128, 256, None), (1024, 256, 128, None),
    (1024, 128, 128, 128), (1024, 256, 128, 100), (1024, 128, 256, 300),
    (1024, 128, 128, 2000),
])
def test_flash_attention_visits_exactly_the_visible_blocks(S, bq, bkv, window):
    """Each loop visits every block holding a pair attention sees, once;
    only blocks the diagonal or the band's edge cuts are masked."""
    n_q, n_kv = S // bq, S // bkv
    full = bq * bkv
    for i in range(n_q):
        masked, plain = _visits(fa._kv_spans(i, bq, bkv, n_kv, True, window))
        seen = [_pairs_seen(i, j, bq, bkv, window) for j in range(n_kv)]
        assert sorted(masked + plain) == [j for j in range(n_kv) if seen[j]]
        assert plain == [j for j in range(n_kv) if seen[j] == full]
    for j in range(n_kv):
        masked, plain = _visits(fa._q_spans(j, bq, bkv, n_q, True, window))
        seen = [_pairs_seen(i, j, bq, bkv, window) for i in range(n_q)]
        assert sorted(masked + plain) == [i for i in range(n_q) if seen[i]]
        assert plain == [i for i in range(n_q) if seen[i] == full]


@pytest.mark.parametrize("S,causal,head_dim,backend,expect", [
    (2048, True, 128, "tpu", "pallas"),   # the benchmark cells' attention
    (4096, True, 128, "tpu", "pallas"),
    (2048, True, 128, "cpu", "chunked"),
    (2048, False, 128, "tpu", "chunked"),  # bidirectional encoder
    (2048, True, 64, "tpu", "chunked"),
    (2000, True, 128, "tpu", "chunked"),   # not a multiple of the block
    (32768, True, 128, "tpu", "chunked"),  # stripes beyond the VMEM budget
])
def test_attention_impl_picks_the_kernel_where_it_fits(S, causal, head_dim,
                                                       backend, expect):
    import dataclasses

    from repro.configs import get_config
    from repro.models.attention import attention_impl

    cfg = get_config("internlm2-1.8b")
    cfg = dataclasses.replace(cfg, head_dim=head_dim,
                              d_model=head_dim * cfg.n_heads)
    assert attention_impl(cfg, S, S, causal, None, backend) == expect
    assert attention_impl(cfg, S, S, causal, 512, backend) == expect


def test_attention_impls_counts_the_cells_layers():
    """The four attention layers of the benchmark's internlm2 take the
    kernel on a TPU and the jnp path elsewhere; an encoder's layers
    always keep the jnp path."""
    from repro.configs import get_config
    from repro.models.transformer import attention_impls

    cfg = get_config("internlm2-1.8b", n_layers=4)
    assert attention_impls(cfg, 2048, "tpu") == ["pallas"] * 4
    assert attention_impls(cfg, 2048, "cpu") == ["chunked"] * 4
    whisper = get_config("whisper-large-v3")
    impls = attention_impls(whisper, 2048, "tpu")
    assert impls[:whisper.encoder.n_layers] == ["chunked"] * whisper.encoder.n_layers


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
