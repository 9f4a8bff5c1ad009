"""§Perf features: banded SWA attention and the flash-style custom VJP
must be exact drop-ins for the naive chunked formulation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models.attention import (
    banded_swa_attention,
    chunked_attention,
    flash_attention_vjp,
    naive_attention,
)


def _inputs(key, B, S, K, G, hd):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, K, G, hd))
    k = jax.random.normal(ks[1], (B, S, K, hd))
    v = jax.random.normal(ks[2], (B, S, K, hd))
    return q, k, v


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 6), st.sampled_from([64, 100, 256]),
       st.sampled_from([64, 128]))
def test_banded_swa_matches_naive(seed, window, q_block):
    q, k, v = _inputs(jax.random.PRNGKey(seed), 1, 512, 2, 1, 32)
    pos = jnp.arange(512, dtype=jnp.int32)
    a = banded_swa_attention(q, k, v, pos, window=window, q_block=q_block)
    b = naive_attention(q, k, v, pos, pos, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


@pytest.mark.parametrize("window", [None, 48])
def test_flash_vjp_forward_matches_chunked(window):
    q, k, v = _inputs(jax.random.PRNGKey(0), 2, 128, 2, 2, 32)
    pos = jnp.arange(128, dtype=jnp.int32)
    a = flash_attention_vjp(q, k, v, pos, pos, True, window, 64)
    b = chunked_attention(q, k, v, pos, pos, causal=True, window=window,
                          kv_block=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


@pytest.mark.parametrize("window", [None, 48])
def test_flash_vjp_grads_match_autodiff(window):
    q, k, v = _inputs(jax.random.PRNGKey(1), 2, 128, 2, 2, 32)
    pos = jnp.arange(128, dtype=jnp.int32)

    def f_ref(q, k, v):
        return (chunked_attention(q, k, v, pos, pos, causal=True,
                                  window=window, kv_block=64) ** 2).sum()

    def f_new(q, k, v):
        return (flash_attention_vjp(q, k, v, pos, pos, True, window, 64) ** 2).sum()

    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    g_new = jax.grad(f_new, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_new):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_model_forward_same_with_flash_vjp():
    """End-to-end: enabling flash_vjp must not change the model output."""
    from repro.configs import get_config
    from repro.models import init_params, transformer as T
    import dataclasses

    cfg = get_config("h2o-danube-1.8b").reduced()
    params = init_params(jax.random.PRNGKey(0), T.model_specs(cfg))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                                cfg.vocab_size)
    base, _ = T.forward(params, cfg, tokens)
    cfg2 = dataclasses.replace(cfg, flash_vjp=True)
    new, _ = T.forward(params, cfg2, tokens)
    np.testing.assert_allclose(np.asarray(base), np.asarray(new),
                               atol=2e-3, rtol=2e-3)


def test_model_forward_same_with_banded_swa():
    from repro.configs import get_config
    from repro.models import init_params, transformer as T
    import dataclasses

    cfg = get_config("h2o-danube-1.8b").reduced()
    assert cfg.sliding_window == 32
    params = init_params(jax.random.PRNGKey(0), T.model_specs(cfg))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 128), 0,
                                cfg.vocab_size)
    base, _ = T.forward(params, cfg, tokens)
    cfg2 = dataclasses.replace(cfg, banded_swa=True)
    new, _ = T.forward(params, cfg2, tokens)
    np.testing.assert_allclose(np.asarray(base), np.asarray(new),
                               atol=2e-3, rtol=2e-3)
