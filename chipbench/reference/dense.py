"""Decoder-only GQA transformer (internlm2), as the published model
defines it: pre-norm RMSNorm, rotary positions (rotate-half, base
``rope_theta``), causal softmax attention in which query head h reads
key/value head h // (heads / kv heads), a SwiGLU MLP, an untied LM head
and mean token cross-entropy.  Full S x S attention, nothing chunked.

Departures from arXiv:2403.17297: none in the equations.  InternLM2
packs q, k and v into one ``wqkv`` matrix; here they are three, which
is the same map with the columns regrouped.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import lm_layout, lm_loss, rms_norm


def layout(cfg):
    D, H, K, hd, F = (cfg["hidden_size"], cfg["num_attention_heads"],
                      cfg["num_key_value_heads"], cfg["head_dim"],
                      cfg["intermediate_size"])

    def block(kind):
        if kind != "attn":
            raise KeyError(kind)
        return {
            "ln1": ((D,), "ones", 1.0),
            "attn.wq": ((D, H * hd), "normal", D ** -0.5),
            "attn.wk": ((D, K * hd), "normal", D ** -0.5),
            "attn.wv": ((D, K * hd), "normal", D ** -0.5),
            "attn.wo": ((H * hd, D), "normal", (H * hd) ** -0.5),
            "ln2": ((D,), "ones", 1.0),
            "mlp.w_gate": ((D, F), "normal", D ** -0.5),
            "mlp.w_up": ((D, F), "normal", D ** -0.5),
            "mlp.w_down": ((F, D), "normal", F ** -0.5),
        }

    return lm_layout(cfg, block)


def layer_flops(cfg, kind, S):
    """Forward FLOPs of one ``attn`` layer over a sequence of ``S``: every
    matmul from its shapes; a causal query sees (S + 1) / 2 keys on
    average."""
    if kind != "attn":
        raise KeyError(f"no FLOP count for block kind {kind!r}")
    D, H, K, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["num_key_value_heads"], cfg["head_dim"])
    Te = (S + 1) / 2
    f = 2 * S * D * (H + 2 * K) * hd          # q, k, v projections
    f += 4 * S * Te * H * hd                   # q k^T and p v
    f += 2 * S * H * hd * D                    # output projection
    f += 6 * S * D * cfg["intermediate_size"]  # SwiGLU
    return f


def rope(x, theta):
    """Rotary embedding of x [B, S, heads, hd], rotate-half convention."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(p, cfg, x):
    B, S, _ = x.shape
    H, K, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = rope((x @ p["attn.wq"]).reshape(B, S, H, hd), cfg["rope_theta"])
    k = rope((x @ p["attn.wk"]).reshape(B, S, K, hd), cfg["rope_theta"])
    v = (x @ p["attn.wv"]).reshape(B, S, K, hd)
    k = jnp.repeat(k, H // K, axis=2)   # query head h reads kv head h // G
    v = jnp.repeat(v, H // K, axis=2)
    s = jnp.einsum("bshd,bthd->bhst", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    o = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(B, S, H * hd) @ p["attn.wo"]


def _block(cfg):
    eps = cfg["rms_norm_eps"]

    def block(p, kind, x):
        x = x + attention(p, cfg, rms_norm(x, p["ln1"], eps))
        h = rms_norm(x, p["ln2"], eps)
        return x + (jax.nn.silu(h @ p["mlp.w_gate"]) * (h @ p["mlp.w_up"])) @ p["mlp.w_down"]

    return block


def loss(params, cfg, tokens, labels):
    """Mean next-token cross-entropy of tokens/labels [B, S]."""
    return lm_loss(params, cfg, tokens, labels, _block(cfg))
