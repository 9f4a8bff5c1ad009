"""Layers the references share, written out plainly in float32."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def cross_entropy(logits, labels):
    """Mean over every position of -log softmax(logits)[label]."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def lm_layout(cfg, blocks):
    """Embedding, ``blocks`` (path suffix -> (shape, init, scale)) for
    each layer kind, final norm and untied LM head."""
    D, V = cfg["hidden_size"], (cfg["vocab_size"] + 127) // 128 * 128
    out = {"embed": ((V, D), "normal", D ** -0.5)}
    pattern = list(cfg["layer_pattern"])
    for i in range(cfg["num_hidden_layers"]):
        for name, spec in blocks(pattern[i % len(pattern)]).items():
            out[f"layers.{i}.{name}"] = spec
    out["final_ln"] = ((D,), "ones", 1.0)
    out["lm_head"] = ((D, V), "normal", D ** -0.5)
    return out


def lm_loss(params, cfg, tokens, labels, block):
    """Embed, run ``block(p, kind, x)`` per layer, norm, head, loss."""
    x = params["embed"][tokens]
    pattern = list(cfg["layer_pattern"])
    for i in range(cfg["num_hidden_layers"]):
        p = {k[len(f"layers.{i}."):]: v for k, v in params.items()
             if k.startswith(f"layers.{i}.")}
        # recomputed in the backward pass, to fit one chip beside the
        # parameters, momentum and gradient; the values are the same
        x = jax.checkpoint(block, static_argnums=(1,))(p, pattern[i % len(pattern)], x)
    x = rms_norm(x, params["final_ln"], cfg["rms_norm_eps"])
    logits = (x @ params["lm_head"])[..., : cfg["vocab_size"]]
    return cross_entropy(logits, labels)
