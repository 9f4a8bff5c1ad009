"""Model assembly: builds the parameter spec tree from a ModelConfig,
and provides ``forward`` (training), ``prefill`` and ``decode_step``
(serving) for every supported block kind — dense GQA/SWA, MoE, MLA,
mLSTM/sLSTM, Hymba hybrid, Whisper encoder-decoder, VLM prefix."""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .params import ParamSpec
from . import attention as A
from . import moe as MOE
from . import ssm as SSM
from . import hybrid as HY
from .act_sharding import constrain_residual
from .layers import (
    embed_tokens,
    gelu_mlp,
    rms_norm,
    sinusoidal_positions,
    softmax_cross_entropy,
    swiglu,
)

AUDIO_FRONTEND_DIM = 128   # mel-bin stub features (whisper carve-out)
VISION_FRONTEND_DIM = 1024  # ViT patch-embedding stub features (VLM carve-out)


# ---------------------------------------------------------------------------
# parameter spec tree


def mlp_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    D, F = cfg.d_model, cfg.d_ff
    s = D ** -0.5
    if cfg.mlp_variant == "gelu":
        return {
            "w_up": ParamSpec((D, F), ("embed", "ffn"), s),
            "b_up": ParamSpec((F,), ("ffn",), 0.0, init="zeros"),
            "w_down": ParamSpec((F, D), ("ffn", "embed"), F ** -0.5),
            "b_down": ParamSpec((D,), ("embed",), 0.0, init="zeros"),
        }
    return {
        "w_gate": ParamSpec((D, F), ("embed", "ffn"), s),
        "w_up": ParamSpec((D, F), ("embed", "ffn"), s),
        "w_down": ParamSpec((F, D), ("ffn", "embed"), F ** -0.5),
    }


def block_specs(cfg: ModelConfig, kind: str) -> Dict[str, Any]:
    D = cfg.d_model
    ln = lambda: ParamSpec((D,), ("embed",), 1.0, init="ones")
    if kind == "attn":
        return {"ln1": ln(), "attn": A.attn_specs(cfg), "ln2": ln(), "mlp": mlp_specs(cfg)}
    if kind == "attn_moe":
        return {"ln1": ln(), "attn": A.attn_specs(cfg), "ln2": ln(), "moe": MOE.moe_specs(cfg)}
    if kind == "mla":
        return {"ln1": ln(), "attn": A.mla_specs(cfg), "ln2": ln(), "mlp": mlp_specs(cfg)}
    if kind == "mla_moe":
        return {"ln1": ln(), "attn": A.mla_specs(cfg), "ln2": ln(), "moe": MOE.moe_specs(cfg)}
    if kind == "mlstm":
        return {"ln1": ln(), "mlstm": SSM.mlstm_specs(cfg)}
    if kind == "slstm":
        return {"ln1": ln(), "slstm": SSM.slstm_specs(cfg)}
    if kind == "hymba":
        return {"ln1": ln(), "hymba": HY.hymba_specs(cfg), "ln2": ln(), "mlp": mlp_specs(cfg)}
    if kind == "xattn":  # whisper decoder block
        return {
            "ln1": ln(),
            "attn": A.attn_specs(cfg),
            "lnx": ln(),
            "xattn": A.cross_attn_specs(cfg),
            "ln2": ln(),
            "mlp": mlp_specs(cfg),
        }
    raise KeyError(f"unknown block kind {kind!r}")


def model_specs(cfg: ModelConfig) -> Dict[str, Any]:
    D, V = cfg.d_model, cfg.padded_vocab_size
    specs: Dict[str, Any] = {
        "embed": ParamSpec((V, D), ("vocab", "embed"), 1.0 / (D ** 0.5)),
        "layers": [
            block_specs(cfg, "xattn" if cfg.is_encdec and k == "attn" else k)
            for k in cfg.block_pattern
        ],
        "final_ln": ParamSpec((D,), ("embed",), 1.0, init="ones"),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = ParamSpec((D, V), ("embed", "vocab"), D ** -0.5)
    if cfg.is_encdec:
        enc = cfg.encoder
        specs["frontend"] = ParamSpec(
            (AUDIO_FRONTEND_DIM, D), (None, "embed"), AUDIO_FRONTEND_DIM ** -0.5
        )
        specs["enc_layers"] = [block_specs(cfg, "attn") for _ in range(enc.n_layers)]
        specs["enc_final_ln"] = ParamSpec((D,), ("embed",), 1.0, init="ones")
    if cfg.vision_prefix_len:
        specs["vision_proj"] = ParamSpec(
            (VISION_FRONTEND_DIM, D), (None, "embed"), VISION_FRONTEND_DIM ** -0.5
        )
    return specs


# ---------------------------------------------------------------------------
# forward (training / prefill)


def _block_forward(p, cfg: ModelConfig, kind: str, layer: int, x, positions,
                   enc_out=None) -> Tuple[jax.Array, jax.Array]:
    """Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if kind in ("attn", "attn_moe"):
        window = cfg.sliding_window if cfg.layer_uses_window(layer) else None
        h = A.attn_forward(p["attn"], cfg, rms_norm(x, p["ln1"], cfg.norm_eps),
                           positions, causal=True, window=window)
        x = x + h
    elif kind in ("mla", "mla_moe"):
        h = A.mla_forward(p["attn"], cfg, rms_norm(x, p["ln1"], cfg.norm_eps), positions)
        x = x + h
    elif kind == "xattn":
        h = A.attn_forward(p["attn"], cfg, rms_norm(x, p["ln1"], cfg.norm_eps),
                           positions, causal=True, window=None)
        x = x + h
        hx = A.cross_attn_forward(p["xattn"], cfg, rms_norm(x, p["lnx"], cfg.norm_eps), enc_out)
        x = x + hx
    elif kind == "mlstm":
        h = SSM.mlstm_forward(p["mlstm"], cfg, rms_norm(x, p["ln1"], cfg.norm_eps))
        return x + h, aux
    elif kind == "slstm":
        h = SSM.slstm_forward(p["slstm"], cfg, rms_norm(x, p["ln1"], cfg.norm_eps))
        return x + h, aux
    elif kind == "hymba":
        h = HY.hymba_forward(p["hymba"], cfg, rms_norm(x, p["ln1"], cfg.norm_eps),
                             positions, layer)
        x = x + h
    else:
        raise KeyError(kind)
    # FFN half
    xin = rms_norm(x, p["ln2"], cfg.norm_eps)
    if kind in ("attn_moe", "mla_moe"):
        h, aux = MOE.moe_forward(p["moe"], cfg, xin)
    elif cfg.mlp_variant == "gelu":
        h = gelu_mlp(xin, p["mlp"]["w_up"], p["mlp"]["b_up"],
                     p["mlp"]["w_down"], p["mlp"]["b_down"])
    else:
        h = swiglu(xin, p["mlp"]["w_gate"], p["mlp"]["w_up"], p["mlp"]["w_down"])
    return x + h, aux


def encode(params, cfg: ModelConfig, frames: jax.Array) -> jax.Array:
    """Whisper encoder over stubbed frame features [B, T_enc, 128]."""
    x = frames @ params["frontend"]
    T = x.shape[1]
    x = x + sinusoidal_positions(T, cfg.d_model).astype(x.dtype)
    x = constrain_residual(x)
    positions = jnp.arange(T, dtype=jnp.int32)

    def enc_block(x, p):
        h = A.attn_forward(p["attn"], cfg, rms_norm(x, p["ln1"], cfg.norm_eps),
                           positions, causal=False, window=None)
        x = x + h
        xin = rms_norm(x, p["ln2"], cfg.norm_eps)
        if cfg.mlp_variant == "gelu":
            h = gelu_mlp(xin, p["mlp"]["w_up"], p["mlp"]["b_up"],
                         p["mlp"]["w_down"], p["mlp"]["b_down"])
        else:
            h = swiglu(xin, p["mlp"]["w_gate"], p["mlp"]["w_up"], p["mlp"]["w_down"])
        return x + h

    for li, p in enumerate(params["enc_layers"]):
        blk = jax.checkpoint(enc_block) if cfg.remat else enc_block
        x = constrain_residual(blk(x, p))
    return rms_norm(x, params["enc_final_ln"], cfg.norm_eps)


def forward(
    params,
    cfg: ModelConfig,
    tokens: jax.Array,                 # [B, S]
    *,
    enc_frames: Optional[jax.Array] = None,   # [B, T_enc, 128] (audio stub)
    vision_embeds: Optional[jax.Array] = None,  # [B, P, 1024] (VLM stub)
) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward.  Returns (logits [B,S,V], aux_loss)."""
    B, S = tokens.shape
    x = embed_tokens(params["embed"], tokens)
    x = constrain_residual(x)
    if cfg.vision_prefix_len:
        assert vision_embeds is not None
        prefix = vision_embeds @ params["vision_proj"]
        x = jnp.concatenate([prefix.astype(x.dtype), x], axis=1)
    Sfull = x.shape[1]
    positions = jnp.arange(Sfull, dtype=jnp.int32)
    enc_out = None
    if cfg.is_encdec:
        assert enc_frames is not None
        enc_out = encode(params, cfg, enc_frames)

    def run_block(x, p, kind, layer):
        k = "xattn" if cfg.is_encdec and kind == "attn" else kind
        return _block_forward(p, cfg, k, layer, x, positions, enc_out)

    aux_total = jnp.zeros((), jnp.float32)
    for layer, (p, kind) in enumerate(zip(params["layers"], cfg.block_pattern)):
        blk = run_block
        if cfg.remat:
            blk = jax.checkpoint(run_block, static_argnums=(2, 3))
        x, aux = blk(x, p, kind, layer)
        x = constrain_residual(x)
        aux_total = aux_total + aux
    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    if cfg.vision_prefix_len:
        x = x[:, cfg.vision_prefix_len :]
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head)[..., : cfg.vocab_size]
    return logits, aux_total


def attention_impls(cfg: ModelConfig, seq_len: int, backend: str) -> List[str]:
    """:func:`attention.attention_impl` of every self-attention call a
    :func:`forward` of ``seq_len`` tokens makes on ``backend``: the
    encoder's layers, then each decoder layer that attends."""
    impls = []
    if cfg.is_encdec:
        T = cfg.encoder.seq_len
        impls += [A.attention_impl(cfg, T, T, False, None, backend)] * cfg.encoder.n_layers
    S = seq_len + cfg.vision_prefix_len
    for layer, kind in enumerate(cfg.block_pattern):
        if kind in ("attn", "attn_moe", "hymba"):
            window = cfg.sliding_window if cfg.layer_uses_window(layer) else None
            impls.append(A.attention_impl(cfg, S, S, True, window, backend))
    return impls


def loss_fn(params, cfg: ModelConfig, batch: Dict[str, jax.Array]) -> jax.Array:
    logits, aux = forward(
        params, cfg, batch["tokens"],
        enc_frames=batch.get("enc_frames"),
        vision_embeds=batch.get("vision_embeds"),
    )
    return softmax_cross_entropy(logits, batch["labels"]) + aux


# ---------------------------------------------------------------------------
# serving: cache init / prefill / decode


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    caches: List[Any] = []
    for layer, kind in enumerate(cfg.block_pattern):
        if kind in ("attn", "attn_moe"):
            window = cfg.sliding_window if cfg.layer_uses_window(layer) else None
            c = A.init_kv_cache(cfg, batch, max_len, window, dtype)
            if cfg.is_encdec:
                c = {"kv": c, "xk": None, "xv": None}  # filled at prefill
            caches.append(c)
        elif kind in ("mla", "mla_moe"):
            caches.append(A.init_mla_cache(cfg, batch, max_len, dtype))
        elif kind == "mlstm":
            caches.append(SSM.init_mlstm_state(cfg, batch))
        elif kind == "slstm":
            caches.append(SSM.init_slstm_state(cfg, batch))
        elif kind == "hymba":
            caches.append(HY.init_hymba_cache(cfg, batch, max_len, layer, dtype))
        else:
            raise KeyError(kind)
    return caches


def decode_step(
    params,
    cfg: ModelConfig,
    token: jax.Array,      # [B] int32
    cache,
    position: jax.Array,   # scalar int32
    *,
    enc_out: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Any]:
    """One-token decode; returns (logits [B,V], new_cache)."""
    B = token.shape[0]
    x = embed_tokens(params["embed"], token[:, None])  # [B,1,D]
    x = constrain_residual(x)
    new_cache = []
    for layer, (p, kind, c) in enumerate(zip(params["layers"], cfg.block_pattern, cache)):
        if cfg.is_encdec and kind == "attn":
            h, kv = A.attn_decode(p["attn"], cfg, rms_norm(x, p["ln1"], cfg.norm_eps),
                                  c["kv"], position, window=None)
            x = x + h
            # cross attention against cached encoder K/V
            hx = _cross_decode(p["xattn"], cfg, rms_norm(x, p["lnx"], cfg.norm_eps),
                               c["xk"], c["xv"])
            x = x + hx
            new_cache.append({"kv": kv, "xk": c["xk"], "xv": c["xv"]})
        elif kind in ("attn", "attn_moe"):
            window = cfg.sliding_window if cfg.layer_uses_window(layer) else None
            h, kv = A.attn_decode(p["attn"], cfg, rms_norm(x, p["ln1"], cfg.norm_eps),
                                  c, position, window=window)
            x = x + h
            new_cache.append(kv)
        elif kind in ("mla", "mla_moe"):
            h, kv = A.mla_decode(p["attn"], cfg, rms_norm(x, p["ln1"], cfg.norm_eps),
                                 c, position)
            x = x + h
            new_cache.append(kv)
        elif kind == "mlstm":
            h, st = SSM.mlstm_decode(p["mlstm"], cfg, rms_norm(x, p["ln1"], cfg.norm_eps), c)
            x = x + h
            new_cache.append(st)
            continue
        elif kind == "slstm":
            h, st = SSM.slstm_decode(p["slstm"], cfg, rms_norm(x, p["ln1"], cfg.norm_eps), c)
            x = x + h
            new_cache.append(st)
            continue
        elif kind == "hymba":
            h, hc = HY.hymba_decode(p["hymba"], cfg, rms_norm(x, p["ln1"], cfg.norm_eps),
                                    c, position, layer)
            x = x + h
            new_cache.append(hc)
        else:
            raise KeyError(kind)
        # FFN half
        xin = rms_norm(x, p["ln2"], cfg.norm_eps)
        if kind in ("attn_moe", "mla_moe"):
            h, _ = MOE.moe_forward(p["moe"], cfg, xin)
        elif cfg.mlp_variant == "gelu":
            h = gelu_mlp(xin, p["mlp"]["w_up"], p["mlp"]["b_up"],
                         p["mlp"]["w_down"], p["mlp"]["b_down"])
        else:
            h = swiglu(xin, p["mlp"]["w_gate"], p["mlp"]["w_up"], p["mlp"]["w_down"])
        x = x + h
    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head)[:, 0, : cfg.vocab_size]
    return logits, new_cache


def prefill(
    params,
    cfg: ModelConfig,
    tokens: jax.Array,     # [B, S]
    max_len: int,
    *,
    cache_dtype=jnp.bfloat16,
    enc_frames: Optional[jax.Array] = None,
    vision_embeds: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Any]:
    """Serving prefill: full forward + populate the serving cache.

    Returns (last-token logits [B, V], cache ready for decode at position
    S).  Attention caches are written via scatter into the (ring) buffers;
    recurrent blocks return their final state directly.
    """
    B, S = tokens.shape
    x = embed_tokens(params["embed"], tokens)
    x = constrain_residual(x)
    if cfg.vision_prefix_len:
        assert vision_embeds is not None
        prefix = vision_embeds @ params["vision_proj"]
        x = jnp.concatenate([prefix.astype(x.dtype), x], axis=1)
    Sfull = x.shape[1]
    positions = jnp.arange(Sfull, dtype=jnp.int32)
    enc_out = None
    if cfg.is_encdec:
        assert enc_frames is not None
        enc_out = encode(params, cfg, enc_frames)

    cache = init_cache(cfg, B, max_len, cache_dtype)
    new_cache: List[Any] = []
    for layer, (p, kind, c) in enumerate(zip(params["layers"], cfg.block_pattern, cache)):
        if cfg.is_encdec and kind == "attn":
            h, (k, v) = A.attn_forward(
                p["attn"], cfg, rms_norm(x, p["ln1"], cfg.norm_eps), positions,
                causal=True, window=None, return_kv=True)
            x = x + h
            hx = A.cross_attn_forward(p["xattn"], cfg,
                                      rms_norm(x, p["lnx"], cfg.norm_eps), enc_out)
            x = x + hx
            kv = A.fill_kv_cache(cfg, c["kv"], k, v, positions, None)
            H, hd = cfg.n_heads, cfg.head_dim
            T = enc_out.shape[1]
            xk = (enc_out @ p["xattn"]["wk"]).reshape(B, T, H, hd)
            xv = (enc_out @ p["xattn"]["wv"]).reshape(B, T, H, hd)
            new_cache.append({"kv": kv, "xk": xk, "xv": xv})
        elif kind in ("attn", "attn_moe"):
            window = cfg.sliding_window if cfg.layer_uses_window(layer) else None
            h, (k, v) = A.attn_forward(
                p["attn"], cfg, rms_norm(x, p["ln1"], cfg.norm_eps), positions,
                causal=True, window=window, return_kv=True)
            x = x + h
            new_cache.append(A.fill_kv_cache(cfg, c, k, v, positions, window))
        elif kind in ("mla", "mla_moe"):
            h, (c_kv, k_rope) = A.mla_forward(
                p["attn"], cfg, rms_norm(x, p["ln1"], cfg.norm_eps), positions,
                return_latent=True)
            x = x + h
            new_cache.append(A.fill_mla_cache(cfg, c, c_kv, k_rope, positions))
        elif kind == "mlstm":
            h, st = SSM.mlstm_forward(p["mlstm"], cfg,
                                      rms_norm(x, p["ln1"], cfg.norm_eps),
                                      return_state=True)
            x = x + h
            new_cache.append(st)
            continue
        elif kind == "slstm":
            h, st = SSM.slstm_forward(p["slstm"], cfg,
                                      rms_norm(x, p["ln1"], cfg.norm_eps),
                                      return_state=True)
            x = x + h
            new_cache.append(st)
            continue
        elif kind == "hymba":
            h, ((k, v), st) = HY.hymba_forward(
                p["hymba"], cfg, rms_norm(x, p["ln1"], cfg.norm_eps), positions,
                layer, return_cache=True)
            x = x + h
            window = cfg.sliding_window if cfg.layer_uses_window(layer) else None
            kv = A.fill_kv_cache(cfg, c["kv"], k, v, positions, window)
            new_cache.append({"kv": kv, "ssm": st})
        else:
            raise KeyError(kind)
        # FFN half (skipped for pure recurrent blocks via `continue`)
        xin = rms_norm(x, p["ln2"], cfg.norm_eps)
        if kind in ("attn_moe", "mla_moe"):
            h, _ = MOE.moe_forward(p["moe"], cfg, xin)
        elif cfg.mlp_variant == "gelu":
            h = gelu_mlp(xin, p["mlp"]["w_up"], p["mlp"]["b_up"],
                         p["mlp"]["w_down"], p["mlp"]["b_down"])
        else:
            h = swiglu(xin, p["mlp"]["w_gate"], p["mlp"]["w_up"], p["mlp"]["w_down"])
        x = constrain_residual(x + h)
    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bd,dv->bv", x[:, -1], head)[:, : cfg.vocab_size]
    return logits, new_cache


def _cross_decode(p, cfg: ModelConfig, x, xk, xv):
    H, hd = cfg.n_heads, cfg.head_dim
    B = x.shape[0]
    q = (x @ p["wq"]).reshape(B, 1, H, hd)
    s = jnp.einsum("bshd,bthd->bsht", q.astype(jnp.float32) * hd ** -0.5,
                   xk.astype(jnp.float32))
    w = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o = jnp.einsum("bsht,bthd->bshd", w, xv).reshape(B, 1, H * hd)
    return o @ p["wo"]


def prefill_cross_cache(params, cfg: ModelConfig, enc_out: jax.Array):
    """Precompute per-layer cross-attention K/V from encoder output."""
    out = []
    B, T, _ = enc_out.shape
    H, hd = cfg.n_heads, cfg.head_dim
    for p in params["layers"]:
        xk = (enc_out @ p["xattn"]["wk"]).reshape(B, T, H, hd)
        xv = (enc_out @ p["xattn"]["wv"]).reshape(B, T, H, hd)
        out.append((xk, xv))
    return out
