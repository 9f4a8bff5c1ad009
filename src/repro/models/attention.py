"""Attention: GQA (causal / sliding-window / bidirectional), DeepSeek MLA
(multi-head latent attention, absorbed decode path), and cross-attention.

Full-sequence causal self-attention runs the Pallas flash kernel with
its own backward (repro.kernels.flash_attention) on a TPU whenever the
shapes fit it; :func:`attention_impl` makes that choice from the
backend and the shapes.  Everywhere else (CPU, odd shapes, bidirectional
encoders, decode, MLA, cross attention) it uses a memory-bounded chunked
(flash-style) formulation in pure jnp — `lax.scan` over KV blocks with
running max/normalizer — so 32k-token prefill lowers without
materializing S^2 score matrices.  The kernel is validated against this
reference.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import apply_rope, rope_angles
from .params import ParamSpec

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# parameter specs


def attn_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    D, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    s = D ** -0.5
    return {
        "wq": ParamSpec((D, H * hd), ("embed", "heads"), s),
        "wk": ParamSpec((D, K * hd), ("embed", "kv_heads"), s),
        "wv": ParamSpec((D, K * hd), ("embed", "kv_heads"), s),
        "wo": ParamSpec((H * hd, D), ("heads", "embed"), (H * hd) ** -0.5),
    }


def mla_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    assert cfg.mla is not None
    m = cfg.mla
    D, H = cfg.d_model, cfg.n_heads
    s = D ** -0.5
    return {
        "w_dkv": ParamSpec((D, m.kv_lora_rank), ("embed", "rank"), s),
        "w_krope": ParamSpec((D, m.qk_rope_dim), ("embed", None), s),
        "kv_ln": ParamSpec((m.kv_lora_rank,), ("rank",), 1.0, init="ones"),
        "w_uk": ParamSpec((m.kv_lora_rank, H * m.qk_nope_dim), ("rank", "heads"), m.kv_lora_rank ** -0.5),
        "w_uv": ParamSpec((m.kv_lora_rank, H * m.v_head_dim), ("rank", "heads"), m.kv_lora_rank ** -0.5),
        "wq": ParamSpec((D, H * (m.qk_nope_dim + m.qk_rope_dim)), ("embed", "heads"), s),
        "wo": ParamSpec((H * m.v_head_dim, D), ("heads", "embed"), (H * m.v_head_dim) ** -0.5),
    }


def cross_attn_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    D, H, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    s = D ** -0.5
    return {
        "wq": ParamSpec((D, H * hd), ("embed", "heads"), s),
        "wk": ParamSpec((D, H * hd), ("embed", "heads"), s),
        "wv": ParamSpec((D, H * hd), ("embed", "heads"), s),
        "wo": ParamSpec((H * hd, D), ("heads", "embed"), (H * hd) ** -0.5),
    }


# ---------------------------------------------------------------------------
# chunked (flash-style) attention reference — memory O(S * kv_block)


def chunked_attention(
    q: jax.Array,  # [B, S, K, G, hd] (grouped query heads)
    k: jax.Array,  # [B, T, K, hd]
    v: jax.Array,  # [B, T, K, hd]
    q_pos: jax.Array,  # [S] int32
    kv_pos: jax.Array,  # [T] int32 (-1 marks invalid cache slots)
    *,
    causal: bool,
    window: Optional[int],
    kv_block: int = 1024,
    unroll: bool = False,
) -> jax.Array:
    B, S, K, G, hd = q.shape
    hd_v = v.shape[-1]
    T = k.shape[1]
    blocks = max(1, (T + kv_block - 1) // kv_block)
    pad = blocks * kv_block - T
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, (0, pad), constant_values=-1)
    kb = k.reshape(B, blocks, kv_block, K, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, blocks, kv_block, K, hd_v).transpose(1, 0, 2, 3, 4)
    pb = kv_pos.reshape(blocks, kv_block)
    scale = hd ** -0.5
    qf = q.astype(jnp.float32) * scale

    def step(carry, blk):
        m, l, acc = carry
        kc, vc, pc = blk  # [B, kb, K, hd], [B, kb, K, hd], [kb]
        s = jnp.einsum("bskgd,btkd->bskgt", qf, kc.astype(jnp.float32))
        mask = pc[None, :] >= 0  # [1, kb] valid
        if causal:
            mask = mask & (pc[None, :] <= q_pos[:, None])
        if window is not None:
            mask = mask & (q_pos[:, None] - pc[None, :] < window)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bskgt,btkd->bskgd", p, vc.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, S, K, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, S, K, G), jnp.float32)
    a0 = jnp.zeros((B, S, K, G, hd_v), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kb, vb, pb),
                                  unroll=blocks if unroll else 1)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def flash_attention_vjp(q, k, v, q_pos, kv_pos, causal, window, kv_block):
    """chunked_attention with a flash-style custom VJP: the backward pass
    recomputes the probability blocks from (q, k, logsumexp stats) instead
    of storing them — O(S * kv_block) residuals instead of
    O(S * T) fp32 probabilities per layer (the dominant training-memory
    term at 4k+ context; see EXPERIMENTS.md §Perf)."""
    out, _ = _flash_fwd_impl(q, k, v, q_pos, kv_pos, causal, window, kv_block)
    return out


def _flash_fwd_impl(q, k, v, q_pos, kv_pos, causal, window, kv_block):
    B, S, K, G, hd = q.shape
    hd_v = v.shape[-1]
    T = k.shape[1]
    blocks = max(1, (T + kv_block - 1) // kv_block)
    pad = blocks * kv_block - T
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, (0, pad), constant_values=-1)
    kb = k.reshape(B, blocks, kv_block, K, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, blocks, kv_block, K, hd_v).transpose(1, 0, 2, 3, 4)
    pb = kv_pos.reshape(blocks, kv_block)
    scale = hd ** -0.5
    qf = q.astype(jnp.float32) * scale

    def step(carry, blk):
        m, l, acc = carry
        kc, vc, pc = blk
        s = jnp.einsum("bskgd,btkd->bskgt", qf, kc.astype(jnp.float32))
        mask = pc[None, :] >= 0
        if causal:
            mask = mask & (pc[None, :] <= q_pos[:, None])
        if window is not None:
            mask = mask & (q_pos[:, None] - pc[None, :] < window)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bskgt,btkd->bskgd", p, vc.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, S, K, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, S, K, G), jnp.float32)
    a0 = jnp.zeros((B, S, K, G, hd_v), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kb, vb, pb))
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))  # [B,S,K,G]
    return out, lse


def _flash_fwd(q, k, v, q_pos, kv_pos, causal, window, kv_block):
    out, lse = _flash_fwd_impl(q, k, v, q_pos, kv_pos, causal, window, kv_block)
    return out, (q, k, v, q_pos, kv_pos, out, lse)


def _flash_bwd(causal, window, kv_block, res, dout):
    q, k, v, q_pos, kv_pos, out, lse = res
    B, S, K, G, hd = q.shape
    hd_v = v.shape[-1]
    T = k.shape[1]
    blocks = max(1, (T + kv_block - 1) // kv_block)
    pad = blocks * kv_block - T
    kp, vp, kvp = k, v, kv_pos
    if pad:
        kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kvp = jnp.pad(kv_pos, (0, pad), constant_values=-1)
    kb = kp.reshape(B, blocks, kv_block, K, hd).transpose(1, 0, 2, 3, 4)
    vb = vp.reshape(B, blocks, kv_block, K, hd_v).transpose(1, 0, 2, 3, 4)
    pb = kvp.reshape(blocks, kv_block)
    scale = hd ** -0.5
    qf = q.astype(jnp.float32) * scale
    do = dout.astype(jnp.float32)
    # D_i = sum_d dout_i * out_i  (rowwise)
    Drow = jnp.einsum("bskgd,bskgd->bskg", do, out.astype(jnp.float32))

    def step(dq, blk):
        kc, vc, pc = blk
        s = jnp.einsum("bskgd,btkd->bskgt", qf, kc.astype(jnp.float32))
        mask = pc[None, :] >= 0
        if causal:
            mask = mask & (pc[None, :] <= q_pos[:, None])
        if window is not None:
            mask = mask & (q_pos[:, None] - pc[None, :] < window)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])  # [B,S,K,G,t]
        dv_blk = jnp.einsum("bskgt,bskgd->btkd", p, do)
        dp = jnp.einsum("bskgd,btkd->bskgt", do, vc.astype(jnp.float32))
        ds = p * (dp - Drow[..., None])
        dq = dq + jnp.einsum("bskgt,btkd->bskgd", ds, kc.astype(jnp.float32))
        dk_blk = jnp.einsum("bskgt,bskgd->btkd", ds, qf)
        return dq, (dk_blk, dv_blk)

    dq0 = jnp.zeros((B, S, K, G, hd), jnp.float32)
    dq, (dk_b, dv_b) = jax.lax.scan(step, dq0, (kb, vb, pb))
    dq = (dq * scale).astype(q.dtype)
    dk = dk_b.transpose(1, 0, 2, 3, 4).reshape(B, blocks * kv_block, K, hd)
    dv = dv_b.transpose(1, 0, 2, 3, 4).reshape(B, blocks * kv_block, K, hd_v)
    if pad:
        dk = dk[:, :T]
        dv = dv[:, :T]
    return (dq, dk.astype(k.dtype), dv.astype(v.dtype), None, None)


flash_attention_vjp.defvjp(_flash_fwd, _flash_bwd)


def banded_swa_attention(
    q: jax.Array,  # [B, S, K, G, hd]
    k: jax.Array,  # [B, S, K, hd]
    v: jax.Array,  # [B, S, K, hd]
    positions: jax.Array,  # [S]
    *,
    window: int,
    q_block: int = 1024,
) -> jax.Array:
    """Sliding-window attention that only touches the KV band each query
    block can see — O(S * window) compute/bytes instead of O(S^2).

    §Perf optimization (beyond the naive chunked formulation): scan over
    query blocks; for each, ``dynamic_slice`` the KV band
    [q_start - window + 1, q_end] (clamped), compute one flash-style
    block.  Band length = q_block + window rounded up — static, so the
    whole thing stays jittable.
    """
    B, S, K, G, hd = q.shape
    if S % q_block:
        q_block = math_gcd_block(S, q_block)
    n_q = S // q_block
    band = q_block + window  # static band length (covers the visible range)
    band = min(band, S)
    scale = hd ** -0.5

    qb = q.reshape(B, n_q, q_block, K, G, hd).transpose(1, 0, 2, 3, 4, 5)
    pb = positions.reshape(n_q, q_block)

    def one_block(_, inp):
        qc, pc, qi = inp  # [B,q_block,K,G,hd], [q_block], scalar
        start = jnp.clip(qi * q_block + q_block - band, 0, S - band)
        kc = jax.lax.dynamic_slice(k, (0, start, 0, 0), (B, band, K, hd))
        vc = jax.lax.dynamic_slice(v, (0, start, 0, 0), (B, band, K, hd))
        kv_pos = start + jnp.arange(band, dtype=jnp.int32)
        s = jnp.einsum("bskgd,btkd->bskgt", qc.astype(jnp.float32) * scale,
                       kc.astype(jnp.float32))
        mask = (kv_pos[None, :] <= pc[:, None]) & (
            pc[:, None] - kv_pos[None, :] < window)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bskgt,btkd->bskgd", p, vc.astype(jnp.float32))
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(
        one_block, None,
        (qb, pb, jnp.arange(n_q, dtype=jnp.int32)))
    return outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, K, G, hd)


def math_gcd_block(S: int, prefer: int) -> int:
    b = min(prefer, S)
    while S % b:
        b -= 1
    return b


def naive_attention(q, k, v, q_pos, kv_pos, *, causal, window):
    """O(S*T) reference used for small-shape correctness tests."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bskgd,btkd->bskgt", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    mask = kv_pos[None, :] >= 0
    if causal:
        mask = mask & (kv_pos[None, :] <= q_pos[:, None])
    if window is not None:
        mask = mask & (q_pos[:, None] - kv_pos[None, :] < window)
    s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bskgt,btkd->bskgd", p, v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA block forward


def attention_impl(cfg: ModelConfig, S: int, T: int, causal: bool,
                   window: Optional[int], backend: str) -> str:
    """``"pallas"`` where :func:`attn_forward`'s self-attention runs the
    Pallas flash kernel, ``"chunked"`` where it keeps the jnp path: the
    kernel needs a TPU, causal attention, a head width of whole 128-lane
    tiles, sequence lengths that are multiples of its blocks, and its
    resident stripes within the VMEM budget.  ``window`` needs nothing
    more: the kernel skips the blocks outside the band."""
    from repro.kernels import flash_attention as fa

    del window
    hd, G = cfg.head_dim, cfg.n_heads // cfg.n_kv_heads
    block_q, block_kv = fa.flash_blocks(S, T)
    fits = (backend == "tpu" and causal and S == T and hd % fa.LANES == 0
            and block_q % fa.LANES == 0 and block_kv % fa.LANES == 0
            and S % block_q == 0 and T % block_kv == 0
            and fa.stripe_bytes(S, T, G, hd) <= fa.STRIPE_BUDGET)
    return "pallas" if fits else "chunked"


def _split_heads(x, n, hd):
    B, S, _ = x.shape
    return x.reshape(B, S, n, hd)


def attn_forward(
    p: Dict[str, jax.Array],
    cfg: ModelConfig,
    x: jax.Array,  # [B, S, D]
    positions: jax.Array,  # [S]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    kv_heads_override: Optional[int] = None,
    return_kv: bool = False,
):
    H, K, hd = cfg.n_heads, kv_heads_override or cfg.n_kv_heads, cfg.head_dim
    G = H // K
    q = _split_heads(x @ p["wq"], H, hd)
    k = _split_heads(x @ p["wk"], K, hd)
    v = _split_heads(x @ p["wv"], K, hd)
    cos, sin = rope_angles(positions, hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    B, S = x.shape[:2]
    qg = q.reshape(B, S, K, G, hd)
    if attention_impl(cfg, S, S, causal, window, jax.default_backend()) == "pallas":
        from repro.kernels import flash_attention as fa

        block_q, block_kv = fa.flash_blocks(S, S)
        out = fa.flash_attention_pallas(qg, k, v, causal=causal, window=window,
                                        block_q=block_q, block_kv=block_kv,
                                        mxu_dtype=jnp.bfloat16)
    elif cfg.banded_swa and causal and window is not None and S > 2 * window:
        out = banded_swa_attention(qg, k, v, positions, window=window)
    elif cfg.flash_vjp:
        out = flash_attention_vjp(qg, k, v, positions, positions,
                                  causal, window, 1024)
    else:
        out = chunked_attention(qg, k, v, positions, positions,
                                causal=causal, window=window,
                                unroll=cfg.analysis_unroll)
    out = out.reshape(B, S, H * hd)
    out = out @ p["wo"]
    if return_kv:
        return out, (k, v)
    return out


def fill_kv_cache(cfg: ModelConfig, cache: Dict[str, jax.Array],
                  k: jax.Array, v: jax.Array, positions: jax.Array,
                  window: Optional[int]) -> Dict[str, jax.Array]:
    """Write prefill K/V into a (possibly ring-buffered) cache."""
    size = cache["k"].shape[1]
    S = k.shape[1]
    take = min(S, size)
    k_t, v_t = k[:, -take:], v[:, -take:]
    pos_t = positions[-take:]
    slots = (pos_t % size).astype(jnp.int32)
    ck = cache["k"].at[:, slots].set(k_t.astype(cache["k"].dtype))
    cv = cache["v"].at[:, slots].set(v_t.astype(cache["v"].dtype))
    cpos = cache["pos"].at[slots].set(pos_t.astype(jnp.int32))
    return {"k": ck, "v": cv, "pos": cpos}


def attn_decode(
    p: Dict[str, jax.Array],
    cfg: ModelConfig,
    x: jax.Array,  # [B, 1, D]
    cache: Dict[str, jax.Array],
    position: jax.Array,  # scalar int32
    *,
    window: Optional[int] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode against a (possibly ring-buffered) KV cache."""
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = H // K
    B = x.shape[0]
    q = _split_heads(x @ p["wq"], H, hd)
    k = _split_heads(x @ p["wk"], K, hd)
    v = _split_heads(x @ p["wv"], K, hd)
    pos_arr = position[None]
    cos, sin = rope_angles(pos_arr, hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    cache_len = cache["k"].shape[1]
    slot = (position if window is None else position % cache_len).astype(jnp.int32)
    ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
    cpos = jax.lax.dynamic_update_slice(cache["pos"], pos_arr.astype(jnp.int32), (slot,))
    qg = q.reshape(B, 1, K, G, hd)
    out = chunked_attention(qg, ck, cv, pos_arr, cpos, causal=True, window=window)
    out = out.reshape(B, 1, H * hd)
    return out @ p["wo"], {"k": ck, "v": cv, "pos": cpos}


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  window: Optional[int], dtype) -> Dict[str, jax.Array]:
    K, hd = cfg.n_kv_heads, cfg.head_dim
    size = min(max_len, window) if window is not None else max_len
    return {
        "k": jnp.zeros((batch, size, K, hd), dtype),
        "v": jnp.zeros((batch, size, K, hd), dtype),
        "pos": jnp.full((size,), -1, jnp.int32),
    }


# ---------------------------------------------------------------------------
# MLA (DeepSeek multi-head latent attention)


def mla_forward(p, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
                *, return_latent: bool = False):
    """Training/prefill path: expand the latent to per-head K/V."""
    m = cfg.mla
    H = cfg.n_heads
    B, S, D = x.shape
    from .layers import rms_norm

    c_kv = rms_norm(x @ p["w_dkv"], p["kv_ln"], cfg.norm_eps)  # [B,S,R]
    k_rope = (x @ p["w_krope"]).reshape(B, S, 1, m.qk_rope_dim)
    cos, sin = rope_angles(positions, m.qk_rope_dim, cfg.rope_theta)
    k_rope = apply_rope(k_rope, cos, sin)  # shared across heads
    k_nope = (c_kv @ p["w_uk"]).reshape(B, S, H, m.qk_nope_dim)
    v = (c_kv @ p["w_uv"]).reshape(B, S, H, m.v_head_dim)
    q = (x @ p["wq"]).reshape(B, S, H, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim :]
    q_rope = apply_rope(q_rope, cos, sin)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, S, H, m.qk_rope_dim))], axis=-1
    )
    qfull = jnp.concatenate([q_nope, q_rope], axis=-1)
    qg = qfull.reshape(B, S, H, 1, m.qk_nope_dim + m.qk_rope_dim)
    out = chunked_attention(qg, k, v, positions, positions, causal=True,
                            window=None, unroll=cfg.analysis_unroll)
    out = out.reshape(B, S, H * m.v_head_dim)
    out = out @ p["wo"]
    if return_latent:
        return out, (c_kv, k_rope[:, :, 0, :])
    return out


def fill_mla_cache(cfg: ModelConfig, cache, c_kv, k_rope, positions):
    size = cache["c_kv"].shape[1]
    S = c_kv.shape[1]
    take = min(S, size)
    slots = (positions[-take:] % size).astype(jnp.int32)
    return {
        "c_kv": cache["c_kv"].at[:, slots].set(c_kv[:, -take:].astype(cache["c_kv"].dtype)),
        "k_rope": cache["k_rope"].at[:, slots].set(k_rope[:, -take:].astype(cache["k_rope"].dtype)),
        "pos": cache["pos"].at[slots].set(positions[-take:].astype(jnp.int32)),
    }


def mla_decode(p, cfg: ModelConfig, x: jax.Array, cache, position):
    """Absorbed decode: the cache holds only (c_kv, k_rope) — the paper-
    faithful MLA memory saving.  Scores are computed in latent space by
    absorbing W_uk into the query and W_uv into the output projection."""
    m = cfg.mla
    H = cfg.n_heads
    B = x.shape[0]
    from .layers import rms_norm

    c_kv_new = rms_norm(x @ p["w_dkv"], p["kv_ln"], cfg.norm_eps)  # [B,1,R]
    k_rope_new = (x @ p["w_krope"]).reshape(B, 1, 1, m.qk_rope_dim)
    pos_arr = position[None]
    cos, sin = rope_angles(pos_arr, m.qk_rope_dim, cfg.rope_theta)
    k_rope_new = apply_rope(k_rope_new, cos, sin)[:, :, 0, :]  # [B,1,rope]
    ckv = jax.lax.dynamic_update_slice(cache["c_kv"], c_kv_new, (0, position, 0))
    ckr = jax.lax.dynamic_update_slice(cache["k_rope"], k_rope_new, (0, position, 0))
    cpos = jax.lax.dynamic_update_slice(
        cache["pos"], pos_arr.astype(jnp.int32), (position,)
    )
    q = (x @ p["wq"]).reshape(B, 1, H, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim :]
    q_rope = apply_rope(q_rope, cos, sin)
    # absorb: q_lat[b,1,h,R] = q_nope . W_uk^T
    w_uk = p["w_uk"].reshape(m.kv_lora_rank, H, m.qk_nope_dim)
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_uk)
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    s = (
        jnp.einsum("bshr,btr->bsht", q_lat, ckv)
        + jnp.einsum("bshn,btn->bsht", q_rope, ckr)
    ) * scale
    mask = (cpos >= 0) & (cpos <= position)
    s = jnp.where(mask[None, None, None, :], s.astype(jnp.float32), NEG_INF)
    pattn = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o_lat = jnp.einsum("bsht,btr->bshr", pattn, ckv)  # [B,1,H,R]
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    out = jnp.einsum("bshr,rhv->bshv", o_lat, w_uv).reshape(B, 1, H * m.v_head_dim)
    return out @ p["wo"], {"c_kv": ckv, "k_rope": ckr, "pos": cpos}


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, m.qk_rope_dim), dtype),
        "pos": jnp.full((max_len,), -1, jnp.int32),
    }


# ---------------------------------------------------------------------------
# cross attention (whisper decoder)


def cross_attn_forward(p, cfg: ModelConfig, x: jax.Array, enc: jax.Array) -> jax.Array:
    H, hd = cfg.n_heads, cfg.head_dim
    B, S, _ = x.shape
    T = enc.shape[1]
    q = _split_heads(x @ p["wq"], H, hd)
    k = _split_heads(enc @ p["wk"], H, hd)
    v = _split_heads(enc @ p["wv"], H, hd)
    qg = q.reshape(B, S, H, 1, hd)
    pos_q = jnp.arange(S, dtype=jnp.int32)
    pos_k = jnp.arange(T, dtype=jnp.int32)
    out = chunked_attention(qg, k, v, pos_q, pos_k, causal=False, window=None)
    return out.reshape(B, S, H * hd) @ p["wo"]
