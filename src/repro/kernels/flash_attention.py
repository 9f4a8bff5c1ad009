"""Pallas TPU flash attention (causal + sliding window, GQA).

TPU mapping: grid = (batch, query_head, q_blocks); each program streams
KV blocks of shape (block_kv, head_dim) through VMEM while keeping a
(block_q, head_dim) query tile and fp32 accumulators resident.  Block
shapes are multiples of 128 to align with the MXU systolic array; the
online-softmax recurrence avoids materializing the S^2 score matrix in
HBM (memory term: O(S * block_kv) per core instead of O(S^2)).

Layout: the wrapper moves heads ahead of the sequence (``[B, H, S, hd]``
queries, ``[B, K, T, hd]`` keys/values) so every block's last two dims
are (sequence tile, head_dim), as the TPU (8, 128) tiling rule wants;
GQA maps query head h to KV head ``h // G`` in the index map.

Validated in interpret mode against ``repro.kernels.ref.attention_ref``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._interpret import resolve_interpret

NEG_INF = -1e30


def _attn_kernel(
    q_ref,  # [block_q, hd]
    k_ref,  # [T, hd]      (full KV stripe for this (b, kv_head))
    v_ref,  # [T, hd]
    o_ref,  # [block_q, hd]
    *,
    block_q: int,
    block_kv: int,
    seq_len_kv: int,
    causal: bool,
    window: Optional[int],
):
    qi = pl.program_id(2)
    q = q_ref[...].astype(jnp.float32)
    hd = q.shape[-1]
    q = q * (hd ** -0.5)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0)

    def body(ki, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(ki * block_kv, block_kv), :].astype(jnp.float32)
        v = v_ref[pl.ds(ki * block_kv, block_kv), :].astype(jnp.float32)
        kv_pos = ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        mask = jnp.ones((block_q, block_kv), jnp.bool_)
        if causal:
            mask = mask & (kv_pos <= q_pos)
        if window is not None:
            mask = mask & (q_pos - kv_pos < window)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    a0 = jnp.zeros((block_q, hd), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, seq_len_kv // block_kv, body,
                                  (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,  # [B, S, K, G, hd]
    k: jax.Array,  # [B, T, K, hd]
    v: jax.Array,  # [B, T, K, hd]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: Optional[bool] = None,  # None = compiled on TPU, interpret on CPU
) -> jax.Array:
    B, S, K, G, hd = q.shape
    T = k.shape[1]
    if S % block_q or T % block_kv:
        raise ValueError(
            f"seq lens ({S}, {T}) must be multiples of the blocks "
            f"({block_q}, {block_kv})")
    kernel = functools.partial(
        _attn_kernel,
        block_q=block_q,
        block_kv=block_kv,
        seq_len_kv=T,
        causal=causal,
        window=window,
    )
    qh = jnp.transpose(q.reshape(B, S, K * G, hd), (0, 2, 1, 3))
    kh = jnp.transpose(k, (0, 2, 1, 3))
    vh = jnp.transpose(v, (0, 2, 1, 3))
    q_spec = pl.BlockSpec((None, None, block_q, hd),
                          lambda b, h, i: (b, h, i, 0))
    kv_spec = pl.BlockSpec((None, None, T, hd),
                           lambda b, h, i: (b, h // G, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid=(B, K * G, S // block_q),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, K * G, S, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(qh, kh, vh)
    return jnp.transpose(out, (0, 2, 1, 3)).reshape(B, S, K, G, hd)
