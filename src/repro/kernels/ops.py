"""Jitted public wrappers around the Pallas kernels.

Interpret mode is auto-detected: compiled via Mosaic on TPU, Pallas
interpreter on CPU.  ``gossip_mix`` and ``edge_segment_max`` also take
an explicit ``interpret`` flag.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attention import BLOCK_KV, BLOCK_Q, flash_attention_pallas
from .gossip_mix import gossip_mix_pallas
from .mlstm_scan import mlstm_scan_pallas
from .segment_max import edge_segment_max_pallas


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_kv"))
def flash_attention(
    q: jax.Array,  # [B, S, K, G, hd]
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = BLOCK_Q,
    block_kv: int = BLOCK_KV,
) -> jax.Array:
    """Differentiable: the kernel's own backward (bf16 MXU operands)."""
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  block_q=block_q, block_kv=block_kv,
                                  mxu_dtype=jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def gossip_mix(neighbor_blocks: jax.Array, weights: jax.Array, *,
               block: int = 65536, interpret: Optional[bool] = None):
    return gossip_mix_pallas(neighbor_blocks, weights, block=block,
                             interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk",))
def mlstm_scan(q, k, v, log_i, log_f, *, chunk: int = 128):
    return mlstm_scan_pallas(q, k, v, log_i, log_f, chunk=chunk)


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "block", "n_block", "interpret"))
def edge_segment_max(vals: jax.Array, seg_ids: jax.Array, *,
                     num_segments: int, block: int = 512,
                     n_block: int = 512,
                     interpret: Optional[bool] = None) -> jax.Array:
    return edge_segment_max_pallas(
        vals, seg_ids, num_segments, block=block, n_block=n_block,
        interpret=interpret)
