"""Pallas TPU kernel for the chunkwise-parallel mLSTM / gated linear
attention scan (xLSTM, Hymba recurrent hot-spot).

Grid = (batch, head, chunk); each program walks the sequence chunk by
chunk, holding the [hd, hd] recurrent state in VMEM scratch.  Per chunk
it does three MXU matmuls (intra-chunk attention, inter-chunk
query*state, state update) on (chunk, hd) tiles — the matmul-form
recurrence that makes linear-attention states TPU-friendly (vs. a
per-token scan which would be VPU-bound and sequence-length
latency-bound).

Contract identical to ``repro.models.ssm.mlstm_chunked_ref``:

    S_t = f_t * S_{t-1} + i_t * k_t v_t^T ;   h_t = q_t . S_t

Layout: the wrapper moves heads ahead of the sequence (``[B, H, S, hd]``)
and the gates to ``[B, H, 1, S]`` rows, so every block's last two dims
satisfy the TPU (8, 128) tiling rule.  Inside the kernel the gate row is
turned into the column forms the recurrence needs by masked lane
reductions (``_to_column``), which lower to plain VPU/XLU ops.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._interpret import resolve_interpret


# The recurrence is carried in f32; Mosaic's default contract precision
# would round every matmul operand to bf16, and the state would carry
# that error from chunk to chunk.
_F32 = jax.lax.Precision.HIGHEST


def _to_column(row, mask):
    """``[1, c]`` row -> ``[c, 1]`` column of ``sum_t mask[i, t] * row[t]``
    (the identity mask transposes, a lower-triangular one cumsums)."""
    return jnp.sum(jnp.where(mask, row, 0.0), axis=1, keepdims=True)


def _mlstm_kernel(q_ref, k_ref, v_ref, li_ref, lf_ref, o_ref, state_ref,
                  *, chunk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    q = q_ref[...].astype(jnp.float32)      # [chunk, hd]
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    li = li_ref[...].astype(jnp.float32)    # [1, chunk]
    lf = lf_ref[...].astype(jnp.float32)
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = rows >= cols
    g_col = _to_column(lf, causal)          # cumulative log-forget [chunk, 1]
    li_col = _to_column(li, rows == cols)
    lf_col = _to_column(lf, rows == cols)
    g_row = jnp.sum(jnp.where(rows <= cols, lf_col, 0.0), axis=0,
                    keepdims=True)
    g_total = jnp.sum(lf, axis=1, keepdims=True)  # [1, 1]

    state = state_ref[...]                  # [hd, hd] f32
    # inter-chunk: h_inter = (q * exp(g)) @ S
    h_inter = jax.lax.dot(q * jnp.exp(g_col), state, precision=_F32,
                          preferred_element_type=jnp.float32)
    # intra-chunk: att[c,t] = (q k^T)[c,t] * exp(g[c]-g[t]+li[t]) * causal
    att = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), precision=_F32,
                              preferred_element_type=jnp.float32)
    decay = jnp.where(causal, jnp.exp(g_col - g_row + li), 0.0)
    h_intra = jax.lax.dot(att * decay, v, precision=_F32,
                          preferred_element_type=jnp.float32)
    o_ref[...] = (h_inter + h_intra).astype(o_ref.dtype)
    # state update: S' = exp(g_total) S + (k * exp(g_total - g + li))^T @ v
    k_dec = k * jnp.exp(g_total - g_col + li_col)
    state_ref[...] = jnp.exp(g_total) * state + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())), precision=_F32,
        preferred_element_type=jnp.float32)


def mlstm_scan_pallas(
    q: jax.Array,       # [B, S, H, hd]
    k: jax.Array,
    v: jax.Array,
    log_i: jax.Array,   # [B, S, H]
    log_f: jax.Array,
    *,
    chunk: int = 128,
    interpret: Optional[bool] = None,  # None = compiled on TPU, interpret on CPU
) -> jax.Array:
    B, S, H, hd = q.shape
    if S % chunk:
        raise ValueError(f"seq len {S} is not a multiple of chunk {chunk}")
    heads_first = lambda x: jnp.transpose(x, (0, 2, 1, 3))  # noqa: E731
    gate_rows = lambda x: jnp.transpose(x, (0, 2, 1))[:, :, None, :]  # noqa: E731
    seq = pl.BlockSpec((None, None, chunk, hd), lambda b, h, c: (b, h, c, 0))
    gate = pl.BlockSpec((None, None, 1, chunk), lambda b, h, c: (b, h, 0, c))
    out = pl.pallas_call(
        functools.partial(_mlstm_kernel, chunk=chunk),
        grid=(B, H, S // chunk),
        in_specs=[seq, seq, seq, gate, gate],
        out_specs=seq,
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(heads_first(q), heads_first(k), heads_first(v),
      gate_rows(log_i), gate_rows(log_f))
    return heads_first(out)
