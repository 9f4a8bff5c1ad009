"""Pallas TPU flash attention with its own backward (causal or sliding
window, GQA).

Layout: no transposes.  Queries ``[B, S, K, G, hd]`` are read as
``[B, S, K*G*hd]``; a program owns one query block of the G heads that
share KV head ``kh`` (the ``(block_q, G*hd)`` tile at column ``kh``).
Keys and values ``[B, T, K, hd]`` are read as ``[B, T, K*hd]``, one
``hd``-wide stripe per KV head.  With ``hd`` a multiple of 128 every
tile is whole TPU (8, 128) tiles.

Forward, grid ``(B, K, S / block_q)``: the full KV stripe of ``kh``
stays in VMEM while a ``fori_loop`` streams its blocks through an
online softmax.  The loop runs only over the KV blocks some query of
the block can see: up to the diagonal block, and for a window from the
first block inside the band.  Blocks that every query sees in full run
unmasked; only the blocks the diagonal or the band's edge cuts build a
mask, from ``iota`` inside the kernel.  It writes ``out`` and the
per-row log-sum-exp ``lse`` (f32, ``[B, K, G, S]``).

Backward (FlashAttention-2): ``D = rowsum(dO * O)`` once in XLA, then
two kernels that recompute ``p = exp(s - lse)`` per block from q, k and
``lse`` and skip the same blocks as the forward:

- ``dq``, grid ``(B, K, S / block_q)``: the forward's loop, accumulating
  ``dS @ K``;
- ``dk, dv``, grid ``(B, K, T / block_kv)``: the query stripe, ``dO``,
  ``lse`` and ``D`` of the G heads stay in VMEM; the loop runs over the
  query blocks that see the KV block, and sums the G heads' ``dS^T Q``
  and ``P^T dO`` in the kernel, so GQA costs no repeated K or V.  This
  kernel works on ``s^T`` (``[block_kv, block_q]``), so ``lse`` and
  ``D`` are read as rows, as they are stored.

Precision: the MXU operands (q, k, p, v, dO, dS) are ``mxu_dtype``;
softmax statistics, ``exp`` and every accumulator are f32.  The model
path feeds bf16 operands with f32 accumulation, the precision XLA's
default gives the jnp attention it replaces; f32 operands are
multiplied at ``Precision.HIGHEST``, never at Mosaic's default.

Validated in interpret mode against ``repro.kernels.ref.attention_ref``
and the model's ``chunked_attention`` (forward and gradients).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._interpret import resolve_interpret

NEG_INF = -1e30
LANES = 128
# Blocks at S = 2048, hd = 128 on a TPU v5e (PERF.md, PR 14).
BLOCK_Q = 512
BLOCK_KV = 512
# VMEM a program's resident stripes may take, double-buffered.
STRIPE_BUDGET = 8 * 2 ** 20

_NT = (((1,), (1,)), ((), ()))  # a @ b.T


def flash_blocks(S: int, T: int) -> Tuple[int, int]:
    """The (block_q, block_kv) the kernel runs at for sequence lengths
    (S, T)."""
    return min(BLOCK_Q, S), min(BLOCK_KV, T)


def stripe_bytes(S: int, T: int, G: int, hd: int, itemsize: int = 2) -> int:
    """VMEM the largest resident stripes take, double-buffered: the
    forward's and ``dq``'s K and V stripes, or the ``dk, dv`` kernel's
    query and ``dO`` stripes of G heads."""
    return 2 * 2 * itemsize * hd * max(T, S * G)


def _dot(a, b, dims=None):
    precision = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    if dims is None:
        dims = (((1,), (0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _spans(lo, full_lo, full_hi, hi):
    """Split the visited blocks ``[lo, hi)`` into (start, stop, masked)
    runs: ``[full_lo, full_hi)`` needs no mask, the runs either side do."""
    full_lo = jnp.clip(full_lo, lo, hi)
    full_hi = jnp.clip(full_hi, full_lo, hi)
    return ((lo, full_lo, True), (full_lo, full_hi, False), (full_hi, hi, True))


def _kv_spans(i, block_q, block_kv, n_kv, causal, window):
    """KV blocks the query block ``i`` visits."""
    if not causal:
        return ((0, n_kv, False),)
    first_q = i * block_q
    last_q = first_q + block_q - 1
    hi = last_q // block_kv + 1                    # holds the last query's own key
    full_hi = (first_q + 1) // block_kv            # last key <= first query
    if window is None:
        lo = full_lo = 0
    else:
        lo = jnp.maximum(0, (first_q - window + 1) // block_kv)
        full_lo = (last_q - window + block_kv) // block_kv  # first key in band of last query
    return _spans(lo, full_lo, full_hi, hi)


def _q_spans(j, block_q, block_kv, n_q, causal, window):
    """Query blocks that see the KV block ``j``."""
    if not causal:
        return ((0, n_q, False),)
    first_k = j * block_kv
    last_k = first_k + block_kv - 1
    lo = first_k // block_q                        # holds the query at first_k
    full_lo = (last_k + block_q - 1) // block_q    # first query >= last key
    if window is None:
        hi = full_hi = n_q
    else:
        hi = jnp.minimum(n_q, (window + last_k - 1 + block_q) // block_q)
        full_hi = (window + first_k) // block_q    # last query within band of first key
    return _spans(lo, full_lo, full_hi, hi)


def _visible(shape, window, transposed=False):
    """``(first query, first key) -> mask`` of the pairs of a tile that
    causal attention sees; rows are queries, or keys when ``transposed``."""
    q_axis, k_axis = (1, 0) if transposed else (0, 1)

    def mask(q_first, k_first):
        q_pos = q_first + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
        k_pos = k_first + jax.lax.broadcasted_iota(jnp.int32, shape, k_axis)
        seen = k_pos <= q_pos
        if window is not None:
            seen = seen & (q_pos - k_pos < window)
        return seen

    return mask


def _run(spans, body, carry, visible):
    """``body(visible or None, index, carry)`` over each run of spans."""
    for start, stop, masked in spans:
        carry = jax.lax.fori_loop(
            start, stop, functools.partial(body, visible if masked else None), carry)
    return carry


# ---------------------------------------------------------------------------
# forward


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, G, hd, block_q,
                block_kv, n_kv, causal, window):
    i = pl.program_id(2)
    qs = [q_ref[:, g * hd:(g + 1) * hd] for g in range(G)]

    def body(visible, j, carry):
        start = pl.multiple_of(j * block_kv, block_kv)
        k = k_ref[pl.ds(start, block_kv), :]
        v = v_ref[pl.ds(start, block_kv), :]
        mask = None if visible is None else visible(i * block_q, start)
        out = []
        for q, (m, l, acc) in zip(qs, carry):
            s = _dot(q, k, _NT)
            if mask is not None:
                s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            if mask is not None:
                p = jnp.where(mask, p, 0.0)
            alpha = jnp.exp(m - m_new)
            l = alpha * l + p.sum(axis=-1, keepdims=True)
            acc = alpha * acc + _dot(p.astype(v.dtype), v)
            out.append((m_new, l, acc))
        return tuple(out)

    init = tuple((jnp.full((block_q, 1), NEG_INF, jnp.float32),
                  jnp.zeros((block_q, 1), jnp.float32),
                  jnp.zeros((block_q, hd), jnp.float32)) for _ in range(G))
    carry = _run(_kv_spans(i, block_q, block_kv, n_kv, causal, window), body, init,
                 _visible((block_q, block_kv), window))
    for g, (m, l, acc) in enumerate(carry):
        o_ref[:, g * hd:(g + 1) * hd] = (acc / l).astype(o_ref.dtype)
        lse_ref[g, :] = (m + jnp.log(l))[:, 0]


def _forward(qs, k, v, *, causal, window, block_q, block_kv, out_dtype,
             interpret):
    """(out [B, S, K, G, hd], lse [B, K, G, S] f32) for the scaled
    queries ``qs``."""
    B, S, K, G, hd = qs.shape
    T = k.shape[1]
    n_kv = T // block_kv
    kernel = functools.partial(
        _fwd_kernel, G=G, hd=hd, block_q=block_q, block_kv=block_kv,
        n_kv=n_kv, causal=causal, window=window)
    q_spec = pl.BlockSpec((None, block_q, G * hd), lambda b, h, i: (b, i, h))
    kv_spec = pl.BlockSpec((None, T, hd), lambda b, h, i: (b, 0, h))
    lse_spec = pl.BlockSpec((None, None, G, block_q), lambda b, h, i: (b, h, 0, i))
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, K, S // block_q),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, lse_spec],
        out_shape=[jax.ShapeDtypeStruct((B, S, K * G * hd), out_dtype),
                   jax.ShapeDtypeStruct((B, K, G, S), jnp.float32)],
        compiler_params=_params(S, T, G, hd, qs.dtype),
        interpret=resolve_interpret(interpret),
    )(qs.reshape(B, S, K * G * hd), k.reshape(B, T, K * hd),
      v.reshape(B, T, K * hd))
    return out.reshape(B, S, K, G, hd), lse


def _params(S, T, G, hd, dtype):
    need = stripe_bytes(S, T, G, hd, jnp.dtype(dtype).itemsize)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel"),
        vmem_limit_bytes=max(32 * 2 ** 20, 2 * need))


# ---------------------------------------------------------------------------
# backward


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dq_ref, *, G, hd,
               block_q, block_kv, n_kv, causal, window):
    i = pl.program_id(2)
    cols = [slice(g * hd, (g + 1) * hd) for g in range(G)]
    qs = [q_ref[:, c] for c in cols]
    dos = [do_ref[:, c] for c in cols]
    lses = [lse_ref[g, :][:, None] for g in range(G)]
    ds_ = [d_ref[g, :][:, None] for g in range(G)]

    def body(visible, j, dqs):
        start = pl.multiple_of(j * block_kv, block_kv)
        k = k_ref[pl.ds(start, block_kv), :]
        v = v_ref[pl.ds(start, block_kv), :]
        mask = None if visible is None else visible(i * block_q, start)
        out = []
        for q, do, lse, d, dq in zip(qs, dos, lses, ds_, dqs):
            s = _dot(q, k, _NT)
            if mask is not None:
                s = jnp.where(mask, s, NEG_INF)
            p = jnp.exp(s - lse)
            dp = _dot(do, v, _NT)
            ds = p * (dp - d)
            out.append(dq + _dot(ds.astype(k.dtype), k))
        return tuple(out)

    init = tuple(jnp.zeros((block_q, hd), jnp.float32) for _ in range(G))
    dqs = _run(_kv_spans(i, block_q, block_kv, n_kv, causal, window), body, init,
               _visible((block_q, block_kv), window))
    for c, dq in zip(cols, dqs):
        dq_ref[:, c] = dq


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dk_ref, dv_ref, *,
                G, hd, block_q, block_kv, n_q, causal, window):
    j = pl.program_id(2)
    k = k_ref[...]
    v = v_ref[...]

    def body(visible, i, carry):
        dk, dv = carry
        start = pl.multiple_of(i * block_q, block_q)
        mask = None if visible is None else visible(start, j * block_kv)
        for g in range(G):
            c = slice(g * hd, (g + 1) * hd)
            q = q_ref[pl.ds(start, block_q), c]
            do = do_ref[pl.ds(start, block_q), c]
            lse = lse_ref[pl.ds(g, 1), pl.ds(start, block_q)]
            d = d_ref[pl.ds(g, 1), pl.ds(start, block_q)]
            st = _dot(k, q, _NT)                      # s^T: [block_kv, block_q]
            if mask is not None:
                st = jnp.where(mask, st, NEG_INF)
            pt = jnp.exp(st - lse)
            dv = dv + _dot(pt.astype(do.dtype), do)
            dpt = _dot(v, do, _NT)
            dst = pt * (dpt - d)
            dk = dk + _dot(dst.astype(q.dtype), q)
        return dk, dv

    init = (jnp.zeros((block_kv, hd), jnp.float32),
            jnp.zeros((block_kv, hd), jnp.float32))
    dk, dv = _run(_q_spans(j, block_q, block_kv, n_q, causal, window), body, init,
                  _visible((block_kv, block_q), window, transposed=True))
    dk_ref[...] = dk
    dv_ref[...] = dv


def _backward(qs, k, v, do, lse, d, *, causal, window, block_q, block_kv,
              interpret):
    """(dqs, dk, dv) in f32; ``dqs`` is the gradient of the scaled
    queries."""
    B, S, K, G, hd = qs.shape
    T = k.shape[1]
    n_q, n_kv = S // block_q, T // block_kv
    q2 = qs.reshape(B, S, K * G * hd)
    do2 = do.reshape(B, S, K * G * hd)
    k2, v2 = k.reshape(B, T, K * hd), v.reshape(B, T, K * hd)
    shared = dict(G=G, hd=hd, block_q=block_q, block_kv=block_kv,
                  causal=causal, window=window)
    params = _params(S, T, G, hd, qs.dtype)
    interpret = resolve_interpret(interpret)

    q_blk = pl.BlockSpec((None, block_q, G * hd), lambda b, h, i: (b, i, h))
    kv_all = pl.BlockSpec((None, T, hd), lambda b, h, i: (b, 0, h))
    row_blk = pl.BlockSpec((None, None, G, block_q), lambda b, h, i: (b, h, 0, i))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, n_kv=n_kv, **shared),
        grid=(B, K, n_q),
        in_specs=[q_blk, kv_all, kv_all, q_blk, row_blk, row_blk],
        out_specs=q_blk,
        out_shape=jax.ShapeDtypeStruct((B, S, K * G * hd), jnp.float32),
        compiler_params=params,
        interpret=interpret,
    )(q2, k2, v2, do2, lse, d)

    q_all = pl.BlockSpec((None, S, G * hd), lambda b, h, j: (b, 0, h))
    kv_blk = pl.BlockSpec((None, block_kv, hd), lambda b, h, j: (b, j, h))
    row_all = pl.BlockSpec((None, None, G, S), lambda b, h, j: (b, h, 0, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, n_q=n_q, **shared),
        grid=(B, K, n_kv),
        in_specs=[q_all, kv_blk, kv_blk, q_all, row_all, row_all],
        out_specs=[kv_blk, kv_blk],
        out_shape=[jax.ShapeDtypeStruct((B, T, K * hd), jnp.float32)] * 2,
        compiler_params=params,
        interpret=interpret,
    )(q2, k2, v2, do2, lse, d)
    return (dq.reshape(B, S, K, G, hd), dk.reshape(B, T, K, hd),
            dv.reshape(B, T, K, hd))


# ---------------------------------------------------------------------------
# public entry points


def _check(S, T, causal, window, block_q, block_kv):
    if S % block_q or T % block_kv:
        raise ValueError(
            f"seq lens ({S}, {T}) must be multiples of the blocks "
            f"({block_q}, {block_kv})")
    if window is not None and not causal:
        raise ValueError("a window needs causal attention")
    if causal and S != T:
        raise ValueError(f"causal attention needs S == T, got ({S}, {T})")


def _scaled(q, mxu_dtype):
    return (q.astype(jnp.float32) * q.shape[-1] ** -0.5).astype(mxu_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, window, block_q, block_kv, mxu_dtype, interpret):
    out, _ = _fwd(q, k, v, causal, window, block_q, block_kv, mxu_dtype,
                  interpret)
    return out


def _fwd(q, k, v, causal, window, block_q, block_kv, mxu_dtype, interpret):
    qs, kb, vb = _scaled(q, mxu_dtype), k.astype(mxu_dtype), v.astype(mxu_dtype)
    out, lse = _forward(qs, kb, vb, causal=causal, window=window,
                        block_q=block_q, block_kv=block_kv, out_dtype=q.dtype,
                        interpret=interpret)
    # empty arrays carry the inputs' dtypes to the backward
    dtypes = tuple(jnp.zeros((0,), x.dtype) for x in (q, k, v))
    return out, (qs, kb, vb, out, lse, dtypes)


def _bwd(causal, window, block_q, block_kv, mxu_dtype, interpret, res, g):
    qs, kb, vb, out, lse, dtypes = res
    hd = qs.shape[-1]
    d = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    d = jnp.transpose(d, (0, 2, 3, 1))                       # [B, K, G, S]
    dqs, dk, dv = _backward(qs, kb, vb, g.astype(mxu_dtype), lse, d,
                            causal=causal, window=window, block_q=block_q,
                            block_kv=block_kv, interpret=interpret)
    return tuple(x.astype(like.dtype)
                 for x, like in zip((dqs * hd ** -0.5, dk, dv), dtypes))


_flash.defvjp(_fwd, _bwd)


def flash_attention_pallas(
    q: jax.Array,  # [B, S, K, G, hd]
    k: jax.Array,  # [B, T, K, hd]
    v: jax.Array,  # [B, T, K, hd]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_kv: int = 128,
    mxu_dtype=None,  # None = the inputs' dtype
    interpret: Optional[bool] = None,  # None = compiled on TPU, interpret on CPU
) -> jax.Array:
    """Differentiable flash attention.  Positions are ``arange`` on both
    sides (any common offset gives the same masks).  Returns
    ``[B, S, K, G, hd]`` in q's dtype; gradients come in each input's
    dtype."""
    _check(q.shape[1], k.shape[1], causal, window, block_q, block_kv)
    mxu_dtype = jnp.dtype(q.dtype if mxu_dtype is None else mxu_dtype)
    return _flash(q, k, v, causal, window, block_q, block_kv, mxu_dtype,
                  interpret)
