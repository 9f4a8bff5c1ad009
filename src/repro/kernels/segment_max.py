"""Pallas TPU kernel for the max-plus segment reduction — the design
loop's hot spot.

One Karp/timing-recursion step over an edge batch is

    nxt[b, v] = max over arcs (u -> v) in graph b of cur[b, u] + w[b, e]

i.e. a gather (``cur[b, src]``), an add, and a *per-destination segment
max*.  ``jax.ops.segment_max`` lowers that reduction to a scatter-max,
which XLA:CPU executes as a serial loop over E and XLA:TPU does not
vectorise either — BENCH_sparse_search.json shows the jitted sparse
path losing to host numpy by ~6x at N=1024 purely on this op.

The kernel here re-states the reduction as a dense one-hot max so it
runs on the TPU VPU at full lane width: each grid step loads a tile of
``block`` edge values + their int32 segment ids into VMEM, compares the
ids against a ``[block, n_block]`` iota of segment indices, and folds a
masked max over the tile into the output block.  Work is O(E * S)
instead of O(E), but every op is a dense vector op — for the segment
counts the design loop cares about (S = N <= a few thousand) that is a
large net win over serial scatter, and VMEM stays bounded at
``block * n_block`` elements regardless of problem size.

Numerics: ``max`` is associative, commutative, and exact in floating
point, and empty segments come out as the same ``-inf`` identity that
``jax.ops.segment_max`` uses for floats — the kernel is **bit-identical**
to ``jax.ops.segment_max`` for any float input without NaNs (CI smoke
asserts this in interpret mode; tier-1 tests assert it too).

Dispatch: the kernel only *wins* when compiled via Mosaic, so
:func:`select_segment_max_impl` returns ``"pallas"`` strictly on TPU
backends.  On CPU it picks the degree-padded dense-gather formulation
(``"padded"``, implemented in ``core.maxplus_sparse``) when the caller
can bound the in-degree statically, else plain ``"xla"`` — the losing
interpret-mode path is never auto-selected.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..analysis.contracts import contract
from ..core.maxplus_vec import NEG_INF
from ._interpret import interpret_default, resolve_interpret

__all__ = [
    "segment_max_pallas",
    "edge_segment_max_pallas",
    "segment_max",
    "select_segment_max_impl",
]


def _segmax_kernel(v_ref, i_ref, o_ref, *, n_block: int):
    # v_ref / i_ref: [block, b_block] values and int32 segment ids, edges
    # on sublanes and graphs on lanes, so each graph's edge tile is a
    # column that broadcasts across the segment lanes without a relayout.
    # o_ref: [b_block, n_block] running max for segment tile
    # program_id(1).  Grid is (B_tiles, S_tiles, E_tiles) with the edge
    # axis innermost, so the output block stays resident in VMEM while
    # edge tiles stream by.
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.full(o_ref.shape, NEG_INF, o_ref.dtype)

    block, b_block = v_ref.shape
    seg = jax.lax.broadcasted_iota(jnp.int32, (block, n_block), 1) \
        + pl.program_id(1) * n_block
    neg = jnp.full((), NEG_INF, o_ref.dtype)
    for b in range(b_block):
        hit = i_ref[:, b:b + 1] == seg
        cand = jnp.max(jnp.where(hit, v_ref[:, b:b + 1], neg), axis=0,
                       keepdims=True)
        o_ref[b:b + 1, :] = jnp.maximum(o_ref[b:b + 1, :], cand)


@contract("[B,E]", "[B,E]", "S", ret="[B,S]")
def edge_segment_max_pallas(
    vals: jax.Array,
    seg_ids: jax.Array,
    num_segments: int,
    *,
    block: int = 512,
    n_block: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Row-wise segment max over an edge batch: ``out[b, s] = max vals[b, e]
    over e with seg_ids[b, e] == s`` (``-inf`` where the segment is empty).

    Bit-identical to ``jax.vmap`` of ``jax.ops.segment_max`` for float
    inputs.  ``num_segments`` must be static; ids outside
    ``[0, num_segments)`` are dropped, matching ``segment_max``'s
    out-of-bounds scatter semantics.

    Tiles obey the TPU (8, 128) rule: an axis that fits in one tile is
    taken whole, otherwise it is padded to whole tiles of ``block``
    edges (a multiple of 8) and ``n_block`` segments (a multiple of
    128); more than 128 graphs are tiled 128 at a time.
    """
    interpret = resolve_interpret(interpret)
    vals = jnp.asarray(vals)
    if not jnp.issubdtype(vals.dtype, jnp.floating):
        raise TypeError(
            f"edge_segment_max_pallas needs a float dtype (the -inf "
            f"identity is float-only); got {vals.dtype}")
    if block % 8 or n_block % 128:
        raise ValueError(
            f"block must be a multiple of 8 and n_block of 128 (TPU "
            f"tiling); got block={block}, n_block={n_block}")
    seg_ids = jnp.asarray(seg_ids, dtype=jnp.int32)
    B, E = vals.shape
    S = int(num_segments)
    block = min(block, max(E, 1))
    n_block = min(n_block, max(S, 1))
    b_block = min(B, 128)
    e_pad, s_pad, b_pad = (-E) % block, (-S) % n_block, (-B) % b_block
    # Padding ids are -1: they match no segment tile and fold away.
    vals = jnp.pad(vals.T, ((0, e_pad), (0, b_pad)), constant_values=NEG_INF)
    seg_ids = jnp.pad(seg_ids.T, ((0, e_pad), (0, b_pad)), constant_values=-1)
    grid = ((B + b_pad) // b_block, (S + s_pad) // n_block,
            (E + e_pad) // block)
    out = pl.pallas_call(
        functools.partial(_segmax_kernel, n_block=n_block),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, b_block), lambda b, j, i: (i, b)),
            pl.BlockSpec((block, b_block), lambda b, j, i: (i, b)),
        ],
        out_specs=pl.BlockSpec((b_block, n_block), lambda b, j, i: (b, j)),
        out_shape=jax.ShapeDtypeStruct((B + b_pad, S + s_pad), vals.dtype),
        interpret=interpret,
    )(vals, seg_ids)
    return out[:B, :S]


@contract("[M]", "[M]", "S", ret="[S]")
def segment_max_pallas(
    vals: jax.Array,
    seg_ids: jax.Array,
    num_segments: int,
    *,
    block: int = 512,
    n_block: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Flat drop-in for ``jax.ops.segment_max`` (float input, static
    ``num_segments``), bit-identical on any NaN-free float input."""
    out = edge_segment_max_pallas(
        vals[None, :], seg_ids[None, :], num_segments,
        block=block, n_block=n_block, interpret=interpret)
    return out[0]


@contract()
def select_segment_max_impl(kernel: str = "auto", *,
                            padded: bool = False) -> str:
    """Resolve a segment-max implementation name for the hot recursions.

    ======== ==========================================================
    auto     ``"pallas"`` on compiled-TPU backends; else ``"padded"``
             when the caller supplies a static in-degree bound, else
             ``"xla"``.  Interpret-mode Pallas is never auto-selected —
             it cannot beat either alternative.
    xla      ``jax.ops.segment_max`` (scatter-max lowering).
    padded   degree-padded ``[B, N, D]`` gather + dense max (CPU
             winner; needs ``max_in_degree``).
    pallas   the kernel above (forced; interpret fallback off-TPU).
    ======== ==========================================================
    """
    if kernel != "auto":
        if kernel not in ("xla", "padded", "pallas"):
            raise ValueError(f"unknown segment-max impl {kernel!r}")
        return kernel
    if not interpret_default():
        return "pallas"
    return "padded" if padded else "xla"


@contract("[M]", "[M]", "S", ret="[S]")
def segment_max(
    vals: jax.Array,
    seg_ids: jax.Array,
    num_segments: int,
    *,
    impl: str = "xla",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Flat segment max routed through the chosen implementation
    (``"xla"`` or ``"pallas"``; the ``"padded"`` layout lives in
    ``core.maxplus_sparse`` because it needs the edge structure)."""
    if impl == "pallas":
        return segment_max_pallas(
            vals, seg_ids, num_segments, interpret=interpret)
    if impl == "xla":
        return jax.ops.segment_max(
            vals, seg_ids, num_segments=int(num_segments))
    raise ValueError(
        f"segment_max impl {impl!r} not routable here (padded needs "
        f"edge structure; use batched_cycle_time_sparse_jax)")
