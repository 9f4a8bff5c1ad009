"""Pallas segment-max kernel: bit-identity against ``jax.ops.segment_max``
(interpret mode on CPU), the dispatch policy, and the degree-padded
Karp path it competes with."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

pytest.importorskip("jax")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.maxplus_sparse import (  # noqa: E402
    EdgeBatch,
    batched_cycle_time_sparse,
    batched_cycle_time_sparse_jax,
)
from repro.kernels.ops import edge_segment_max  # noqa: E402
from repro.kernels.segment_max import (  # noqa: E402
    edge_segment_max_pallas,
    segment_max,
    segment_max_pallas,
    select_segment_max_impl,
)


def _ref_flat(vals, ids, S):
    return jax.ops.segment_max(jnp.asarray(vals), jnp.asarray(ids),
                               num_segments=S)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 4), st.integers(1, 90), st.integers(1, 300),
       st.integers(0, 2 ** 31 - 1))
def test_edge_segment_max_bit_identical(B, E, S, seed):
    """Random values (including -inf entries and out-of-range ids) match
    vmapped ``jax.ops.segment_max`` bit for bit, empty segments included,
    with several edge tiles and (past 128 segments) several segment
    tiles."""
    rng = np.random.default_rng(seed)
    vals = rng.standard_normal((B, E)).astype(np.float32)
    vals[rng.random((B, E)) < 0.15] = -np.inf
    # ids in [-1, S]: -1 and S are out of range and must be dropped,
    # exactly like segment_max's out-of-bounds scatter semantics.
    ids = rng.integers(-1, S + 1, size=(B, E)).astype(np.int32)
    got = edge_segment_max_pallas(vals, ids, S, block=32, n_block=128,
                                  interpret=True)
    want = jax.vmap(lambda v, i: _ref_flat(v, i, S))(
        jnp.asarray(vals), jnp.asarray(ids))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_flat_form_and_jitted_wrapper_bit_identical():
    rng = np.random.default_rng(0)
    vals = rng.standard_normal(513).astype(np.float64)
    ids = rng.integers(0, 100, size=513).astype(np.int32)
    want = np.asarray(_ref_flat(vals, ids, 100))
    got_flat = segment_max_pallas(vals, ids, 100, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_flat), want)
    got_router = segment_max(jnp.asarray(vals), jnp.asarray(ids), 100,
                             impl="pallas", interpret=True)
    np.testing.assert_array_equal(np.asarray(got_router), want)
    got_jit = edge_segment_max(jnp.asarray(vals)[None], jnp.asarray(ids)[None],
                               num_segments=100, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_jit)[0], want)


def test_all_segments_empty_is_all_neg_inf():
    vals = np.full((2, 8), -np.inf, dtype=np.float32)
    ids = np.full((2, 8), -1, dtype=np.int32)
    out = np.asarray(edge_segment_max_pallas(vals, ids, 5, interpret=True))
    assert np.all(np.isneginf(out)) and out.shape == (2, 5)


def test_int_dtype_rejected():
    with pytest.raises(TypeError):
        edge_segment_max_pallas(np.ones((1, 4), dtype=np.int32),
                                np.zeros((1, 4), dtype=np.int32), 3,
                                interpret=True)


def test_dispatch_policy_on_cpu():
    """On this (CPU, interpret-default) container auto must never pick
    the interpret Pallas path: padded when the caller can bound the
    in-degree, xla otherwise.  Explicit names pass through."""
    assert select_segment_max_impl("auto") == "xla"
    assert select_segment_max_impl("auto", padded=True) == "padded"
    for name in ("xla", "padded", "pallas"):
        assert select_segment_max_impl(name) == name
        assert select_segment_max_impl(name, padded=True) == name
    with pytest.raises(ValueError):
        select_segment_max_impl("mosaic")
    with pytest.raises(ValueError):
        segment_max(jnp.ones(4), jnp.zeros(4, jnp.int32), 2, impl="padded")


def _random_edge_batch(rng, B, n, deg):
    """Strongly cyclic sparse batch with in-degree <= deg + 1 (ring +
    chords + self-loops), f32 weights."""
    E = n * (deg + 1)
    src = np.empty((B, E), dtype=np.int32)
    dst = np.empty((B, E), dtype=np.int32)
    w = np.empty((B, E), dtype=np.float32)
    idx = np.arange(n, dtype=np.int32)
    for b in range(B):
        cols = [(idx, np.roll(idx, -1))]
        for off in rng.choice(np.arange(2, n - 1), size=deg - 1,
                              replace=False):
            cols.append((idx, (idx + off) % n))
        cols.append((idx, idx))
        src[b] = np.concatenate([s for (s, _) in cols])
        dst[b] = np.concatenate([d for (_, d) in cols])
        w[b] = rng.uniform(0.5, 20.0, E).astype(np.float32)
    return src, dst, w


@pytest.mark.parametrize("kernel,kw", [
    ("padded", {"max_in_degree": 6}),
    ("pallas", {}),
])
def test_karp_recursion_kernels_bit_identical_to_xla(kernel, kw):
    """The hot Karp recursion produces bit-identical cycle times through
    every segment-max implementation (max is exact, order-independent)."""
    rng = np.random.default_rng(3)
    src, dst, w = _random_edge_batch(rng, B=3, n=24, deg=4)
    ref = batched_cycle_time_sparse_jax(src, dst, w, 24, kernel="xla")
    got = batched_cycle_time_sparse_jax(src, dst, w, 24, kernel=kernel, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # and the xla path agrees with the host oracle to fp tolerance
    host = batched_cycle_time_sparse(
        EdgeBatch(src, dst, w.astype(np.float64), 24))
    np.testing.assert_allclose(np.asarray(ref, np.float64), host, rtol=1e-5)


def test_padded_layout_drops_absent_arcs_before_ranking():
    """Regression: -inf (absent) arcs must not consume degree-table
    slots and evict real arcs sharing the destination."""
    n = 4
    # 5 arcs into node 0: 3 absent (-inf), 2 real; D=2 only fits the
    # real ones if absent arcs are routed out of the segment first.
    src = np.array([[1, 2, 3, 1, 2, 0, 1, 2, 3]], dtype=np.int32)
    dst = np.array([[0, 0, 0, 0, 0, 1, 2, 3, 1]], dtype=np.int32)
    w = np.array([[-np.inf, -np.inf, -np.inf, 3.0, 4.0,
                   1.0, 1.0, 1.0, 1.0]], dtype=np.float64)
    ref = batched_cycle_time_sparse_jax(src, dst, w, n, kernel="xla")
    got = batched_cycle_time_sparse_jax(src, dst, w, n, kernel="padded",
                                        max_in_degree=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_many_graphs_tile_128_at_a_time():
    """Past 128 graphs the batch axis is tiled (and padded) 128 at a
    time; every row still matches ``jax.ops.segment_max`` bit for bit."""
    rng = np.random.default_rng(7)
    vals = rng.standard_normal((130, 24)).astype(np.float32)
    ids = rng.integers(-1, 6, size=(130, 24)).astype(np.int32)
    got = edge_segment_max_pallas(vals, ids, 5, interpret=True)
    want = jax.vmap(lambda v, i: _ref_flat(v, i, 5))(
        jnp.asarray(vals), jnp.asarray(ids))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("block,n_block", [(12, 128), (16, 100)])
def test_tiles_off_the_tpu_grid_rejected(block, n_block):
    with pytest.raises(ValueError, match="TPU tiling"):
        edge_segment_max_pallas(np.ones((1, 4), np.float32),
                                np.zeros((1, 4), np.int32), 3,
                                block=block, n_block=n_block, interpret=True)
