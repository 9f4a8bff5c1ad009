"""Sparse (edge-list) engine: equivalence against the dense engine.

The dense engine is itself equivalence-tested against the ``*_legacy``
dict oracles (tests/test_maxplus_vec.py), so agreement here closes the
chain legacy == dense == sparse.  Property tests cover random
strongly-connected overlays in f32 and f64 plus the padded-edge and
duplicate-arc edge cases of the ``[B, E]`` representation.
"""

import math
import random

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

import repro.core as C
from repro.core.delays import batched_overlay_delay_matrices
from repro.core.maxplus_sparse import (
    EdgeBatch,
    batched_cycle_time_sparse,
    batched_is_strongly_connected_sparse,
    batched_overlay_delay_edges,
    batched_timing_recursion_sparse,
    cycle_time_sparse,
    dense_to_edge_batch,
    edge_batch_to_dense,
    reachable_from_sparse,
    scc_labels_sparse,
)
from repro.core.maxplus_vec import (
    NEG_INF,
    batched_cycle_time,
    batched_is_strongly_connected,
    batched_timing_recursion,
    missing_mask,
    reachability_closure,
    scc_labels,
)


def random_dense_batch(rng, b, n, density=0.35):
    """[B, N, N] random digraphs with -inf holes."""
    W = np.where(
        rng.random((b, n, n)) < density,
        rng.uniform(0.1, 30.0, (b, n, n)),
        -np.inf,
    )
    return W


def random_strong_batch(rng, b, n):
    """Ring + chords + self loops: strongly connected by construction."""
    W = np.full((b, n, n), -np.inf)
    idx = np.arange(n)
    for k in range(b):
        perm = rng.permutation(n)
        W[k, perm, np.roll(perm, -1)] = rng.uniform(0.5, 20.0, n)
        W[k, idx, idx] = rng.uniform(0.0, 5.0, n)
        chords = rng.integers(0, n, size=(2 * n, 2))
        for (i, j) in chords:
            if i != j:
                W[k, i, j] = rng.uniform(0.5, 20.0)
    return W


def test_round_trip_dense_edge_batch():
    rng = np.random.default_rng(0)
    W = random_dense_batch(rng, 17, 7)
    eb = dense_to_edge_batch(W)
    np.testing.assert_array_equal(edge_batch_to_dense(eb), W)


def test_cycle_time_matches_dense_on_random_digraphs():
    """Including disconnected and acyclic instances (tau = -inf)."""
    rng = np.random.default_rng(1)
    for density in (0.1, 0.35, 0.8):
        W = random_dense_batch(rng, 32, 8, density)
        ref = batched_cycle_time(W)
        got = batched_cycle_time_sparse(dense_to_edge_batch(W))
        np.testing.assert_allclose(got, ref, rtol=1e-12)


def test_padding_and_duplicates_are_neutral():
    rng = np.random.default_rng(2)
    W = random_strong_batch(rng, 8, 6)
    eb = dense_to_edge_batch(W)
    ref = batched_cycle_time_sparse(eb)
    # extra padded capacity
    wide = dense_to_edge_batch(W, e_max=eb.max_edges + 13)
    np.testing.assert_array_equal(batched_cycle_time_sparse(wide), ref)
    # duplicate arcs with *smaller* weights never win a segment max
    dup = EdgeBatch(
        np.concatenate([eb.src, eb.src], axis=1),
        np.concatenate([eb.dst, eb.dst], axis=1),
        np.concatenate([eb.w, eb.w - 5.0], axis=1),
        eb.num_nodes,
    )
    np.testing.assert_array_equal(batched_cycle_time_sparse(dup), ref)


def test_batch_chunking_is_invisible():
    """The [N+1, chunk, N] Karp DP table is bounded by max_dp_bytes via
    batch chunking; chunk size must not affect results (mirrors the
    dense engine's test)."""
    rng = np.random.default_rng(3)
    eb = dense_to_edge_batch(random_dense_batch(rng, 33, 7, 0.4))
    full = batched_cycle_time_sparse(eb)
    tiny = batched_cycle_time_sparse(eb, max_dp_bytes=8 * 7 * 10)
    np.testing.assert_array_equal(tiny, full)


def test_empty_and_tiny_graphs():
    eb = EdgeBatch(
        np.zeros((3, 1), dtype=np.int32),
        np.zeros((3, 1), dtype=np.int32),
        np.full((3, 1), -np.inf),
        4,
    )
    assert np.all(batched_cycle_time_sparse(eb) == -np.inf)
    assert cycle_time_sparse([0], [0], [5.0], 1) == pytest.approx(5.0)


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 10), st.integers(0, 10_000), st.booleans())
def test_property_sparse_dense_agree_on_strong_overlays(n, seed, use_f32):
    """Acceptance: sparse and dense batched_cycle_time agree on random
    strongly-connected overlays, f32 and f64, with padded edges."""
    rng = np.random.default_rng(seed)
    W = random_strong_batch(rng, 6, n)
    eb = dense_to_edge_batch(W, e_max=W.shape[1] * W.shape[1] + 3)
    assert np.all(batched_is_strongly_connected_sparse(eb))
    if use_f32:
        ref = batched_cycle_time(W.astype(np.float32), dtype=np.float32)
        got = batched_cycle_time_sparse(
            EdgeBatch(eb.src, eb.dst, eb.w.astype(np.float32), n)
        )
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-4)
    else:
        ref = batched_cycle_time(W)
        got = batched_cycle_time_sparse(eb)
        np.testing.assert_allclose(got, ref, rtol=1e-12)


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 8), st.integers(0, 10_000))
def test_property_timing_recursion_matches_dense(n, seed):
    rng = np.random.default_rng(seed)
    W = random_dense_batch(rng, 4, n, density=0.5)
    ref = batched_timing_recursion(W, 20)
    got = batched_timing_recursion_sparse(dense_to_edge_batch(W), 20)
    np.testing.assert_allclose(got, ref, rtol=1e-12)


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 9), st.integers(0, 10_000))
def test_property_strong_connectivity_matches_dense(n, seed):
    rng = np.random.default_rng(seed)
    W = random_dense_batch(rng, 16, n, density=rng.uniform(0.1, 0.6))
    ref = batched_is_strongly_connected(W)
    got = batched_is_strongly_connected_sparse(dense_to_edge_batch(W))
    np.testing.assert_array_equal(got, ref)


def test_reachability_matches_dense_closure():
    rng = np.random.default_rng(5)
    W = random_dense_batch(rng, 12, 9, density=0.25)
    eb = dense_to_edge_batch(W)
    got = reachable_from_sparse(eb, start=0)
    adj = W > -np.inf
    idx = np.arange(9)
    adj[:, idx, idx] = False
    ref = reachability_closure(adj)[:, 0, :]  # row 0: reachable from 0
    np.testing.assert_array_equal(got, ref)


def test_scc_labels_same_partition_as_dense():
    rng = np.random.default_rng(6)
    for _ in range(20):
        n = int(rng.integers(1, 12))
        A = (rng.random((n, n)) < 0.25) & ~np.eye(n, dtype=bool)
        dense = scc_labels(A, dense_threshold=1024)
        i, j = np.nonzero(A)
        sparse = scc_labels_sparse(i, j, n)
        f, g = {}, {}
        for a, b in zip(dense.tolist(), sparse.tolist()):
            assert f.setdefault(a, b) == b
            assert g.setdefault(b, a) == a


def test_overlay_delay_edges_matches_dense_matrices():
    """Eq. 3 pricing: the sparse builder and the dense builder price the
    same candidate masks identically (degrees, sharing, self loops)."""
    u = C.make_underlay("gaia")
    M, Tc = C.WORKLOADS["inaturalist"]
    tp = C.TrainingParams(model_size_mbits=M, local_steps=1)
    gc = u.connectivity_graph(comp_time_ms=Tc)
    arcs = [e for e in gc.edges() if e[0] != e[1]]
    rng = np.random.default_rng(7)
    masks = rng.random((12, len(arcs))) < 0.15
    Wd = batched_overlay_delay_matrices(gc, tp, arcs, masks)
    eb = batched_overlay_delay_edges(gc, tp, arcs, masks)
    np.testing.assert_allclose(edge_batch_to_dense(eb), Wd, rtol=1e-15)
    np.testing.assert_allclose(
        batched_cycle_time_sparse(eb), batched_cycle_time(Wd), rtol=1e-12
    )


# ---------------------------------------------------------------------------
# Adversarial NEG_INF arithmetic: the sentinel must stay absorbing (never
# NaN) under f32 and under padded-edge masks — the failure modes the
# repro-lint sentinel-discipline rule exists to keep out of the engines.
# ---------------------------------------------------------------------------


def test_all_padding_f32_yields_neg_inf_not_nan():
    """A fully padded f32 batch: every reduction walks -inf + -inf chains,
    which must stay -inf (absorbing), never NaN (-inf - -inf)."""
    z = np.zeros((4, 6), dtype=np.int32)
    eb = EdgeBatch(z, z, np.full((4, 6), NEG_INF, dtype=np.float32), 5)
    tau = batched_cycle_time_sparse(eb)
    assert np.all(np.isneginf(np.asarray(tau, dtype=np.float64)))
    assert not np.any(np.isnan(tau))
    times = batched_timing_recursion_sparse(eb, 7)
    assert not np.any(np.isnan(times))
    assert not np.all(batched_is_strongly_connected_sparse(eb))


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 8), st.integers(0, 10_000), st.booleans())
def test_property_interleaved_neg_inf_padding_is_absorbing(n, seed, use_f32):
    """Padded arcs shuffled *between* real arcs (not just appended at the
    tail, the layout dense_to_edge_batch emits) pointing at arbitrary
    node pairs must be invisible to every engine, in f32 and f64."""
    rng = np.random.default_rng(seed)
    dtype = np.float32 if use_f32 else np.float64
    W = random_strong_batch(rng, 4, n)
    eb = dense_to_edge_batch(W)
    b, e = eb.src.shape
    p = int(rng.integers(1, 2 * n + 2))
    pad_src = rng.integers(0, n, (b, p)).astype(eb.src.dtype)
    pad_dst = rng.integers(0, n, (b, p)).astype(eb.dst.dtype)
    perm = rng.permutation(e + p)
    adv = EdgeBatch(
        np.concatenate([eb.src, pad_src], axis=1)[:, perm],
        np.concatenate([eb.dst, pad_dst], axis=1)[:, perm],
        np.concatenate(
            [eb.w, np.full((b, p), NEG_INF)], axis=1
        )[:, perm].astype(dtype),
        n,
    )
    ref_eb = EdgeBatch(eb.src, eb.dst, eb.w.astype(dtype), n)
    ref = batched_cycle_time_sparse(ref_eb)
    got = batched_cycle_time_sparse(adv)
    # max-plus reductions are order-independent and -inf is absorbing,
    # so agreement is exact even in f32 — not merely close.
    np.testing.assert_array_equal(got, ref)
    assert not np.any(np.isnan(got))
    np.testing.assert_array_equal(
        batched_is_strongly_connected_sparse(adv),
        batched_is_strongly_connected_sparse(ref_eb),
    )
    t_ref = batched_timing_recursion_sparse(ref_eb, 6)
    t_got = batched_timing_recursion_sparse(adv, 6)
    np.testing.assert_array_equal(t_got, t_ref)
    assert not np.any(np.isnan(t_got))


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 9), st.integers(0, 10_000))
def test_property_missing_mask_survives_round_trip(n, seed):
    """missing_mask is the sanctioned absent-arc test: it must identify
    exactly the -inf holes through dense -> sparse -> dense, and treat a
    huge-but-finite f32 value as a real arc, not padding."""
    rng = np.random.default_rng(seed)
    W = random_dense_batch(rng, 6, n, density=0.3)
    back = edge_batch_to_dense(dense_to_edge_batch(W))
    np.testing.assert_array_equal(missing_mask(back), missing_mask(W))
    np.testing.assert_array_equal(missing_mask(W), np.isneginf(W))
    assert bool(missing_mask(np.float32(NEG_INF)))
    assert not bool(missing_mask(np.float32(-3.0e38)))  # finite in f32


def test_jax_sparse_matches_numpy_sparse():
    jax = pytest.importorskip("jax")
    from repro.core.maxplus_sparse import batched_cycle_time_sparse_jax

    rng = np.random.default_rng(8)
    W = random_dense_batch(rng, 16, 10, density=0.4)
    eb = dense_to_edge_batch(W)
    ref = batched_cycle_time_sparse(eb)
    jit = jax.jit(batched_cycle_time_sparse_jax, static_argnums=3)
    got = np.asarray(jit(eb.src, eb.dst, eb.w.astype(np.float32), 10))
    finite = np.isfinite(ref)
    np.testing.assert_array_equal(finite, np.isfinite(got))
    np.testing.assert_allclose(got[finite], ref[finite], rtol=1e-4, atol=1e-4)
