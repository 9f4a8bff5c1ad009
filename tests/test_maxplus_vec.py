"""Vectorized max-plus engine: old-vs-new equivalence and batched APIs.

The legacy dict-based implementations (``*_legacy``) are the oracle: the
dense/batched engine must reproduce them exactly (same floats up to
associativity noise) on arbitrary digraphs — strongly connected or not,
cyclic or not.
"""

import math
import random

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.maxplus import (
    DelayDigraph,
    critical_circuit,
    cycle_time,
    empirical_cycle_time,
    is_strongly_connected,
    max_cycle_mean,
    max_cycle_mean_legacy,
    timing_recursion,
    timing_recursion_legacy,
)
from repro.core.maxplus_vec import (
    batched_cycle_time,
    batched_is_strongly_connected,
    batched_timing_recursion,
    cycle_time_dense,
    edges_to_matrix,
    graph_to_matrix,
    reachability_closure,
    scc_labels,
    timing_recursion_dense,
)


def random_digraph(rng, n, density=0.35, allow_negative=False):
    lo = -5.0 if allow_negative else 0.1
    delays = {}
    for i in range(n):
        for j in range(n):
            if rng.random() < density:
                delays[(i, j)] = rng.uniform(lo, 20.0)
    if not delays:
        delays[(0, 0)] = rng.uniform(0.1, 5.0)
    return DelayDigraph(tuple(range(n)), delays)


def random_strong_digraph(rng, n):
    """Ring (guarantees strong connectivity) + random chords + self loops."""
    delays = {(i, (i + 1) % n): rng.uniform(0.5, 20.0) for i in range(n)}
    for i in range(n):
        delays[(i, i)] = rng.uniform(0.0, 5.0)
        j = rng.randrange(n)
        if j != i:
            delays[(i, j)] = rng.uniform(0.5, 20.0)
    return DelayDigraph(tuple(range(n)), delays)


def test_equivalence_on_100_random_digraphs():
    """Acceptance: batched_cycle_time == legacy Karp on >= 100 digraphs,
    including disconnected, acyclic, and negative-weight instances."""
    rng = random.Random(20260729)
    graphs = []
    for trial in range(120):
        n = rng.randint(1, 9)
        g = random_digraph(
            rng, n, density=rng.uniform(0.15, 0.9),
            allow_negative=(trial % 3 == 0),
        )
        graphs.append(g)
    for g in graphs:
        legacy = max_cycle_mean_legacy(g)
        W, _ = graph_to_matrix(g)
        vec = cycle_time_dense(W)
        if legacy == -math.inf:
            assert vec == -math.inf
        else:
            assert vec == pytest.approx(legacy, rel=1e-9, abs=1e-9)


def test_batched_matches_per_graph_on_common_size():
    rng = random.Random(7)
    n = 8
    graphs = [random_digraph(rng, n, density=0.4) for _ in range(64)]
    W = np.stack([edges_to_matrix(g.delays, g.nodes) for g in graphs])
    taus = batched_cycle_time(W)
    for k, g in enumerate(graphs):
        expect = max_cycle_mean_legacy(g)
        if expect == -math.inf:
            assert taus[k] == -math.inf
        else:
            assert taus[k] == pytest.approx(expect, rel=1e-9)


def test_batched_chunking_is_invisible():
    rng = random.Random(11)
    W = np.stack(
        [edges_to_matrix(g.delays, g.nodes)
         for g in (random_digraph(rng, 6, 0.5) for _ in range(33))]
    )
    full = batched_cycle_time(W)
    tiny_chunks = batched_cycle_time(W, max_dp_bytes=W.shape[1] * 100)
    np.testing.assert_array_equal(full, tiny_chunks)


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 10), st.integers(0, 10_000))
def test_property_strong_equivalence(n, seed):
    """cycle_time (vec) == legacy Karp on random strongly-connected digraphs."""
    g = random_strong_digraph(random.Random(seed), n)
    assert is_strongly_connected(g)
    assert cycle_time(g) == pytest.approx(max_cycle_mean_legacy(g), rel=1e-9)


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 8), st.integers(0, 10_000))
def test_property_strong_connectivity_equivalence(n, seed):
    rng = random.Random(seed)
    g = random_digraph(rng, n, density=rng.uniform(0.1, 0.7))
    W, _ = graph_to_matrix(g)
    # legacy oracle: Tarjan SCC count
    from repro.core.maxplus import strongly_connected_components

    sccs = strongly_connected_components(g)
    legacy = len(sccs) == 1 and len(sccs[0]) == g.num_nodes
    assert bool(batched_is_strongly_connected(W)) == legacy


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 8), st.integers(0, 10_000))
def test_property_timing_recursion_equivalence(n, seed):
    g = random_strong_digraph(random.Random(seed), n)
    legacy = timing_recursion_legacy(g, 30)
    W, nodes = graph_to_matrix(g)
    dense = timing_recursion_dense(W, 30)
    for k, v in enumerate(nodes):
        np.testing.assert_allclose(legacy[v], dense[:, k], rtol=1e-12)
    # and the public dict API (now vectorized) agrees with its legacy self
    new = timing_recursion(g, 30)
    for v in nodes:
        np.testing.assert_allclose(legacy[v], new[v], rtol=1e-12)


@settings(max_examples=15, deadline=None)
@given(st.integers(3, 8), st.integers(0, 10_000))
def test_property_recursion_slope_is_tau(n, seed):
    """t_i(k)/k -> tau through the dense recursion (Thm 3.23)."""
    g = random_strong_digraph(random.Random(seed), n)
    tau = cycle_time(g)
    est = empirical_cycle_time(g, num_rounds=400)
    assert est == pytest.approx(tau, rel=0.05, abs=0.05)


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 8), st.integers(0, 10_000))
def test_property_critical_circuit_attains_tau(n, seed):
    """The returned circuit's own mean must equal the reported tau."""
    g = random_strong_digraph(random.Random(seed), n)
    tau, circ = critical_circuit(g)
    assert len(circ) >= 2 and circ[0] == circ[-1]
    hops = list(zip(circ[:-1], circ[1:]))
    mean = sum(g.delays[e] for e in hops) / len(hops)
    assert mean == pytest.approx(tau, rel=1e-6, abs=1e-6)


def test_batched_timing_recursion_shapes_and_slope():
    rng = random.Random(3)
    graphs = [random_strong_digraph(rng, 6) for _ in range(8)]
    W = np.stack([edges_to_matrix(g.delays, g.nodes) for g in graphs])
    series = batched_timing_recursion(W, 200)
    assert series.shape == (8, 201, 6)
    taus = batched_cycle_time(W)
    slopes = np.max((series[:, 200] - series[:, 100]) / 100.0, axis=1)
    np.testing.assert_allclose(slopes, taus, rtol=0.05, atol=0.05)


def test_scc_labels_matrix_vs_tarjan():
    rng = random.Random(5)
    for _ in range(25):
        n = rng.randint(1, 12)
        A = np.zeros((n, n), dtype=bool)
        for i in range(n):
            for j in range(n):
                if i != j and rng.random() < 0.25:
                    A[i, j] = True
        dense = scc_labels(A, dense_threshold=1024)
        tarjan = scc_labels(A, dense_threshold=0)
        # labels may differ by name but must induce the same partition
        f, g = {}, {}
        for a, b in zip(dense.tolist(), tarjan.tolist()):
            assert f.setdefault(a, b) == b
            assert g.setdefault(b, a) == a


def test_reachability_closure_tiny():
    A = np.array([[0, 1, 0], [0, 0, 1], [0, 0, 0]], dtype=bool)
    R = reachability_closure(A)
    assert R[0, 2] and R[0, 0] and not R[2, 0]


def test_jax_variant_matches_numpy():
    jax = pytest.importorskip("jax")
    rng = np.random.default_rng(0)
    B, N = 16, 10
    W = np.where(
        rng.random((B, N, N)) < 0.4,
        rng.uniform(0.1, 30.0, (B, N, N)),
        -np.inf,
    )
    ref = batched_cycle_time(W)
    from repro.core.maxplus_vec import batched_cycle_time_jax

    got = np.asarray(jax.jit(batched_cycle_time_jax)(W))
    finite = np.isfinite(ref)
    np.testing.assert_array_equal(finite, np.isfinite(got))
    # jax default f32: compare at f32 tolerance
    np.testing.assert_allclose(got[finite], ref[finite], rtol=1e-4, atol=1e-4)


def test_acyclic_and_empty_conventions():
    # pure DAG: no circuit, tau = -inf
    dag = DelayDigraph((0, 1, 2), {(0, 1): 3.0, (1, 2): 4.0})
    W, _ = graph_to_matrix(dag)
    assert cycle_time_dense(W) == -math.inf
    assert max_cycle_mean(dag) == -math.inf
    # single self loop: tau = loop weight
    loop = DelayDigraph((0,), {(0, 0): 5.0})
    assert cycle_time(loop) == pytest.approx(5.0)
