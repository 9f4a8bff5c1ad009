"""Max-plus algebra layer: Karp's algorithm, the timing recursion, and
the paper's worked examples (Appendix C)."""

import math

import pytest
from hypothesis import given, settings, strategies as st

from repro.core.maxplus import (
    DelayDigraph,
    cycle_time,
    critical_circuit,
    empirical_cycle_time,
    is_strongly_connected,
    max_cycle_mean,
    timing_recursion,
)


def ring_graph(delays):
    n = len(delays)
    d = {(i, (i + 1) % n): delays[i] for i in range(n)}
    for i in range(n):
        d[(i, i)] = 0.0
    return DelayDigraph(tuple(range(n)), d)


def test_appendix_c_three_node_example():
    """Fig. 5a: undirected tree tau=3, directed ring tau=8/3."""
    und = DelayDigraph((1, 2, 3), {
        (1, 2): 1.0, (2, 1): 1.0, (2, 3): 3.0, (3, 2): 3.0,
        (1, 1): 0.0, (2, 2): 0.0, (3, 3): 0.0,
    })
    ring = DelayDigraph((1, 2, 3), {
        (1, 2): 1.0, (2, 3): 3.0, (3, 1): 4.0,
        (1, 1): 0.0, (2, 2): 0.0, (3, 3): 0.0,
    })
    assert cycle_time(und) == pytest.approx(3.0)
    assert cycle_time(ring) == pytest.approx(8.0 / 3.0)


def test_appendix_c_chain_vs_ring_family():
    """Fig. 5b: chain tau=n, ring tau=(4n-2)/(n+1) < 4."""
    for n in (3, 5, 9):
        # chain 1-2-...-n-(n+1) with delays 1 except last link n
        d = {}
        for i in range(1, n):
            d[(i, i + 1)] = 1.0
            d[(i + 1, i)] = 1.0
        d[(n, n + 1)] = float(n)
        d[(n + 1, n)] = float(n)
        for i in range(1, n + 2):
            d[(i, i)] = 0.0
        chain = DelayDigraph(tuple(range(1, n + 2)), d)
        assert cycle_time(chain) == pytest.approx(n)
        ring_d = {(i, i + 1): 1.0 for i in range(1, n)}
        ring_d[(n, n + 1)] = float(n)
        ring_d[(n + 1, 1)] = float(n + (n - 1))
        for i in range(1, n + 2):
            ring_d[(i, i)] = 0.0
        ring = DelayDigraph(tuple(range(1, n + 2)), ring_d)
        assert cycle_time(ring) == pytest.approx((4 * n - 2) / (n + 1))


def test_self_loop_only():
    g = DelayDigraph((0,), {(0, 0): 5.0})
    assert cycle_time(g) == pytest.approx(5.0)


def test_ring_cycle_time_is_mean():
    g = ring_graph([1.0, 2.0, 3.0, 6.0])
    assert cycle_time(g) == pytest.approx(3.0)


def test_critical_circuit_recovers_tau():
    g = ring_graph([1.0, 2.0, 3.0, 6.0])
    tau, circ = critical_circuit(g)
    assert tau == pytest.approx(3.0)
    assert len(circ) >= 2


@settings(max_examples=40, deadline=None)
@given(st.lists(st.floats(0.1, 100.0), min_size=2, max_size=8))
def test_property_ring_mean(delays):
    """Property: ring cycle time == mean of edge delays (single circuit)."""
    g = ring_graph(delays)
    assert cycle_time(g) == pytest.approx(sum(delays) / len(delays), rel=1e-9)


@settings(max_examples=25, deadline=None)
@given(
    st.integers(3, 6),
    st.data(),
)
def test_property_recursion_slope_matches_karp(n, data):
    """The paper's central identity: lim t_i(k)/k = max cycle mean."""
    delays = {}
    for i in range(n):
        delays[(i, (i + 1) % n)] = data.draw(st.floats(0.5, 20.0))
        delays[(i, i)] = data.draw(st.floats(0.0, 5.0))
        # random extra chord
        j = data.draw(st.integers(0, n - 1))
        if j != i:
            delays[(i, j)] = data.draw(st.floats(0.5, 20.0))
    g = DelayDigraph(tuple(range(n)), delays)
    tau = cycle_time(g)
    est = empirical_cycle_time(g, num_rounds=400)
    assert est == pytest.approx(tau, rel=0.05, abs=0.05)


@settings(max_examples=25, deadline=None)
@given(st.integers(3, 7), st.data())
def test_property_adding_edge_cannot_decrease_reachability(n, data):
    """Adding an edge to a strong digraph can only increase (or keep) the
    max cycle mean (more circuits to maximize over)."""
    delays = {(i, (i + 1) % n): data.draw(st.floats(1.0, 10.0)) for i in range(n)}
    for i in range(n):
        delays[(i, i)] = 0.0
    g = DelayDigraph(tuple(range(n)), delays)
    tau0 = cycle_time(g)
    i = data.draw(st.integers(0, n - 1))
    j = data.draw(st.integers(0, n - 1))
    if i == j or (i, j) in delays:
        return
    delays2 = dict(delays)
    delays2[(i, j)] = data.draw(st.floats(1.0, 10.0))
    tau1 = cycle_time(DelayDigraph(tuple(range(n)), delays2))
    assert tau1 >= tau0 - 1e-9


def test_timing_recursion_monotone_nondecreasing_increments():
    g = ring_graph([2.0, 4.0])
    t = timing_recursion(g, 50)
    for series in t.values():
        diffs = [b - a for a, b in zip(series, series[1:])]
        assert all(d >= -1e-9 for d in diffs)


def test_strongly_connected_detection():
    g = DelayDigraph((0, 1, 2), {(0, 1): 1.0, (1, 0): 1.0, (1, 2): 1.0})
    assert not is_strongly_connected(g)
    g2 = DelayDigraph((0, 1, 2), {(0, 1): 1.0, (1, 2): 1.0, (2, 0): 1.0})
    assert is_strongly_connected(g2)
