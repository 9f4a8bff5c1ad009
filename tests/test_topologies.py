"""Topology design algorithms: optimality / approximation / structural
guarantees from Sect. 3, certified against brute force on small instances."""

import math
import random

import pytest
from hypothesis import given, settings, strategies as st

import repro.core as C
from repro.core.delays import ConnectivityGraph, SiloParams, TrainingParams
from repro.core.topologies import (
    algorithm1_mbst,
    brute_force_mct,
    christofides_tour,
    delta_prim,
    evaluate_overlay,
    mst_overlay,
    ring_overlay,
    star_overlay,
    two_opt_ring_overlay,
)


def random_euclidean_gc(n, seed, access=10.0, comp=5.0):
    rng = random.Random(seed)
    pts = [(rng.uniform(0, 100), rng.uniform(0, 100)) for _ in range(n)]

    def dist(a, b):
        return math.hypot(pts[a][0] - pts[b][0], pts[a][1] - pts[b][1])

    lat = {}
    bw = {}
    for i in range(n):
        for j in range(n):
            if i != j:
                lat[(i, j)] = 4.0 + dist(i, j) * 0.1
                bw[(i, j)] = 1.0
    params = {i: SiloParams(comp, access, access) for i in range(n)}
    return ConnectivityGraph(tuple(range(n)), lat, bw, params)


TP = TrainingParams(model_size_mbits=42.88, local_steps=1)


def test_mst_optimal_undirected_edge_capacitated():
    """Prop. 3.1: the MST is optimal among undirected overlays on
    edge-capacitated graphs — certified by brute force (n=5,6)."""
    for n, seed in ((5, 0), (6, 1)):
        gc = random_euclidean_gc(n, seed, access=1e5)  # huge access => edge-cap
        mst = mst_overlay(gc, TP)
        best = brute_force_mct(gc, TP, undirected=True)
        assert mst.cycle_time_ms == pytest.approx(best.cycle_time_ms, rel=1e-6)


def test_ring_within_3n_approximation():
    """Prop. 3.3/3.6: the Christofides ring is a 3N-approximation."""
    for n, seed in ((5, 2), (6, 3)):
        gc = random_euclidean_gc(n, seed)
        ring = ring_overlay(gc, TP)
        best_und = brute_force_mct(gc, TP, undirected=True)
        # optimal (directed) <= optimal undirected, so this bound is looser
        assert ring.cycle_time_ms <= 3 * n * best_und.cycle_time_ms


def test_ring_is_a_hamiltonian_cycle():
    gc = random_euclidean_gc(8, 4)
    ring = ring_overlay(gc, TP)
    assert len(ring.edges) == 8
    outs = {i for (i, _) in ring.edges}
    ins = {j for (_, j) in ring.edges}
    assert outs == set(gc.silos) and ins == set(gc.silos)
    for v in gc.silos:
        assert ring.out_degree(v) == 1 and ring.in_degree(v) == 1


def test_two_opt_never_worse_than_christofides():
    for seed in range(3):
        gc = random_euclidean_gc(9, seed)
        r0 = ring_overlay(gc, TP)
        r1 = two_opt_ring_overlay(gc, TP)
        assert r1.cycle_time_ms <= r0.cycle_time_ms + 1e-9


def test_delta_prim_degree_bound():
    gc = random_euclidean_gc(10, 5)
    for delta in (2, 3, 4):
        tree = delta_prim(gc, lambda i, j: gc.latency_ms[(i, j)], delta)
        deg = {v: 0 for v in gc.silos}
        for (u, v) in tree:
            deg[u] += 1
            deg[v] += 1
        assert max(deg.values()) <= delta
        assert len(tree) == len(gc.silos) - 1


def test_algorithm1_beats_or_matches_star_on_node_capacitated():
    """In the node-capacitated regime low-degree overlays must win."""
    gc = random_euclidean_gc(10, 6, access=0.05)  # slow access links
    star = star_overlay(gc, TP)
    mbst = algorithm1_mbst(gc, TP)
    ring = ring_overlay(gc, TP)
    assert mbst.cycle_time_ms < star.cycle_time_ms
    assert ring.cycle_time_ms < star.cycle_time_ms


def test_christofides_tour_visits_every_node_once():
    nodes = list(range(12))
    rng = random.Random(7)
    pts = {v: (rng.uniform(0, 1), rng.uniform(0, 1)) for v in nodes}

    def w(a, b):
        return math.hypot(pts[a][0] - pts[b][0], pts[a][1] - pts[b][1])

    tour = christofides_tour(nodes, w)
    assert sorted(tour) == nodes


@settings(max_examples=15, deadline=None)
@given(st.integers(4, 8), st.integers(0, 1000))
def test_property_designed_overlays_strongly_connected(n, seed):
    gc = random_euclidean_gc(n, seed)
    from repro.core.delays import overlay_delay_digraph
    from repro.core.maxplus import is_strongly_connected

    for kind in ("mst", "ring", "delta_mbst"):
        ov = C.design_overlay(kind, gc, TP)
        dg = overlay_delay_digraph(gc, TP, ov.edges)
        assert is_strongly_connected(dg), kind


@settings(max_examples=10, deadline=None)
@given(st.integers(4, 7), st.integers(0, 100))
def test_property_slower_access_never_helps(n, seed):
    """Cycle time is monotone in access capacity for every designer."""
    for kind in ("mst", "ring"):
        fast = C.design_overlay(kind, random_euclidean_gc(n, seed, access=10.0), TP)
        slow = C.design_overlay(kind, random_euclidean_gc(n, seed, access=0.1), TP)
        assert slow.cycle_time_ms >= fast.cycle_time_ms - 1e-9


def test_brute_force_heuristic_cut_is_opt_in_and_unsound():
    """Regression for the unsound ``r >= n + 2`` early exit.

    Minimally strong digraphs can need up to 2(N-1) arcs (bidirected
    trees), so stopping at n+2 arcs can certify a suboptimal overlay.
    Construction: hub + 4 leaves, hub<->leaf latency 1, the single
    leaf-leaf pair latency 100, bandwidth effectively unlimited.  Every
    strong overlay with <= n+2 = 7 arcs must contain a directed circuit
    of length >= 3, which must use the latency-100 link (tau >= 34);
    the bidirected star needs 8 arcs and achieves tau ~= 1.
    """
    hub, leaves = "h", ["l1", "l2", "l3", "l4"]
    silos = tuple([hub] + leaves)
    lat, bw = {}, {}

    def link(a, b, latency):
        for (i, j) in ((a, b), (b, a)):
            lat[(i, j)] = latency
            bw[(i, j)] = 1e6

    for l in leaves:
        link(hub, l, 1.0)
    link("l1", "l2", 100.0)
    params = {v: SiloParams(0.0, 1e6, 1e6) for v in silos}
    gc = ConnectivityGraph(silos, lat, bw, params)
    tp = TrainingParams(model_size_mbits=1e-6, local_steps=0)

    exact = brute_force_mct(gc, tp)  # exhaustive by default now
    cut = brute_force_mct(gc, tp, exhaustive=False)
    assert exact.cycle_time_ms == pytest.approx(1.0, rel=1e-3)
    assert cut.cycle_time_ms == pytest.approx(102.0 / 3.0, rel=1e-3)
    assert exact.cycle_time_ms < cut.cycle_time_ms
    # the certified optimum is the bidirected star
    assert set(exact.edges) == {(hub, l) for l in leaves} | {
        (l, hub) for l in leaves
    }


def test_table3_reproduction_bands():
    """Gaia / AWS-NA are rebuilt from real coordinates: our cycle times
    must land within 15% of the paper's Table 3 for MST and RING and the
    RING must beat the STAR on every network."""
    from benchmarks.common import PAPER_TABLE3, cycle_times_for_network

    for net, tol in (("gaia", 0.15), ("aws_na", 0.15)):
        ct = cycle_times_for_network(net)
        paper = PAPER_TABLE3[net]
        assert abs(ct["star"] - paper[0]) / paper[0] < tol
        assert abs(ct["mst"] - paper[2]) / paper[2] < tol
        assert abs(ct["ring"] - paper[4]) / paper[4] < tol
    for net in C.NETWORK_NAMES:
        ct = cycle_times_for_network(net, overlays=("star", "ring"))
        assert ct["ring"] < ct["star"]
