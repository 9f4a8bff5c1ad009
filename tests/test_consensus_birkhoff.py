"""Consensus matrices and Birkhoff decomposition (the topology -> TPU
collective-schedule bridge)."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.birkhoff import birkhoff_decomposition, reconstruct, schedule_cost
from repro.core.consensus import (
    is_doubly_stochastic,
    local_degree_matrix,
    metropolis_matrix,
    ring_matrix,
    spectral_gap,
    star_matrix,
)


def undirected_edges(pairs):
    out = []
    for (i, j) in pairs:
        out += [(i, j), (j, i)]
    return out


def test_local_degree_rule_doubly_stochastic_on_trees():
    edges = undirected_edges([(0, 1), (1, 2), (1, 3), (3, 4)])
    A = local_degree_matrix(5, edges)
    assert is_doubly_stochastic(A)
    assert (A >= 0).all()
    # support matches overlay
    assert A[0, 2] == 0 and A[2, 0] == 0


def test_ring_matrix_doubly_stochastic():
    A = ring_matrix(6, list(range(6)))
    assert is_doubly_stochastic(A)
    assert np.allclose(np.diag(A), 0.5)


def test_star_matrix_is_full_averaging():
    A = star_matrix(5, 0)
    assert is_doubly_stochastic(A)
    w = np.random.default_rng(0).normal(size=(5, 3))
    assert np.allclose(A @ w, w.mean(0, keepdims=True))


def test_birkhoff_exact_reconstruction_ring():
    A = ring_matrix(8, list(range(8)))
    terms = birkhoff_decomposition(A)
    assert np.allclose(reconstruct(terms, 8), A, atol=1e-9)
    assert schedule_cost(terms) == 1  # a ring is ONE ppermute


def test_birkhoff_tree_cost_bounded_by_degree_plus_one():
    edges = undirected_edges([(0, 1), (1, 2), (1, 3), (3, 4), (4, 5)])
    A = local_degree_matrix(6, edges)
    terms = birkhoff_decomposition(A)
    assert np.allclose(reconstruct(terms, 6), A, atol=1e-8)
    max_deg = 3
    assert schedule_cost(terms) <= 2 * max_deg + 1


@settings(max_examples=30, deadline=None)
@given(st.integers(2, 9), st.integers(0, 10_000))
def test_property_birkhoff_roundtrip_random_ds(n, seed):
    """Random doubly stochastic (Sinkhorn) matrices decompose and
    reconstruct exactly; coefficients form a distribution."""
    rng = np.random.default_rng(seed)
    A = rng.random((n, n)) + 0.05
    for _ in range(200):
        A /= A.sum(1, keepdims=True)
        A /= A.sum(0, keepdims=True)
    terms = birkhoff_decomposition(A, tol=1e-10)
    R = reconstruct(terms, n)
    assert np.allclose(R, A, atol=1e-6)
    coeffs = np.array([c for c, _ in terms])
    assert coeffs.sum() == pytest.approx(1.0, abs=1e-9)
    assert (coeffs > 0).all()


def test_spectral_gap_ordering():
    """Denser consensus mixes faster: star > ring > chain in gap."""
    n = 8
    star = star_matrix(n, 0)
    ring = ring_matrix(n, list(range(n)))
    chain_edges = undirected_edges([(i, i + 1) for i in range(n - 1)])
    chain = local_degree_matrix(n, chain_edges)
    g_star, g_ring, g_chain = map(spectral_gap, (star, ring, chain))
    assert g_star > g_ring > g_chain > 0


def test_consensus_converges_to_mean():
    n = 8
    A = ring_matrix(n, list(range(n)))
    w = np.random.default_rng(1).normal(size=(n, 4))
    target = w.mean(0)
    x = w.copy()
    for _ in range(400):
        x = A @ x
    assert np.allclose(x, target, atol=1e-6)
