"""Mixing-rate pricing: the convergence half of (τ, ρ) co-design.

Key identities under test:

* closed-form contraction factors — K_n under Metropolis is exact full
  averaging (ρ = 0), the undirected cycle C_n under local-degree has
  eigenvalues ``1/3 + (2/3)·cos(2πk/n)``, the star S_n under Metropolis
  is ``I − L/n`` with ρ = 1 − 1/n, and the deployed directed-ring
  matrix ``(I + P)/2`` is circulant (normal), so its singular values
  are eigenvalue moduli and ρ = cos(π/n) — each checked in f64 and f32
  (hypothesis over n in [3, 64]);
* the batched eigvalsh/SVD paths are *bit-identical* to a per-matrix
  ``numpy.linalg`` oracle loop on random doubly-stochastic stacks
  (same LAPACK driver per slice), and the jittable JAX twin agrees to
  f32 tolerance;
* ``batched_mixing_matrices`` over an activation-mask stack equals the
  per-row :func:`repro.core.consensus.local_degree_matrix` /
  ``metropolis_matrix`` loop exactly, with all-zero rows yielding the
  identity;
* a budget-1.0 MATCHA schedule is deterministic, so its empirical
  ``E[WᵀW]`` collapses to ``WᵀW`` and the expected contraction equals
  the fixed-matrix ρ;
* the auto-family arbitration flips with the objective: on Gaia the
  ring wins under ``objective="tau"`` (the paper's Table 1 regime) and
  MATCHA wins under ``objective="time_to_eps"`` (mixing-per-traffic
  finally visible to the designer).
"""

import math

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro.core as C
from repro.core.consensus import (
    is_doubly_stochastic,
    local_degree_matrix,
    metropolis_matrix,
    ring_matrix,
    spectral_gap,
)
from repro.core.delays import TrainingParams
from repro.core.mixing import (
    OBJECTIVES,
    RHO_FLOOR,
    batched_mixing_matrices,
    batched_rho,
    batched_spectral_gap,
    contraction_from_gram,
    matcha_expected_gram,
    mixing_matrix,
    overlay_mixing_matrix,
    overlay_rho,
    overlay_rho_batch,
    pareto_frontier,
    schedule_rho,
    score_estimate,
    wall_clock_to_eps,
)
from repro.core.schedule import FixedSchedule, ScheduleEstimate
from repro.dynamics import design_best_schedule, design_schedule_portfolio


def gaia_setup(s=1):
    M, Tc = C.WORKLOADS["inaturalist"]
    u = C.make_underlay("gaia")
    gc = u.connectivity_graph(comp_time_ms=Tc)
    tp = TrainingParams(model_size_mbits=M, local_steps=s)
    return u, gc, tp


def both_arcs(pairs):
    """Undirected pair list -> the both-directions arc list the repo uses."""
    return [a for (i, j) in pairs for a in ((i, j), (j, i))]


def complete_edges(n):
    return both_arcs([(i, j) for i in range(n) for j in range(i + 1, n)])


def cycle_edges(n):
    return both_arcs([(i, (i + 1) % n) for i in range(n)])


def star_edges(n):
    return both_arcs([(0, j) for j in range(1, n)])


# ---------------------------------------------------------------------------
# Closed-form contraction factors (hypothesis over n, f64 and f32)


@settings(max_examples=25, deadline=None)
@given(st.integers(3, 64))
def test_complete_graph_metropolis_is_exact_averaging(n):
    # K_n Metropolis: every weight is 1/n, W = (1/n)·11ᵀ exactly, so the
    # deflated matrix is 0 and ρ = 0 / gap = 1 up to one LAPACK solve.
    W = mixing_matrix(n, complete_edges(n), rule="metropolis")
    assert np.allclose(W, np.full((n, n), 1.0 / n), atol=1e-15)
    rho = batched_rho(W[None], symmetric=True)[0]
    assert rho == pytest.approx(0.0, abs=1e-12)
    assert batched_spectral_gap(W[None], symmetric=True)[0] == pytest.approx(
        1.0, abs=1e-12
    )
    rho32 = batched_rho(W[None].astype(np.float32), symmetric=True)[0]
    assert rho32.dtype == np.float32
    assert float(rho32) == pytest.approx(0.0, abs=1e-5)


@settings(max_examples=25, deadline=None)
@given(st.integers(3, 64))
def test_cycle_local_degree_matches_circulant_eigenvalues(n):
    # Undirected C_n, local-degree: every weight 1/3, diagonal 1/3 —
    # a circulant with eigenvalues 1/3 + (2/3)·cos(2πk/n).
    W = mixing_matrix(n, cycle_edges(n), rule="local_degree")
    assert is_doubly_stochastic(W)
    k = np.arange(1, n)
    expected = float(np.max(np.abs(1.0 / 3.0 + (2.0 / 3.0) * np.cos(2 * np.pi * k / n))))
    assert batched_rho(W[None], symmetric=True)[0] == pytest.approx(
        expected, abs=1e-12
    )
    # SVD path agrees on the symmetric matrix, and so does the scalar
    # consensus-module oracle.
    assert batched_rho(W[None])[0] == pytest.approx(expected, abs=1e-10)
    assert spectral_gap(W) == pytest.approx(1.0 - expected, abs=1e-10)
    rho32 = batched_rho(W[None].astype(np.float32), symmetric=True)[0]
    assert rho32.dtype == np.float32
    assert float(rho32) == pytest.approx(expected, abs=1e-5)


@settings(max_examples=25, deadline=None)
@given(st.integers(3, 64))
def test_star_metropolis_rho_is_one_minus_one_over_n(n):
    # S_n Metropolis: center degree n−1, leaves degree 1, every edge
    # weight 1/n → W = I − L/n; star-Laplacian eigenvalues {0, 1^(n−2), n}
    # give W eigenvalues {1, (1 − 1/n)^(n−2), 0} and ρ = 1 − 1/n.
    W = mixing_matrix(n, star_edges(n), rule="metropolis")
    assert is_doubly_stochastic(W)
    expected = 1.0 - 1.0 / n
    assert batched_rho(W[None], symmetric=True)[0] == pytest.approx(
        expected, abs=1e-12
    )
    rho32 = batched_rho(W[None].astype(np.float32), symmetric=True)[0]
    assert rho32.dtype == np.float32
    assert float(rho32) == pytest.approx(expected, abs=1e-5)


@settings(max_examples=25, deadline=None)
@given(st.integers(3, 64))
def test_directed_ring_half_lazy_rho_is_cos_pi_over_n(n):
    # The deployed ring matrix (I + P)/2 is circulant hence normal: its
    # singular values are the eigenvalue *moduli* |(1 + ω^k)/2| =
    # |cos(πk/n)|, so ρ = cos(π/n) — not the real part 1/2 + cos(2π/n)/2.
    W = ring_matrix(n, list(range(n)))
    expected = math.cos(math.pi / n)
    assert batched_rho(W[None])[0] == pytest.approx(expected, abs=1e-12)
    rho32 = batched_rho(W[None].astype(np.float32))[0]
    assert rho32.dtype == np.float32
    assert float(rho32) == pytest.approx(expected, abs=1e-5)


# ---------------------------------------------------------------------------
# Batched paths vs per-matrix numpy.linalg oracle (bit-consistency)


def _sinkhorn_stack(B, n, seed):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(B):
        A = rng.random((n, n)) + 0.1
        for _ in range(80):
            A = A / A.sum(axis=1, keepdims=True)
            A = A / A.sum(axis=0, keepdims=True)
        out.append(A)
    return np.stack(out)


def test_batched_svd_path_bit_matches_per_matrix_oracle():
    W = _sinkhorn_stack(7, 9, seed=3)
    n = W.shape[-1]
    batched = batched_rho(W)
    oracle = np.array(
        [
            np.linalg.svd(W[k] - np.asarray(1.0 / n, dtype=W.dtype),
                          compute_uv=False)[0]
            for k in range(len(W))
        ]
    )
    # Same LAPACK driver per slice: bit-identical, not just close.
    assert np.array_equal(batched, oracle)


def test_batched_eigvalsh_path_bit_matches_per_matrix_oracle():
    A = _sinkhorn_stack(6, 8, seed=4)
    W = 0.5 * (A + np.transpose(A, (0, 2, 1)))  # symmetric, still d.s.
    n = W.shape[-1]
    batched = batched_rho(W, symmetric=True)
    oracle = []
    for k in range(len(W)):
        M = W[k] - np.asarray(1.0 / n, dtype=W.dtype)
        lam = np.linalg.eigvalsh(0.5 * (M + M.T))
        oracle.append(np.maximum(np.abs(lam[0]), np.abs(lam[-1])))
    assert np.array_equal(batched, np.asarray(oracle))
    # ...and the symmetric fast path agrees with the general SVD path.
    assert np.allclose(batched, batched_rho(W), atol=1e-12)


def test_jax_twin_matches_numpy_to_f32_tolerance():
    jax = pytest.importorskip("jax")
    from repro.core.mixing import batched_rho_jax, batched_spectral_gap_jax

    W = _sinkhorn_stack(4, 6, seed=5)
    ref = batched_rho(W)
    got = np.asarray(jax.jit(lambda x: batched_rho_jax(x))(W))
    assert np.allclose(got, ref, atol=1e-5)
    gap = np.asarray(jax.jit(lambda x: batched_spectral_gap_jax(x))(W))
    assert np.allclose(gap, 1.0 - ref, atol=1e-5)


# ---------------------------------------------------------------------------
# Batched matrix construction vs the per-row consensus loop


def _random_mask_pool(n, seed, B=5, density=0.7):
    rng = np.random.default_rng(seed)
    pairs = [(i, j) for i in range(n) for j in range(i + 1, n)]
    keep = rng.choice(len(pairs), size=max(n, len(pairs) // 2), replace=False)
    arcs = both_arcs([pairs[k] for k in sorted(keep)])
    src = np.asarray([a for a, _ in arcs], dtype=np.int64)
    dst = np.asarray([b for _, b in arcs], dtype=np.int64)
    on = rng.random((B, len(arcs) // 2)) < density
    masks = np.repeat(on, 2, axis=1).astype(np.float64)
    return arcs, src, dst, masks


@pytest.mark.parametrize("rule", ["local_degree", "metropolis"])
def test_batched_matrices_equal_per_row_consensus_loop(rule):
    arcs, src, dst, masks = _random_mask_pool(8, seed=0)
    W = batched_mixing_matrices(8, src, dst, masks, rule=rule)
    build = local_degree_matrix if rule == "local_degree" else metropolis_matrix
    for b in range(len(masks)):
        edges = [arcs[e] for e in range(len(arcs)) if masks[b, e]]
        assert np.array_equal(W[b], build(8, edges))


def test_all_zero_activation_row_is_identity():
    arcs, src, dst, masks = _random_mask_pool(6, seed=1, B=3)
    masks[1] = 0.0
    W = batched_mixing_matrices(6, src, dst, masks)
    assert np.array_equal(W[1], np.eye(6))
    assert batched_rho(W[[1]], symmetric=True)[0] == pytest.approx(1.0)


def test_unknown_rule_rejected():
    with pytest.raises(ValueError, match="weight rule"):
        mixing_matrix(3, cycle_edges(3), rule="nope")
    with pytest.raises(ValueError, match="weight rule"):
        batched_mixing_matrices(
            3,
            np.asarray([0], dtype=np.int64),
            np.asarray([1], dtype=np.int64),
            np.ones((1, 1)),
            rule="nope",
        )


# ---------------------------------------------------------------------------
# Overlay / schedule pricing on the measured Gaia graph


def test_overlay_matrices_mirror_deployed_plans():
    _, gc, tp = gaia_setup()
    n = gc.num_silos
    ring = C.design_overlay("ring", gc, tp)
    star = C.design_overlay("star", gc, tp)
    mst = C.design_overlay("mst", gc, tp)
    Wr = overlay_mixing_matrix(ring, n, silos=tuple(gc.silos))
    assert batched_rho(Wr[None])[0] == pytest.approx(math.cos(math.pi / n))
    Ws = overlay_mixing_matrix(star, n, silos=tuple(gc.silos))
    assert np.array_equal(Ws, np.full((n, n), 1.0 / n))
    Wm = overlay_mixing_matrix(mst, n, silos=tuple(gc.silos))
    assert is_doubly_stochastic(Wm)
    # One batched SVD over the pool equals the per-overlay scalars.
    pool = [ring, star, mst]
    rhos = overlay_rho_batch(pool, n, silos=tuple(gc.silos))
    for k, ov in enumerate(pool):
        assert rhos[k] == pytest.approx(
            overlay_rho(ov, n, silos=tuple(gc.silos)), abs=1e-12
        )
    # Trees mix slower than the optimal ring walk on the same n.
    assert rhos[1] < rhos[0] < rhos[2]


def test_budget_one_matcha_gram_collapses_to_fixed_matrix():
    _, gc, tp = gaia_setup()
    sched = C.matcha_schedule_from_connectivity(gc, budget=1.0)
    arcs, _ = sched._arc_pool(gc)
    index = {v: k for k, v in enumerate(gc.silos)}
    W = local_degree_matrix(
        gc.num_silos, [(index[i], index[j]) for (i, j) in arcs]
    )
    G = matcha_expected_gram(sched, gc, rounds=16, seed=0)
    assert np.allclose(G, W.T @ W, atol=1e-12)
    assert contraction_from_gram(G) == pytest.approx(
        float(batched_rho(W[None], symmetric=True)[0]), abs=1e-9
    )
    assert schedule_rho(sched, gc, rounds=16) == pytest.approx(
        contraction_from_gram(G)
    )


def test_fixed_schedule_rho_is_overlay_rho():
    _, gc, tp = gaia_setup()
    ring = C.design_overlay("ring", gc, tp)
    assert schedule_rho(FixedSchedule(ring), gc) == pytest.approx(
        overlay_rho(ring, gc.num_silos, silos=tuple(gc.silos))
    )


def test_matcha_mixes_better_per_round_average_than_it_looks():
    # At budget 0.5 the *expected* contraction beats the ring's ρ on
    # Gaia — the whole reason time_to_eps can flip the arbitration.
    _, gc, tp = gaia_setup()
    sched = C.matcha_schedule_from_connectivity(gc, budget=0.5)
    ring = C.design_overlay("ring", gc, tp)
    assert schedule_rho(sched, gc, rounds=128) < overlay_rho(
        ring, gc.num_silos, silos=tuple(gc.silos)
    )


# ---------------------------------------------------------------------------
# The composite objective, score_estimate, and the Pareto frontier


def test_wall_clock_to_eps_edge_cases():
    assert wall_clock_to_eps(100.0, 0.5) == pytest.approx(100.0 / math.log(2.0))
    assert wall_clock_to_eps(100.0, 1.0) == math.inf
    assert wall_clock_to_eps(100.0, 1.5) == math.inf
    assert math.isnan(wall_clock_to_eps(100.0, float("nan")))
    # ρ = 0 is floored, not free: STAR still pays its τ per round.
    floored = wall_clock_to_eps(100.0, 0.0)
    assert floored == pytest.approx(100.0 / -math.log(RHO_FLOOR))
    assert floored > 0.0
    # Monotone: slower mixing at equal τ can only cost more.
    rhos = [0.0, 0.3, 0.9, 0.99]
    scores = [wall_clock_to_eps(100.0, r) for r in rhos]
    assert scores == sorted(scores)


def test_score_estimate_objectives():
    est = ScheduleEstimate(tau_ms=120.0, ci95_ms=0.0, per_seed_ms=(120.0,), rho=0.5)
    assert score_estimate(est, "tau") == pytest.approx(120.0)
    assert score_estimate(est, "time_to_eps") == pytest.approx(
        wall_clock_to_eps(120.0, 0.5)
    )
    assert est.time_to_eps_score == pytest.approx(wall_clock_to_eps(120.0, 0.5))
    unpriced = ScheduleEstimate(tau_ms=120.0, ci95_ms=0.0, per_seed_ms=(120.0,))
    assert score_estimate(unpriced, "tau") == pytest.approx(120.0)
    with pytest.raises(ValueError, match="rho"):
        score_estimate(unpriced, "time_to_eps")
    with pytest.raises(ValueError, match="objective"):
        score_estimate(est, "rounds")
    assert set(OBJECTIVES) == {"tau", "time_to_eps"}


def test_pareto_frontier_drops_dominated_points():
    taus = np.asarray([100.0, 150.0, 120.0, 200.0, 100.0])
    rhos = np.asarray([0.9, 0.5, 0.95, 0.4, 0.92])
    idx = pareto_frontier(taus, rhos)
    # index 2 dominated by 0 (slower and worse-mixing), 4 by 0 (tie on τ,
    # worse ρ); survivors sorted by τ.
    assert idx.tolist() == [0, 1, 3]
    assert np.all(np.diff(taus[idx]) >= 0)
    assert np.all(np.diff(rhos[idx]) < 0)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 30), st.integers(0, 10_000))
def test_pareto_frontier_is_exactly_the_nondominated_set(m, seed):
    rng = np.random.default_rng(seed)
    taus = rng.uniform(50.0, 500.0, size=m)
    rhos = rng.uniform(0.0, 1.0, size=m)
    idx = set(pareto_frontier(taus, rhos).tolist())

    def dominated(k):
        return any(
            taus[j] <= taus[k]
            and rhos[j] <= rhos[k]
            and (taus[j] < taus[k] or rhos[j] < rhos[k])
            for j in range(m)
        )

    for k in range(m):
        assert (k not in idx) == dominated(k)


# ---------------------------------------------------------------------------
# The acceptance criterion: auto-family arbitration flips with objective


def test_auto_picker_flips_from_ring_to_matcha_under_time_to_eps():
    _, gc, tp = gaia_setup()
    kw = dict(
        designers=("ring",),
        n_candidates=0,
        rewire_restarts=0,
        matcha_budgets=(0.5,),
        matcha_rounds=60,
        matcha_seeds=(0,),
    )
    by_tau, scored_tau = design_best_schedule(gc, tp, objective="tau", **kw)
    assert isinstance(by_tau, FixedSchedule) and by_tau.name == "ring"
    by_eps, scored_eps = design_best_schedule(
        gc, tp, objective="time_to_eps", **kw
    )
    assert by_eps.is_randomized and by_eps.name.startswith("matcha")
    assert scored_tau == scored_eps == 2
    # The flip is explained by the portfolio's own numbers: MATCHA's τ̄
    # is *worse* (the paper's Table 1 story) but its ρ is far better.
    portfolio, _ = design_schedule_portfolio(
        gc, tp, objective="time_to_eps", **kw
    )
    ests = {s.name.split("@")[0]: e for (s, e) in portfolio}
    assert ests["matcha"].tau_ms > ests["ring"].tau_ms
    assert ests["matcha"].rho < ests["ring"].rho
    assert ests["matcha"].time_to_eps_score < ests["ring"].time_to_eps_score


def test_portfolio_under_tau_skips_spectral_pricing():
    _, gc, tp = gaia_setup()
    portfolio, _ = design_schedule_portfolio(
        gc,
        tp,
        designers=("ring", "mst"),
        n_candidates=0,
        rewire_restarts=0,
        objective="tau",
    )
    assert portfolio and all(math.isnan(e.rho) for (_, e) in portfolio)
    with pytest.raises(ValueError, match="objective"):
        design_schedule_portfolio(
            gc, tp, n_candidates=0, rewire_restarts=0, objective="rounds"
        )
