"""DeltaPricer: the incremental cycle-time certificate must agree with
full Karp from scratch after *any* move sequence — bit-identical under
f64, within tolerance under f32 — including moves that disconnect and
reconnect the graph."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro.core as C
from repro.core.maxplus_sparse import (
    NEG_INF,
    DeltaPricer,
    EdgeBatch,
    batched_cycle_time_sparse,
)
from repro.core.topologies import search_overlays_delta, search_overlays_jit


def _fresh_tau(dp: DeltaPricer, n: int) -> float:
    """Full Karp from scratch on the pricer's current graph."""
    src, dst, w = dp.graph()
    return float(batched_cycle_time_sparse(EdgeBatch(
        src[None].astype(np.int32), dst[None].astype(np.int32),
        w[None].astype(np.float64), n))[0])


def _initial_graph(rng, n, slots, integer):
    """Slot arrays: ring + random arcs in [0, slots), self-loops after.
    Integer weights make every Karp quantity exactly representable, so
    f64 agreement can be asserted bitwise."""
    S = slots + n
    src = np.zeros(S, dtype=np.int64)
    dst = np.zeros(S, dtype=np.int64)

    def draw_w(k):
        if integer:
            return rng.integers(1, 50, size=k).astype(np.float64)
        return rng.uniform(0.5, 50.0, size=k)

    w = np.full(S, NEG_INF, dtype=np.float64)
    src[:n] = np.arange(n)
    dst[:n] = (np.arange(n) + 1) % n  # ring keeps it strongly connected
    w[:n] = draw_w(n)
    for s in range(n, slots):
        if rng.random() < 0.5:
            u, v = rng.integers(0, n, size=2)
            src[s], dst[s], w[s] = u, v, draw_w(1)[0]
    src[slots:] = dst[slots:] = np.arange(n)  # comp self-loops
    w[slots:] = draw_w(n)
    return src, dst, w


def _random_moves(rng, dp, n, slots, n_moves, integer):
    """Apply random slot rewrites (swap endpoints / re-weight / drop /
    revive), checking tau against the from-scratch oracle after each."""
    mismatch = 0.0
    for _ in range(n_moves):
        k = int(rng.integers(1, 3))  # 1-2 slots per move (2-opt shape)
        sl = rng.choice(slots, size=k, replace=False).astype(np.int64)
        su = rng.integers(0, n, size=k)
        du = rng.integers(0, n, size=k)
        if integer:
            wu = rng.integers(1, 50, size=k).astype(np.float64)
        else:
            wu = rng.uniform(0.5, 50.0, size=k)
        drop = rng.random(size=k) < 0.3  # disconnect pressure
        wu = np.where(drop, np.full(k, NEG_INF), wu)
        dp.update(sl, su, du, wu)
        mismatch = max(mismatch, abs(dp.tau - _fresh_tau(dp, n)))
    return mismatch


@settings(max_examples=15, deadline=None)
@given(st.integers(3, 10), st.integers(0, 2 ** 31 - 1))
def test_delta_tau_bit_identical_to_full_karp_f64(n, seed):
    rng = np.random.default_rng(seed)
    slots = 3 * n
    src, dst, w = _initial_graph(rng, n, slots, integer=True)
    dp = DeltaPricer(src, dst, w, n)
    assert dp.tau == _fresh_tau(dp, n)
    mismatch = _random_moves(rng, dp, n, slots, n_moves=40, integer=True)
    assert mismatch == 0.0, f"delta tau drifted from Karp by {mismatch}"
    assert sum(dp.stats.values()) >= 40  # every commit took *some* path


def test_fast_path_actually_fires():
    """Certificate reuse is the speedup: on a 16-node graph random
    single-slot moves must mostly price without a full Karp pass."""
    rng = np.random.default_rng(2)
    n, slots = 16, 48
    src, dst, w = _initial_graph(rng, n, slots, integer=True)
    dp = DeltaPricer(src, dst, w, n)
    mismatch = _random_moves(rng, dp, n, slots, n_moves=60, integer=True)
    assert mismatch == 0.0
    assert dp.stats["fast"] > 0


@settings(max_examples=10, deadline=None)
@given(st.integers(3, 10), st.integers(0, 2 ** 31 - 1))
def test_delta_tau_matches_full_karp_continuous_weights(n, seed):
    rng = np.random.default_rng(seed)
    slots = 3 * n
    src, dst, w = _initial_graph(rng, n, slots, integer=False)
    dp = DeltaPricer(src, dst, w, n)
    mismatch = _random_moves(rng, dp, n, slots, n_moves=30, integer=False)
    assert mismatch <= 1e-9 * 50.0


def test_f32_pricer_stays_within_tolerance_and_reanchors():
    rng = np.random.default_rng(11)
    n, slots = 8, 24
    src, dst, w = _initial_graph(rng, n, slots, integer=False)
    dp = DeltaPricer(src, dst, w, n, dtype=np.float32)
    for t in range(30):
        sl = np.array([int(rng.integers(0, slots))])
        dp.update(sl, rng.integers(0, n, 1), rng.integers(0, n, 1),
                  rng.uniform(0.5, 50.0, 1))
        if (t + 1) % 10 == 0:
            dp.reanchor()
        assert abs(dp.tau - _fresh_tau(dp, n)) <= 1e-3 * 50.0
    assert dp.stats["reanchor"] >= 3


def test_price_does_not_mutate_until_commit():
    rng = np.random.default_rng(5)
    n, slots = 6, 18
    src, dst, w = _initial_graph(rng, n, slots, integer=True)
    dp = DeltaPricer(src, dst, w, n)
    tau0 = dp.tau
    g0 = dp.graph()
    pm = dp.price(np.array([0]), np.array([2]), np.array([4]),
                  np.array([40.0]))
    assert dp.tau == tau0
    for a, b in zip(dp.graph(), g0):
        np.testing.assert_array_equal(a, b)
    dp.commit(pm)
    assert dp.tau == _fresh_tau(dp, n)


def test_force_full_is_the_oracle():
    rng = np.random.default_rng(9)
    n, slots = 7, 21
    src, dst, w = _initial_graph(rng, n, slots, integer=True)
    dp = DeltaPricer(src, dst, w, n)
    sl = np.array([1, 2])
    su, du = np.array([0, 3]), np.array([2, 5])
    wu = np.array([10.0, 20.0])
    fast = dp.price(sl, su, du, wu)
    full = dp.price(sl, su, du, wu, force_full=True)
    assert full.kind == "reanchor"
    assert fast.tau == full.tau


# --- the delta-engine search built on the pricer --------------------------


def _gaia_problem():
    u = C.make_underlay("gaia")
    M, Tc = C.WORKLOADS["inaturalist"]
    tp = C.TrainingParams(model_size_mbits=M, local_steps=1)
    return u.connectivity_graph(comp_time_ms=Tc), tp


def test_search_overlays_delta_matches_jit_quality_on_gaia():
    gc, tp = _gaia_problem()
    stats = {}
    ov = search_overlays_delta(gc, tp, n_restarts=4, n_steps=300,
                               delta_max=3, seed=0, stats_out=stats)
    assert ov.name == "delta_rewire"
    ring = C.design_overlay("ring", gc, tp)
    assert ov.cycle_time_ms <= ring.cycle_time_ms + 1e-9
    for (i, j) in ov.edges:
        assert gc.has_edge(i, j)
    assert stats["proposals"] == 4 * 300
    # the whole point: most accepted proposals avoid the full-Karp path
    assert stats["fast"] + stats["propagated"] > stats["reanchor"]


def test_search_overlays_delta_full_pricing_same_quality():
    gc, tp = _gaia_problem()
    dl = search_overlays_delta(gc, tp, n_restarts=2, n_steps=150, seed=3)
    fl = search_overlays_delta(gc, tp, n_restarts=2, n_steps=150, seed=3,
                               pricing="full")
    assert np.isfinite(dl.cycle_time_ms) and np.isfinite(fl.cycle_time_ms)
    ring = C.design_overlay("ring", gc, tp)
    assert dl.cycle_time_ms <= ring.cycle_time_ms + 1e-9
    assert fl.cycle_time_ms <= ring.cycle_time_ms + 1e-9


def test_search_jit_auto_delegates_to_delta_above_threshold(monkeypatch):
    import repro.core.topologies as T

    gc, tp = _gaia_problem()
    called = {}
    orig = T.search_overlays_delta

    def spy(*a, **kw):
        called["yes"] = True
        return orig(*a, **kw)

    monkeypatch.setattr(T, "search_overlays_delta", spy)
    monkeypatch.setattr(T, "_DELTA_ENGINE_MIN_N", 2)
    ov = search_overlays_jit(gc, tp, n_restarts=2, n_steps=16, seed=0)
    assert called.get("yes") and ov.name == "sparse_rewire"
    called.clear()
    monkeypatch.setattr(T, "_DELTA_ENGINE_MIN_N", 10_000)
    ov = search_overlays_jit(gc, tp, n_restarts=2, n_steps=16, seed=0,
                             engine="delta")
    assert called.get("yes") and ov.name == "sparse_rewire"
