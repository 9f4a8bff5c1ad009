"""Dynamics subsystem: scenario model, event-driven simulator, and the
online re-design controller (plus the vectorized critical circuit the
controller explains bottlenecks with).

Key identities under test:

* a no-event scenario reproduces ``timing_recursion_dense`` exactly
  (bit-for-bit, not approximately);
* inside each static segment of an eventful scenario, the realized
  round-time slope matches ``cycle_time_dense`` of that segment's delay
  matrix (the Thm 3.23 identity, per epoch);
* on a seeded Gaia core-link failure the controller beats the
  non-adaptive designed overlay in rounds-by-deadline, and one re-design
  step over >= 256 candidates at N=22 completes in under a second.
"""

import math
import random
import time

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro.core as C
from repro.core.delays import TrainingParams, overlay_delay_matrix
from repro.core.maxplus import DelayDigraph, critical_circuit, critical_circuit_legacy
from repro.core.maxplus_vec import (
    critical_circuit_dense,
    cycle_time_dense,
    graph_to_matrix,
    timing_recursion_dense,
    timing_recursion_piecewise,
)
from repro.dynamics import (
    ComputeStraggler,
    ControllerConfig,
    DynamicTimeline,
    LinkDegraded,
    LinkFailed,
    LinkRestored,
    OnlineTopologyController,
    Scenario,
    SiloJoin,
    SiloLeave,
    active_subgraph,
    churn_scenario,
    design_best_overlay,
    design_best_schedule,
    link_failure_scenario,
    random_scenario,
    schedule_epoch_estimates,
    silo_degrade_scenario,
    simulate_dynamic,
    simulate_scenarios_batched,
    static_scenario,
)
from repro.fed.gossip import PlanSlot, ScheduleSlot


def gaia_setup(workload="inaturalist", s=1):
    M, Tc = C.WORKLOADS[workload]
    u = C.make_underlay("gaia")
    gc = u.connectivity_graph(comp_time_ms=Tc)
    tp = TrainingParams(model_size_mbits=M, local_steps=s)
    return u, gc, tp, Tc


# ---------------------------------------------------------------------------
# Scenario model


def test_no_event_scenario_is_single_segment_of_the_measured_network():
    u, gc, tp, Tc = gaia_setup()
    segs = static_scenario(u, Tc).segments()
    assert len(segs) == 1 and segs[0].t_end_ms == math.inf
    for e, lat in gc.latency_ms.items():
        assert segs[0].gc.latency_ms[e] == pytest.approx(lat)
        assert segs[0].gc.available_bw_gbps[e] == pytest.approx(
            gc.available_bw_gbps[e]
        )


def test_events_fold_into_piecewise_epochs():
    u, gc, tp, Tc = gaia_setup()
    link = u.core_edges[0]
    sc = Scenario(
        name="t",
        underlay=u,
        comp_time_ms=Tc,
        events=(
            LinkDegraded(t_ms=1000.0, link=link, factor=0.1),
            ComputeStraggler(t_ms=1000.0, silo=2, factor=5.0),
            LinkFailed(t_ms=3000.0, link=link),
            SiloLeave(t_ms=5000.0, silo=4),
            SiloJoin(t_ms=7000.0, silo=4),
        ),
        horizon_ms=10_000.0,
    )
    segs = sc.segments()
    # simultaneous events merge: boundaries at 1000, 3000, 5000, 7000
    assert [s.t_start_ms for s in segs] == [0.0, 1000.0, 3000.0, 5000.0, 7000.0]
    i, j = link
    # degradation scales the direct pair's available bandwidth
    assert segs[1].gc.available_bw_gbps[(i, j)] == pytest.approx(
        0.1 * segs[0].gc.available_bw_gbps[(i, j)]
    )
    # straggler scales computation
    assert segs[1].gc.silo_params[2].comp_time_ms == pytest.approx(5.0 * Tc)
    # failure re-routes: latency strictly grows, pair still reachable
    assert segs[2].gc.latency_ms[(i, j)] > segs[0].gc.latency_ms[(i, j)]
    # churn shrinks and restores the active set
    assert 4 not in segs[3].active and 4 in segs[4].active
    assert all((a, 4) not in segs[3].gc.latency_ms for a in segs[3].active)
    # inactive silo contributes no self-loop circuit
    assert segs[3].gc.silo_params[4].comp_time_ms == 0.0


def test_random_scenario_is_seed_deterministic():
    u, gc, tp, Tc = gaia_setup()
    a = random_scenario(u, Tc, seed=11, n_events=8)
    b = random_scenario(u, Tc, seed=11, n_events=8)
    assert a.events == b.events
    c = random_scenario(u, Tc, seed=12, n_events=8)
    assert a.events != c.events


def test_random_scenario_full_churn_pool_recovers():
    """Regression: at ``p_churn=1.0`` the churn candidate pool must not
    shrink monotonically — a silo whose scheduled rejoin has fired is
    eligible to leave again, so long horizons produce more departures
    than the universe could supply under the old always-grows ``away``
    set (which capped SiloLeave events at N - 3 and starved churn into
    stragglers), and every epoch keeps >= min_active silos."""
    u, gc, tp, Tc = gaia_setup()
    for seed in range(3):
        sc = random_scenario(
            u, Tc, seed=seed, horizon_ms=500_000.0, n_events=60, p_churn=1.0
        )
        leaves = [e for e in sc.events if isinstance(e, SiloLeave)]
        joins = [e for e in sc.events if isinstance(e, SiloJoin)]
        # every leave schedules its paired rejoin inside the horizon
        assert len(leaves) == len(joins)
        assert all(e.t_ms <= sc.horizon_ms for e in joins)
        # pool recovery: strictly more departures than a monotone pool
        # could ever emit (the old bug's hard cap)
        assert len(leaves) > u.num_silos - 3
        # some silo left, rejoined, and left again
        assert max(
            sum(1 for e in leaves if e.silo == v) for v in range(u.num_silos)
        ) >= 2
        # the active floor holds on every folded epoch
        assert min(len(seg.active) for seg in sc.segments()) >= 3


def test_link_restore_after_degrade_keeps_degradation():
    """degrade -> fail -> restore: the decided semantics are
    restore-to-degraded — LinkRestored undoes only the failure, the
    degradation persists until an explicit LinkDegraded(factor=1.0)."""
    u, gc, tp, Tc = gaia_setup()
    link = tuple(sorted(u.core_edges[0]))
    i, j = link
    sc = Scenario(
        name="dfr",
        underlay=u,
        comp_time_ms=Tc,
        events=(
            LinkDegraded(t_ms=1000.0, link=link, factor=0.25),
            LinkFailed(t_ms=2000.0, link=link),
            LinkRestored(t_ms=3000.0, link=link),
            LinkDegraded(t_ms=4000.0, link=link, factor=1.0),
        ),
        horizon_ms=5000.0,
    )
    segs = sc.segments()
    assert [s.t_start_ms for s in segs] == [0.0, 1000.0, 2000.0, 3000.0, 4000.0]
    bw0 = segs[0].gc.available_bw_gbps[(i, j)]
    lat0 = segs[0].gc.latency_ms[(i, j)]
    # degraded: capacity scales, path unchanged
    assert segs[1].gc.available_bw_gbps[(i, j)] == pytest.approx(0.25 * bw0)
    assert segs[1].gc.latency_ms[(i, j)] == pytest.approx(lat0)
    # failed: re-routed around the link
    assert segs[2].gc.latency_ms[(i, j)] > lat0
    # restored: the direct path is back, but STILL at the degraded
    # capacity — restore undoes the failure, not the degradation
    assert segs[3].gc.latency_ms[(i, j)] == pytest.approx(lat0)
    assert segs[3].gc.available_bw_gbps[(i, j)] == pytest.approx(0.25 * bw0)
    # only the explicit factor=1.0 degrade event returns full capacity
    assert segs[4].gc.available_bw_gbps[(i, j)] == pytest.approx(bw0)


@settings(max_examples=15, deadline=None)
@given(st.data())
def test_epoch_folding_under_membership_churn(data):
    """Property: under arbitrary initially_inactive sets and interleaved
    SiloJoin/SiloLeave streams, every folded epoch isolates its inactive
    silos (no routed pairs touch them, zero computation time — so they
    contribute no max-plus circuit), ``active_subgraph`` restriction
    loses nothing, and epoch timestamps tile [0, inf) monotonically."""
    u, gc, tp, Tc = gaia_setup()
    n = u.num_silos
    raw = data.draw(st.lists(st.integers(0, n - 1), max_size=n - 2))
    init_inactive = tuple(sorted(set(raw)))[: n - 2]
    active = set(range(n)) - set(init_inactive)
    events = []
    t = 0.0
    for _ in range(data.draw(st.integers(0, 8))):
        t += data.draw(st.floats(1.0, 500.0))
        silo = data.draw(st.integers(0, n - 1))
        if silo in active and len(active) > 1 and data.draw(st.booleans()):
            events.append(SiloLeave(t_ms=t, silo=silo))
            active.discard(silo)
        else:  # join (idempotent when already active)
            events.append(SiloJoin(t_ms=t, silo=silo))
            active.add(silo)
    sc = Scenario(
        name="churn-prop",
        underlay=u,
        comp_time_ms=Tc,
        events=tuple(events),
        horizon_ms=t + 1000.0,
        initially_inactive=init_inactive,
    )
    segs = sc.segments()
    # timestamps: start at 0, strictly increase, tile the half-line
    starts = [s.t_start_ms for s in segs]
    assert starts[0] == 0.0
    assert all(a < b for a, b in zip(starts, starts[1:]))
    assert all(
        s.t_end_ms == nxt.t_start_ms for s, nxt in zip(segs, segs[1:])
    )
    assert segs[-1].t_end_ms == math.inf
    # final epoch's active set matches the folded event stream
    assert set(segs[-1].active) == active
    for seg in segs:
        inactive = set(range(n)) - set(seg.active)
        for v in inactive:
            # zero comp time: no self-loop circuit for inactive silos
            assert seg.gc.silo_params[v].comp_time_ms == 0.0
        # no routed pair touches an inactive silo
        assert all(
            not (set(e) & inactive) for e in seg.gc.latency_ms
        )
        # restriction to the active set is lossless (isolation)
        sub = active_subgraph(seg.gc, seg.active)
        assert set(sub.silos) == set(seg.active)
        assert sub.latency_ms == seg.gc.latency_ms
        assert sub.available_bw_gbps == seg.gc.available_bw_gbps


# ---------------------------------------------------------------------------
# Event-driven simulator


def test_no_event_scenario_reproduces_static_recursion_exactly():
    u, gc, tp, Tc = gaia_setup()
    ring = C.design_overlay("ring", gc, tp)
    run = simulate_dynamic(static_scenario(u, Tc), tp, ring.edges, num_rounds=60)
    W = overlay_delay_matrix(gc, tp, ring.edges)
    assert np.array_equal(run.times, timing_recursion_dense(W, 60))


def test_per_segment_empirical_cycle_time_matches_karp():
    """On static sub-intervals the realized slope equals cycle_time_dense
    of that segment's delay matrix (per-epoch Thm 3.23)."""
    u, gc, tp, Tc = gaia_setup()
    ring = C.design_overlay("ring", gc, tp)
    sc = link_failure_scenario(
        u, Tc, t_fail_ms=60 * ring.cycle_time_ms, overlay_edges=ring.edges
    )
    run = simulate_dynamic(sc, tp, ring.edges, num_rounds=200)
    assert run.predicted_tau_ms.shape == run.empirical_tau_ms.shape == (2,)
    # both segments hold for >= 50 rounds: slopes must have converged
    for emp, pred in zip(run.empirical_tau_ms, run.predicted_tau_ms):
        assert emp == pytest.approx(pred, rel=0.02)
    assert run.predicted_tau_ms[1] > run.predicted_tau_ms[0]


def test_piecewise_recursion_single_epoch_is_dense_recursion():
    rng = np.random.default_rng(3)
    for _ in range(10):
        n = int(rng.integers(2, 9))
        W = np.where(
            rng.random((n, n)) < 0.5, rng.uniform(0.1, 30.0, (n, n)), -np.inf
        )
        a = timing_recursion_dense(W, 30)
        b = timing_recursion_piecewise(W[None], np.zeros(1), 30)
        assert np.array_equal(a, b)


def test_batched_scenarios_match_per_scenario_runs():
    u, gc, tp, Tc = gaia_setup()
    ring = C.design_overlay("ring", gc, tp)
    horizon = 100 * ring.cycle_time_ms
    scenarios = [
        random_scenario(u, Tc, seed=s, horizon_ms=horizon) for s in range(6)
    ]
    batched = simulate_scenarios_batched(scenarios, tp, ring.edges, 80)
    for b, sc in enumerate(scenarios):
        solo = simulate_dynamic(sc, tp, ring.edges, num_rounds=80)
        np.testing.assert_array_equal(batched[b], solo.times)


def test_straggler_slows_rounds_then_recovers():
    u, gc, tp, Tc = gaia_setup()
    ring = C.design_overlay("ring", gc, tp)
    t1, t2 = 30 * ring.cycle_time_ms, 60 * ring.cycle_time_ms
    sc = Scenario(
        name="straggle",
        underlay=u,
        comp_time_ms=Tc,
        events=(
            ComputeStraggler(t_ms=t1, silo=0, factor=40.0),
            ComputeStraggler(t_ms=t2, silo=0, factor=1.0),
        ),
        horizon_ms=100 * ring.cycle_time_ms,
    )
    run = simulate_dynamic(sc, tp, ring.edges, num_rounds=150)
    assert run.predicted_tau_ms[1] > run.predicted_tau_ms[0]
    assert run.predicted_tau_ms[2] == pytest.approx(run.predicted_tau_ms[0])


# ---------------------------------------------------------------------------
# Online controller (acceptance)


def adaptive_vs_static(scenario, tp, gc0, overlay, deadline_ms, **cfg_kw):
    timeline = DynamicTimeline(scenario, tp)
    timeline.set_overlay(overlay.edges)
    slot = PlanSlot(
        OnlineTopologyController(gc0, tp, overlay).plan
    )
    controller = OnlineTopologyController(
        gc0,
        tp,
        overlay,
        config=ControllerConfig(**cfg_kw),
        connectivity_provider=lambda: active_subgraph(
            timeline.current_epoch().gc, timeline.current_epoch().active
        ),
        plan_slot=slot,
    )
    while timeline.now_ms < deadline_ms:
        redesign = controller.observe_round(timeline.step())
        if redesign is not None:
            timeline.set_overlay(redesign.overlay.edges)
    adaptive_rounds = sum(
        1 for f in timeline.round_finish_ms[1:] if f <= deadline_ms
    )
    return adaptive_rounds, controller, slot


def test_controller_beats_nonadaptive_on_seeded_gaia_link_failure():
    """Acceptance: seeded Gaia link-failure scenario — the controller's
    realized rounds-by-deadline beat the non-adaptive designed overlay."""
    u, gc, tp, Tc = gaia_setup()
    ring = C.design_overlay("ring", gc, tp)
    deadline = 400 * ring.cycle_time_ms
    sc = link_failure_scenario(
        u, Tc, t_fail_ms=deadline / 3, overlay_edges=ring.edges,
        horizon_ms=deadline,
    )
    adaptive_rounds, controller, slot = adaptive_vs_static(
        sc, tp, gc, ring, deadline, seed=0
    )
    base = simulate_dynamic(sc, tp, ring.edges, num_rounds=500)
    base_rounds = base.rounds_completed_by(deadline)
    assert len(controller.redesigns) >= 1
    assert adaptive_rounds > base_rounds
    # the hot-swap hook actually fired (init + >= 1 re-design)
    assert slot.version >= 2
    # the re-design is explained by a critical circuit of the new overlay
    rd = controller.redesigns[0]
    assert len(rd.bottleneck) >= 2 and rd.bottleneck[0] == rd.bottleneck[-1]


def test_controller_detects_silo_churn_via_fast_rounds():
    """A departed silo breaks the ring: rounds get *faster* while mixing
    silently stops.  The two-sided detector must fire and re-design over
    the surviving silos."""
    u, gc, tp, Tc = gaia_setup()
    ring = C.design_overlay("ring", gc, tp)
    sc = Scenario(
        name="churn",
        underlay=u,
        comp_time_ms=Tc,
        events=(SiloLeave(t_ms=30 * ring.cycle_time_ms, silo=5),),
        horizon_ms=200 * ring.cycle_time_ms,
    )
    timeline = DynamicTimeline(sc, tp)
    timeline.set_overlay(ring.edges)
    controller = OnlineTopologyController(
        gc, tp, ring,
        connectivity_provider=lambda: active_subgraph(
            timeline.current_epoch().gc, timeline.current_epoch().active
        ),
    )
    for _ in range(120):
        redesign = controller.observe_round(timeline.step())
        if redesign is not None:
            timeline.set_overlay(redesign.overlay.edges)
    assert len(controller.redesigns) >= 1
    survivors = {v for e in controller.overlay.edges for v in e}
    assert 5 not in survivors and len(survivors) == 10


def test_churn_redesign_with_plan_slot_does_not_crash():
    """The slot's mesh axis is sized at launch: a re-design over fewer
    silos must leave the old plan running with an audit note, not raise
    from inside observe_round."""
    u, gc, tp, Tc = gaia_setup()
    ring = C.design_overlay("ring", gc, tp)
    sc = Scenario(
        name="churn",
        underlay=u,
        comp_time_ms=Tc,
        events=(SiloLeave(t_ms=30 * ring.cycle_time_ms, silo=5),),
        horizon_ms=200 * ring.cycle_time_ms,
    )
    timeline = DynamicTimeline(sc, tp)
    timeline.set_overlay(ring.edges)
    from repro.fed.topology_runtime import plan_from_overlay

    slot = PlanSlot(plan_from_overlay(ring, gc.num_silos))
    controller = OnlineTopologyController(
        gc, tp, ring,
        connectivity_provider=lambda: active_subgraph(
            timeline.current_epoch().gc, timeline.current_epoch().active
        ),
        plan_slot=slot,
    )
    version_before = slot.version
    for _ in range(120):
        redesign = controller.observe_round(timeline.step())
        if redesign is not None:
            timeline.set_overlay(redesign.overlay.edges)
    assert len(controller.redesigns) >= 1
    assert slot.version == version_before  # swap skipped, not applied
    assert any("NOT swapped" in note for _, note in slot.history)


def test_controller_membership_swaps_on_leave_and_rejoin():
    """Elastic membership: with a membership provider + MembershipSlot
    the controller reacts to SiloLeave/SiloJoin *immediately* (control
    plane, not timing inference), publishes the new active set, and
    resizes the plan slot across silo counts — no audit-note fallback."""
    from repro.fed.gossip import MembershipSlot
    from repro.fed.topology_runtime import plan_from_overlay

    u, gc, tp, Tc = gaia_setup()
    ring = C.design_overlay("ring", gc, tp)
    tau = ring.cycle_time_ms
    sc = churn_scenario(
        u, Tc, silo=5, t_leave_ms=20 * tau, t_rejoin_ms=50 * tau,
        horizon_ms=200 * tau,
    )
    timeline = DynamicTimeline(sc, tp)
    timeline.set_overlay(ring.edges)
    slot = PlanSlot(plan_from_overlay(ring, gc.num_silos))
    mem = MembershipSlot(range(u.num_silos), u.num_silos)
    controller = OnlineTopologyController(
        gc, tp, ring,
        config=ControllerConfig(seed=0, rewire_restarts=0),
        connectivity_provider=lambda: active_subgraph(
            timeline.current_epoch().gc, timeline.current_epoch().active
        ),
        plan_slot=slot,
        membership_slot=mem,
        membership_provider=timeline.current_active,
    )
    redesigns = []
    for _ in range(150):
        rd = controller.observe_round(timeline.step())
        if rd is not None:
            redesigns.append(rd)
            timeline.set_overlay(rd.overlay.edges)
    churn_rds = [rd for rd in redesigns if rd.membership is not None]
    assert len(churn_rds) == 2  # one per membership event, no extras
    survivors = tuple(v for v in range(u.num_silos) if v != 5)
    assert churn_rds[0].membership == survivors
    assert churn_rds[0].plan.n_silos == u.num_silos - 1  # resized, not skipped
    assert 5 not in {v for e in churn_rds[0].overlay.edges for v in e}
    assert churn_rds[1].membership == tuple(range(u.num_silos))
    assert churn_rds[1].plan.n_silos == u.num_silos
    assert 5 in {v for e in churn_rds[1].overlay.edges for v in e}
    # the membership slot versioned both swaps, the plan slot followed
    assert mem.version == 2 and mem.active == tuple(range(u.num_silos))
    assert slot.plan.n_silos == u.num_silos
    assert not any("NOT swapped" in note for _, note in slot.history)


def test_strike_redesign_never_resizes_plan_without_membership_swap():
    """A MembershipSlot merely *existing* must not let a strike-triggered
    (non-membership) redesign resize the plan across silo counts: without
    a membership swap this actuation carries no rebuild signal, so the
    cross-universe plan must take the audit-note path, and the
    MembershipSlot must not have moved."""
    from repro.fed.gossip import MembershipSlot
    from repro.fed.topology_runtime import plan_from_overlay

    u, gc, tp, Tc = gaia_setup()
    ring = C.design_overlay("ring", gc, tp)
    sc = Scenario(
        name="churn",
        underlay=u,
        comp_time_ms=Tc,
        events=(SiloLeave(t_ms=30 * ring.cycle_time_ms, silo=5),),
        horizon_ms=200 * ring.cycle_time_ms,
    )
    timeline = DynamicTimeline(sc, tp)
    timeline.set_overlay(ring.edges)
    slot = PlanSlot(plan_from_overlay(ring, gc.num_silos))
    mem = MembershipSlot(range(u.num_silos), u.num_silos)
    controller = OnlineTopologyController(
        gc, tp, ring,
        config=ControllerConfig(seed=0, rewire_restarts=0),
        connectivity_provider=lambda: active_subgraph(
            timeline.current_epoch().gc, timeline.current_epoch().active
        ),
        plan_slot=slot,
        membership_slot=mem,  # note: no membership_provider
    )
    for _ in range(120):
        redesign = controller.observe_round(timeline.step())
        if redesign is not None:
            timeline.set_overlay(redesign.overlay.edges)
    assert len(controller.redesigns) >= 1
    assert controller.redesigns[0].membership is None
    assert mem.version == 0  # never swapped: no membership signal
    assert slot.plan.n_silos == gc.num_silos  # plan NOT resized
    assert any("NOT swapped" in note for _, note in slot.history)


def test_controller_membership_without_connectivity_provider():
    """With only a membership signal (no measurement service) the
    controller must still design over exactly the published active set —
    restricting its launch-time estimate on a leave, and growing back
    from it on the rejoin — so the plan never disagrees with the
    MembershipSlot."""
    from repro.fed.gossip import MembershipSlot
    from repro.fed.topology_runtime import plan_from_overlay

    u, gc, tp, Tc = gaia_setup()
    ring = C.design_overlay("ring", gc, tp)
    mem = MembershipSlot(range(u.num_silos), u.num_silos)
    slot = PlanSlot(plan_from_overlay(ring, gc.num_silos))
    membership = [tuple(range(u.num_silos))]
    controller = OnlineTopologyController(
        gc, tp, ring,
        config=ControllerConfig(seed=0, rewire_restarts=0),
        plan_slot=slot,
        membership_slot=mem,
        membership_provider=lambda: membership[0],
    )
    membership[0] = tuple(v for v in range(u.num_silos) if v != 5)
    rd = controller.observe_round(ring.cycle_time_ms)
    assert rd is not None and rd.membership == membership[0]
    assert rd.plan.n_silos == u.num_silos - 1 == mem.n_active
    assert 5 not in {v for e in rd.overlay.edges for v in e}
    membership[0] = tuple(range(u.num_silos))
    rd2 = controller.observe_round(ring.cycle_time_ms)
    assert rd2 is not None and rd2.plan.n_silos == u.num_silos
    assert slot.plan.n_silos == u.num_silos == mem.n_active


@pytest.mark.slow
def test_train_dynamic_random_churn_rebuilds_mesh_and_state():
    """Acceptance: ``train.py --reduced --dynamic --scenario random`` with
    ``p_churn > 0`` completes end-to-end; the mesh/state are rebuilt on a
    SiloLeave and again on the paired SiloJoin, surviving silos'
    parameters are bit-identical across every migration, and joiners
    re-enter at the survivors' consensus average."""
    import os
    import re
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [
            sys.executable, "-m", "repro.launch.train",
            "--arch", "internlm2-1.8b", "--reduced", "--dynamic",
            "--scenario", "random", "--p-churn", "1.0",
            "--scenario-seed", "0", "--verify-migration",
            "--steps", "35", "--seq-len", "16", "--batch-per-silo", "2",
        ],
        capture_output=True,
        text=True,
        timeout=560,
        env=env,
    )
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-2000:]
    swaps = re.findall(
        r"membership v(\d+): (\d+) -> (\d+) silos \(left \[([\d, ]*)\], "
        r"joined \[([\d, ]*)\]\)", out)
    assert len(swaps) >= 2, out[-2000:]
    leavers = {s for _, _, _, left, _ in swaps for s in left.split(", ") if s}
    joiners = {s for _, _, _, _, jn in swaps for s in jn.split(", ") if s}
    # mesh/state rebuilt on a SiloLeave AND again on the paired SiloJoin
    assert leavers and (leavers & joiners), swaps
    shrank = any(int(a) > int(b) for _, a, b, _, _ in swaps)
    grew = any(int(a) < int(b) for _, a, b, _, _ in swaps)
    assert shrank and grew, swaps
    # every migration checked out: survivors bit-identical, joiners at
    # the consensus average (verified in-process, asserted on the log)
    rebuilds = re.findall(r"mesh\+state rebuilt, survivors-bit-identical="
                          r"(\w+), joiners-at-consensus=(\w+)", out)
    assert len(rebuilds) == len(swaps)
    assert all(s == "True" and j == "True" for s, j in rebuilds), rebuilds
    assert "membership swap(s)" in out


def test_controller_is_quiet_on_a_healthy_network():
    u, gc, tp, Tc = gaia_setup()
    ring = C.design_overlay("ring", gc, tp)
    sc = static_scenario(u, Tc)
    adaptive_rounds, controller, _ = adaptive_vs_static(
        sc, tp, gc, ring, 200 * ring.cycle_time_ms
    )
    assert controller.redesigns == []


def test_redesign_latency_256_candidates_n22_under_1s():
    """Acceptance: one controller re-design step over >= 256 candidate
    overlays at N=22 (AWS North America) in under a second."""
    M, Tc = C.WORKLOADS["inaturalist"]
    u = C.make_underlay("aws_na")
    gc = u.connectivity_graph(comp_time_ms=Tc)
    tp = TrainingParams(model_size_mbits=M, local_steps=1)
    assert u.num_silos == 22
    t0 = time.perf_counter()
    best, scored = design_best_overlay(gc, tp, n_candidates=256)
    elapsed = time.perf_counter() - t0
    assert scored >= 256
    assert elapsed < 1.0, f"re-design took {elapsed:.2f}s"
    # sanity: the search result is a real overlay on this network
    assert best.cycle_time_ms > 0 and len(best.edges) >= u.num_silos


def test_plan_slot_swap_contract():
    u, gc, tp, Tc = gaia_setup()
    ring = C.design_overlay("ring", gc, tp)
    mst = C.design_overlay("mst", gc, tp)
    from repro.fed.topology_runtime import plan_from_overlay

    slot = PlanSlot(plan_from_overlay(ring, gc.num_silos))
    seen = []
    slot.on_swap(lambda plan, version: seen.append(version))
    v = slot.swap(plan_from_overlay(mst, gc.num_silos), label="mst")
    assert v == 1 and slot.version == 1 and seen == [1]
    assert slot.history[-1] == (1, "mst")
    from repro.fed.gossip import GossipPlan

    with pytest.raises(ValueError):  # silo-count mismatch is rejected
        slot.swap(GossipPlan.from_matrix(np.eye(3)))


# ---------------------------------------------------------------------------
# Randomized schedules under dynamics


@pytest.mark.slow  # Monte-Carlo schedule sweep: ci.sh --fast skips
def test_schedule_epoch_estimates_track_the_drift():
    """Per-epoch pricing of a plan distribution: the degraded epoch's τ̄
    must exceed the healthy epoch's (the ROADMAP 'average cycle time of a
    plan distribution per epoch' item)."""
    u, gc, tp, Tc = gaia_setup()
    ms = C.matcha_schedule_from_underlay(u, 0.3)
    sc = silo_degrade_scenario(u, Tc, silo=3, t_ms=5000.0, factor=0.02)
    ests = schedule_epoch_estimates(sc, tp, ms, rounds=50, seeds=(0, 1))
    assert len(ests) == 2
    assert all(np.isfinite(e.tau_ms) for e in ests)
    assert ests[1].tau_ms > 2.0 * ests[0].tau_ms


def test_design_best_schedule_defaults_to_fixed_pool():
    u, gc, tp, Tc = gaia_setup()
    sched, scored = design_best_schedule(gc, tp, n_candidates=32,
                                         rewire_restarts=0)
    assert not sched.is_randomized
    best_overlay, _ = design_best_overlay(gc, tp, n_candidates=32,
                                          rng=np.random.default_rng(0))
    # same candidate families -> same winner class of cycle times
    assert sched.price(gc, tp).tau_ms <= best_overlay.cycle_time_ms * 1.05


def test_dynamic_timeline_steps_a_randomized_schedule():
    u, gc, tp, Tc = gaia_setup()
    ms = C.matcha_schedule_from_underlay(u, 0.4, sample_seed=2)
    sc = static_scenario(u, Tc)
    timeline = DynamicTimeline(sc, tp)
    timeline.set_schedule(ms)
    durations = [timeline.step() for _ in range(30)]
    assert all(d > 0 for d in durations)
    # round k's realized duration is reproducible from the shared counter
    timeline2 = DynamicTimeline(sc, tp)
    timeline2.set_schedule(C.matcha_schedule_from_underlay(u, 0.4,
                                                           sample_seed=2))
    assert durations == [timeline2.step() for _ in range(30)]


@pytest.mark.slow  # Monte-Carlo schedule sweep: ci.sh --fast skips
def test_controller_hot_swaps_to_randomized_schedule():
    """Acceptance: under schedule_family='matcha' a regression re-design
    re-fits the plan distribution and hot-swaps the ScheduleSlot from a
    fixed overlay to a randomized schedule."""
    u, gc, tp, Tc = gaia_setup()
    ring = C.design_overlay("ring", gc, tp)
    sc = silo_degrade_scenario(
        u, Tc, silo=3, t_ms=30 * ring.cycle_time_ms, factor=0.02,
        horizon_ms=300 * ring.cycle_time_ms,
    )
    timeline = DynamicTimeline(sc, tp)
    timeline.set_overlay(ring.edges)
    slot = ScheduleSlot(C.FixedSchedule(ring), gc.num_silos, silos=gc.silos)
    controller = OnlineTopologyController(
        gc, tp, ring,
        config=ControllerConfig(
            seed=0, schedule_family="matcha",
            matcha_budgets=(0.1, 0.2, 0.3, 0.5),
            matcha_rounds=80, matcha_seeds=(0, 1), rewire_restarts=0,
        ),
        connectivity_provider=lambda: active_subgraph(
            timeline.current_epoch().gc, timeline.current_epoch().active
        ),
        schedule_slot=slot,
    )
    for _ in range(100):
        redesign = controller.observe_round(timeline.step())
        if redesign is not None:
            timeline.set_schedule(redesign.schedule)
    assert len(controller.redesigns) >= 1
    rd = controller.redesigns[0]
    assert rd.schedule is not None and rd.schedule.is_randomized
    assert rd.overlay is None  # randomized winner carries no single overlay
    assert np.isfinite(rd.predicted_tau_ms) and rd.predicted_tau_ms > 0
    # the slot followed: init swap + redesign swap, now randomized
    assert slot.version >= 2 and slot.schedule.is_randomized
    # per-round plans keep flowing from the shared counter after the swap
    A = slot.matrix_for_round(timeline.rounds_done)
    assert np.allclose(A.sum(axis=0), 1.0) and np.allclose(A.sum(axis=1), 1.0)
    # the plant keeps stepping on the sampled topologies
    assert timeline.step() > 0


def test_redesign_under_time_to_eps_carries_rho_through_the_trace(tmp_path):
    """Co-design audit: under ``objective="time_to_eps"`` every
    re-design actuation carries the winner's (τ, ρ) pair, and both
    round-trip through the flight-recorder trace schema."""
    from repro.obs.events import FlightRecorder, validate_trace

    u, gc, tp, Tc = gaia_setup()
    ring = C.design_overlay("ring", gc, tp)
    sc = silo_degrade_scenario(
        u, Tc, silo=3, t_ms=30 * ring.cycle_time_ms, factor=0.02,
        horizon_ms=300 * ring.cycle_time_ms,
    )
    timeline = DynamicTimeline(sc, tp)
    timeline.set_overlay(ring.edges)
    slot = ScheduleSlot(C.FixedSchedule(ring), gc.num_silos, silos=gc.silos)
    trace = str(tmp_path / "codesign.jsonl")
    with FlightRecorder(trace, silo_names=list(gc.silos)) as rec:
        controller = OnlineTopologyController(
            gc, tp, ring,
            config=ControllerConfig(
                seed=0, schedule_family="matcha", objective="time_to_eps",
                matcha_budgets=(0.3, 0.5), matcha_rounds=60,
                matcha_seeds=(0,), mixing_rounds=60, rewire_restarts=0,
            ),
            connectivity_provider=lambda: active_subgraph(
                timeline.current_epoch().gc, timeline.current_epoch().active
            ),
            schedule_slot=slot,
            recorder=rec,
            silo_names=list(gc.silos),
        )
        for _ in range(100):
            redesign = controller.observe_round(timeline.step())
            if redesign is not None:
                timeline.set_schedule(redesign.schedule)
    assert len(controller.redesigns) >= 1
    rd = controller.redesigns[0]
    # the actuation itself carries the priced pair
    assert rd.objective == "time_to_eps"
    assert np.isfinite(rd.rho) and 0.0 < rd.rho < 1.0
    assert np.isfinite(rd.predicted_tau_ms) and rd.predicted_tau_ms > 0
    # ...and the trace round-trips it under schema validation
    records, problems = validate_trace(trace)
    assert problems == []
    emitted = [r for r in records if r["kind"] == "redesign"]
    assert len(emitted) == len(controller.redesigns)
    for rec_line, actuation in zip(emitted, controller.redesigns):
        assert rec_line["objective"] == "time_to_eps"
        assert rec_line["rho"] == pytest.approx(actuation.rho)


@pytest.mark.slow  # subprocess train acceptance: ci.sh --fast skips
def test_train_dynamic_matcha_completes_hot_swap():
    """Acceptance: ``train.py --dynamic --designer matcha`` completes a
    controller hot-swap to a randomized schedule (traced-consensus step,
    no per-round re-lowering)."""
    import os
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [
            sys.executable, "-m", "repro.launch.train",
            "--arch", "internlm2-1.8b", "--reduced", "--dynamic",
            "--designer", "matcha", "--scenario", "silodegrade",
            "--steps", "30", "--seq-len", "16", "--batch-per-silo", "2",
        ],
        capture_output=True,
        text=True,
        timeout=560,
        env=env,
    )
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-2000:]
    assert "matcha schedule" in out  # initial budget-swept design
    assert "controller re-design -> randomized schedule" in out, out[-2000:]
    assert "final randomized schedule" in out


# ---------------------------------------------------------------------------
# Vectorized critical circuit (controller's bottleneck explanation)


def random_strong_digraph(rng, n):
    delays = {(i, (i + 1) % n): rng.uniform(0.5, 20.0) for i in range(n)}
    for i in range(n):
        delays[(i, i)] = rng.uniform(0.0, 5.0)
        j = rng.randrange(n)
        if j != i:
            delays[(i, j)] = rng.uniform(0.5, 20.0)
    return DelayDigraph(tuple(range(n)), delays)


def test_critical_circuit_dense_matches_legacy_tau_and_attains_it():
    for seed in range(60):
        rng = random.Random(seed)
        g = random_strong_digraph(rng, rng.randint(2, 12))
        tau_l, circ_l = critical_circuit_legacy(g)
        tau, circ = critical_circuit(g)
        assert tau == pytest.approx(tau_l, rel=1e-9)
        assert len(circ) >= 2 and circ[0] == circ[-1]
        hops = list(zip(circ[:-1], circ[1:]))
        mean = sum(g.delays[e] for e in hops) / len(hops)
        assert mean == pytest.approx(tau, rel=1e-6, abs=1e-6)


def test_critical_circuit_dense_acyclic_and_self_loop():
    dag = DelayDigraph((0, 1), {(0, 1): 2.0})
    W, _ = graph_to_matrix(dag)
    assert critical_circuit_dense(W) == (-math.inf, [])
    loop = DelayDigraph((0,), {(0, 0): 7.0})
    W, _ = graph_to_matrix(loop)
    tau, circ = critical_circuit_dense(W)
    assert tau == pytest.approx(7.0) and circ == [0, 0]
