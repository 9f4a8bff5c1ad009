"""Training launcher: DPASGD over a designed topology.

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --reduced --silos 4 --topology ring --steps 50

On the CPU (``JAX_PLATFORMS=cpu``) use ``--reduced`` (tiny same-family
variant); the virtual device count is set from ``--silos``.  On a TPU
the same entry point puts one silo on each chip; ``--layers k`` cuts the
depth to whole periods of the published layer pattern while every width
stays published, e.g. one silo of internlm2-1.8b on one v5e chip:

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --layers 4 --silos 1 --seq-len 2048 --batch-per-silo 4 --steps 5

``--dynamic`` attaches the online topology controller: the WAN between
the silos is simulated from a real underlay (``--underlay``) through a
seeded event scenario (``--scenario``), each training step advances the
simulated network clock by one communication round, and when the
controller detects throughput regression it re-designs the overlay and
hot-swaps the gossip plan — the train step is re-lowered on the new plan.
Membership is *elastic*: on ``SiloLeave``/``SiloJoin`` churn
(``--scenario random`` with ``--p-churn > 0``, or the deterministic
``--scenario churn``) the controller swaps a ``MembershipSlot`` and the
loop rebuilds the device mesh over the surviving silos and migrates the
silo-stacked state — survivors keep their parameters/optimizer slots
bit-identical, leavers' shards are dropped (``--churn-checkpoint`` saves
them first), joiners re-enter at the survivors' consensus average:

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --reduced --dynamic --underlay gaia --scenario linkfail --steps 60

``--designer matcha`` trains on a *randomized* schedule (MATCHA-style
budgeted matching activation): every step samples that round's gossip
plan from a shared round counter through a ``ScheduleSlot``, and the
consensus matrix enters the jitted step as a traced argument — per-round
topologies never recompile.  Works standalone (homogeneous MATCHA over
the complete silo graph) and under ``--dynamic``, where the initial
budget is swept on the measured underlay and the controller re-fits the
distribution on drift (``--scenario silodegrade`` stresses exactly that):

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --reduced --dynamic --designer matcha --scenario silodegrade
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def main(argv=None, report=None) -> int:
    """Run the trainer on ``argv`` (default: ``sys.argv[1:]``).

    ``report``, when a dict, receives what an in-process caller checks:
    ``losses`` (each step's mean loss, read back once after the last
    step), ``state`` (the final train state, still on its devices),
    ``mesh`` and ``plan``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    depth = ap.add_mutually_exclusive_group()
    depth.add_argument("--reduced", action="store_true")
    depth.add_argument("--layers", type=int, default=0,
                       help="depth cut; widths stay published (a multiple "
                            "of the arch's layer-pattern period)")
    ap.add_argument("--silos", type=int, default=4)
    ap.add_argument("--topology", default="ring",
                    choices=["ring", "star", "chain", "none", "mst",
                             "ring_2opt", "delta_mbst"])
    ap.add_argument("--gossip-impl", default="ppermute",
                    choices=["ppermute", "einsum", "pallas", "none"])
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--batch-per-silo", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--dynamic", action="store_true",
                    help="simulate a time-varying WAN and run the online "
                         "topology controller (silo count follows the underlay; "
                         "membership is elastic: on SiloJoin/SiloLeave the "
                         "mesh/state are rebuilt over the surviving silos)")
    ap.add_argument("--designer", default="auto",
                    choices=["auto", "sparse-rewire", "delta-rewire",
                             "hierarchical", "matcha"],
                    help="overlay designer: 'sparse-rewire' designs the "
                         "initial overlay with the rewire search behind "
                         "its size-dispatched engine (needs --dynamic) "
                         "and keeps it in the controller's re-design "
                         "pool; 'delta-rewire' forces the host "
                         "delta-priced climb; 'hierarchical' clusters "
                         "the silos and composes per-cluster searches "
                         "(both need --dynamic); 'matcha' trains on "
                         "a randomized schedule (per-round sampled gossip "
                         "plans; with --dynamic the budget is swept on "
                         "the measured underlay and re-fit on drift); "
                         "default: --topology heuristic")
    ap.add_argument("--matcha-budget", type=float, default=0.5,
                    help="static-mode MATCHA activation probability C_b "
                         "(with --dynamic the budget comes from the sweep)")
    ap.add_argument("--objective", default="tau",
                    choices=["tau", "time_to_eps"],
                    help="what design/re-design optimizes (needs --dynamic): "
                         "'tau' ranks candidates on cycle time alone; "
                         "'time_to_eps' also prices each candidate's "
                         "consensus contraction rho and ranks on the "
                         "composite tau / -log(rho) — wall clock per "
                         "e-fold of consensus-error decay (Sect. 4 "
                         "time-to-accuracy framing)")
    ap.add_argument("--underlay", default="gaia")
    ap.add_argument("--workload", default="inaturalist")
    ap.add_argument("--scenario", default="linkfail",
                    choices=["linkfail", "silodegrade", "random", "static",
                             "churn"])
    ap.add_argument("--scenario-seed", type=int, default=0)
    ap.add_argument("--p-churn", type=float, default=0.15,
                    help="--scenario random: probability mass of silo "
                         "leave/rejoin churn in the event mix (elastic "
                         "membership rebuilds the mesh/state on each)")
    ap.add_argument("--churn-checkpoint", default="",
                    help="directory: a departing silo's state row is "
                         "checkpointed there before its shard is dropped")
    ap.add_argument("--trace-out", default="",
                    help="write a JSONL flight-recorder trace here (turns "
                         "on spans + metrics; render/validate it with "
                         "scripts/obs_report.py)")
    ap.add_argument("--metrics-interval", type=int, default=10,
                    help="steps between 'round' trace records (0 disables "
                         "per-round records; decision records are always "
                         "written when --trace-out is set)")
    ap.add_argument("--verify-migration", action="store_true",
                    help="after each membership rebuild, re-gather the "
                         "migrated state and verify survivors are "
                         "bit-identical and joiners sit at the consensus "
                         "average (full-model host sweep: acceptance "
                         "tests/debugging, not production loops)")
    args = ap.parse_args(argv)

    underlay = None
    silo_names = None
    if args.dynamic:
        # numpy-only imports: safe before the XLA device-count env is set
        from repro.core import make_underlay

        underlay = make_underlay(args.underlay)
        args.silos = underlay.num_silos
        # Site names for bottleneck attribution in the trace: the paper's
        # measured networks carry real city labels; synthetic ones don't.
        from repro.core.networks_data import AWS_NA_SITES, GAIA_SITES

        sites = {"gaia": GAIA_SITES, "aws_na": AWS_NA_SITES}.get(underlay.name)
        if sites is not None:
            silo_names = [name for name, _ in sites]

    if os.environ.get("JAX_PLATFORMS") == "cpu" and "XLA_FLAGS" not in os.environ:
        # virtual CPU devices, one per silo; on an accelerator the silos
        # map onto the real chips and a missing backend is an error
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={max(args.silos, 1)}")

    import contextlib

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.data import SyntheticLMStream, FederatedBatcher
    from repro.fed import (
        DPASGDConfig, init_state, make_train_step, migrate_silo_state,
        slice_silo_row,
    )
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_silo_mesh
    from repro.fed.topology_runtime import plan_for_n_silos, plan_from_overlay
    from repro.obs import enable as obs_enable, span, summary as span_summary
    from repro.obs import metrics as obs_metrics
    from repro.obs.events import FlightRecorder, run_metadata
    from repro.obs.log import get_logger
    from repro.optim import momentum

    log = get_logger("train")
    enable_compile_cache()
    devices = jax.devices()
    print(f"devices: {devices[0].platform} {devices[0].device_kind} "
          f"x{len(devices)}", flush=True)
    recorder = None
    if args.trace_out:
        obs_enable()
        recorder = FlightRecorder(
            args.trace_out,
            meta=run_metadata({
                "underlay": args.underlay if args.dynamic else None,
                "scenario": args.scenario if args.dynamic else None,
                "designer": args.designer,
                "objective": args.objective,
                "steps": args.steps,
            }),
            silo_names=silo_names,
        )
        log.info("trace", path=args.trace_out)

    cfg = (get_config(args.arch, n_layers=args.layers) if args.layers
           else get_config(args.arch))
    if args.reduced:
        cfg = cfg.reduced()
    import dataclasses

    cfg = dataclasses.replace(cfg, n_silos=args.silos)
    n = args.silos
    mesh = make_silo_mesh(n)
    opt = momentum(args.lr, 0.9)
    # Randomized schedules sample a fresh topology per round, so their
    # consensus matrix must be a *traced* step input (einsum lowering) —
    # the baked ppermute/pallas schedules would recompile every round.
    sched_mode = args.designer == "matcha" and n > 1 and \
        args.gossip_impl != "none"
    if sched_mode and args.gossip_impl not in ("einsum",):
        log.warn("gossip-impl-override",
                 "matcha lowers gossip as a traced einsum",
                 requested=args.gossip_impl, used="einsum")
    fed = DPASGDConfig(local_steps=args.local_steps,
                       gossip_impl=("einsum" if sched_mode else
                                    args.gossip_impl) if n > 1 else "none",
                       silo_axis="data")

    timeline = controller = slot = sched_slot = mem_slot = None
    if args.dynamic:
        from repro.core import (
            DEFAULT_MATCHA_BUDGETS, OVERLAY_KINDS, TrainingParams, WORKLOADS,
            design_overlay, design_schedule,
        )
        from repro.dynamics import (
            ControllerConfig, DynamicTimeline, OnlineTopologyController,
            active_subgraph, churn_scenario, link_failure_scenario,
            random_scenario, silo_degrade_scenario, static_scenario,
        )
        from repro.fed.gossip import MembershipSlot, PlanSlot, ScheduleSlot

        M, Tc = WORKLOADS[args.workload]
        tp = TrainingParams(model_size_mbits=M, local_steps=args.local_steps)
        gc0 = underlay.connectivity_graph(comp_time_ms=Tc)
        if args.designer in ("sparse-rewire", "delta-rewire",
                             "hierarchical"):
            kind = args.designer.replace("-", "_")
        else:
            kind = args.topology if args.topology in OVERLAY_KINDS else "ring"
        overlay = design_overlay(kind, gc0, tp)
        schedule = None
        if args.designer == "matcha":
            schedule = design_schedule(
                "matcha", gc0, tp, sample_seed=args.scenario_seed,
                objective=args.objective)
            tau0 = schedule.price(gc0, tp, rounds=150, seeds=(0,)).tau_ms
            print(f"dynamic: {args.underlay} N={n}, matcha schedule "
                  f"(budget sweep -> C_b={schedule.budget:g}, "
                  f"{schedule.num_matchings} matchings), "
                  f"predicted tau={tau0:.1f} ms")
        else:
            tau0 = overlay.cycle_time_ms
            print(f"dynamic: {args.underlay} N={n}, {kind} overlay, "
                  f"predicted tau={tau0:.1f} ms")
        horizon = tau0 * max(args.steps, 1)
        if args.scenario == "linkfail":
            scenario = link_failure_scenario(
                underlay, Tc, t_fail_ms=horizon / 3,
                overlay_edges=overlay.edges, horizon_ms=horizon)
        elif args.scenario == "silodegrade":
            scenario = silo_degrade_scenario(
                underlay, Tc, silo=underlay.load_centrality_center(),
                t_ms=horizon / 3, horizon_ms=horizon)
        elif args.scenario == "random":
            # churn enabled: membership is elastic — on SiloJoin/SiloLeave
            # the controller swaps the MembershipSlot and the loop below
            # rebuilds the mesh and migrates the silo-stacked state
            scenario = random_scenario(
                underlay, Tc, seed=args.scenario_seed, horizon_ms=horizon,
                p_churn=args.p_churn)
        elif args.scenario == "churn":
            scenario = churn_scenario(
                underlay, Tc, silo=underlay.num_silos // 2,
                t_leave_ms=horizon / 4, t_rejoin_ms=horizon / 2,
                horizon_ms=horizon)
        else:
            scenario = static_scenario(underlay, Tc, horizon_ms=horizon)
        timeline = DynamicTimeline(scenario, tp)
        if recorder is not None:
            timeline.attach_recorder(recorder)
        provider = lambda: active_subgraph(  # noqa: E731 — shared by both modes
            timeline.current_epoch().gc, timeline.current_epoch().active)
        mem_slot = MembershipSlot(range(n), n)
        if schedule is not None:
            timeline.set_schedule(schedule)
            sched_slot = ScheduleSlot(schedule, n)
            cfg_ctl = ControllerConfig(
                seed=args.scenario_seed, schedule_family="matcha",
                matcha_budgets=DEFAULT_MATCHA_BUDGETS,
                objective=args.objective)
            slot_kw = dict(schedule_slot=sched_slot)
            plan = None
        else:
            timeline.set_overlay(overlay.edges)
            slot = PlanSlot(plan_from_overlay(overlay, n))
            cfg_ctl = ControllerConfig(
                seed=args.scenario_seed, objective=args.objective)
            slot_kw = dict(plan_slot=slot)
            plan = slot.plan
        controller = OnlineTopologyController(
            gc0, tp, overlay, schedule=schedule, config=cfg_ctl,
            connectivity_provider=provider,
            membership_slot=mem_slot,
            membership_provider=timeline.current_active,
            recorder=recorder,
            silo_names=silo_names,
            **slot_kw,
        )
    else:
        # Without --dynamic there are no network measurements to design
        # from; the measurement-based kinds fall back to their homogeneous
        # mesh equivalents.
        if args.designer in ("sparse-rewire", "delta-rewire",
                             "hierarchical"):
            log.warn("designer-ignored",
                     f"--designer {args.designer} needs --dynamic "
                     "(network measurements)")
        plan = None
        if args.designer == "matcha" and n > 1:
            # Homogeneous MATCHA: matchings of the complete silo graph.
            from repro.core import MatchaSchedule, greedy_edge_coloring
            from repro.fed.gossip import ScheduleSlot

            pairs = [(i, j) for i in range(n) for j in range(i + 1, n)]
            schedule = MatchaSchedule(
                matchings=tuple(
                    tuple(m) for m in greedy_edge_coloring(pairs)),
                budget=args.matcha_budget,
                sample_seed=args.scenario_seed,
            )
            sched_slot = ScheduleSlot(schedule, n)
            print(f"matcha: homogeneous K_{n} base graph, "
                  f"{schedule.num_matchings} matchings, "
                  f"C_b={schedule.budget:g} (per-round sampled plans)")
        else:
            kind = {"delta_mbst": "mst", "ring_2opt": "ring"}.get(
                args.topology, args.topology)
            if kind != args.topology:
                log.warn("topology-fallback",
                         "measurement-based kind needs --dynamic; using "
                         "homogeneous plan",
                         requested=args.topology, used=kind)
            plan = plan_for_n_silos(kind, n) if n > 1 else None

    def silo_shardings(state, mesh):
        # silo-stacked leaves split their leading dim over the silo axis
        # (one silo per device); the shared step counter is replicated
        return jax.tree_util.tree_map(
            lambda x: NamedSharding(mesh, P(*(("data",) + (None,) * (x.ndim - 1)))
                                    if x.ndim else P()), state)

    def shard_state(state_host, mesh):
        return jax.device_put(state_host, silo_shardings(state_host, mesh))

    # Recompile accounting: TraceCounter wraps the *pre-jit* step body, so
    # its count moves exactly when jax re-traces (initial lowering or a
    # hot-swap re-lower) — never on a cached executable call.
    from repro.analysis.recompile import TraceCounter

    def make_counted_step(*a, **kw):
        counted = TraceCounter(make_train_step(*a, **kw), name="train_step")
        trace_counters.append(counted)
        return counted

    trace_counters: list = []
    step_fn = make_counted_step(cfg, fed, opt, plan, mesh,
                                consensus_arg=sched_mode)
    from repro.models.transformer import attention_impls

    impls = attention_impls(cfg, args.seq_len, jax.default_backend())
    for impl in ("pallas", "chunked"):
        obs_metrics.counter(f"attn.{impl}_layers").inc(impls.count(impl))
    print(f"attention: {impls.count('pallas')} pallas, "
          f"{impls.count('chunked')} chunked layers", flush=True)

    def make_state(key):
        return init_state(cfg, opt, key)

    key = jax.random.PRNGKey(0)
    # built where it lives: each silo's rows are initialised on its own
    # device, never gathered on the first one
    state = jax.jit(make_state, out_shardings=silo_shardings(
        jax.eval_shape(make_state, key), mesh))(key)
    # The data stream spans the full silo universe: under elastic
    # membership each silo label keeps its own (non-iid) distribution
    # across leaves/rejoins; the batcher stacks only the active labels.
    stream = SyntheticLMStream(cfg.vocab_size, args.seq_len, n_silos=max(n, 1))
    batcher = FederatedBatcher(stream, args.local_steps, args.batch_per_silo)
    jstep = jax.jit(step_fn, donate_argnums=0)
    built_version = slot.version if slot is not None else 0
    built_mem_version = mem_slot.version if mem_slot is not None else 0
    active = tuple(range(n))
    losses = []
    t0 = time.time()
    with contextlib.ExitStack() as mesh_stack:
        mesh_stack.enter_context(jax.set_mesh(mesh))
        for i in range(args.steps):
            if args.dynamic:
                # one train step == one communication round of simulated
                # WAN.  Simulated *first*, so the consensus mask below
                # (and, after the step, the controller) see the epoch the
                # round actually spans — a silo departing mid-round is
                # masked out of this very round's mix, not the next one's.
                duration = timeline.step()
            with span("train.input", step=i):
                raw = batcher.batch(i, silos=active if args.dynamic else None)
                b = {k: jnp.asarray(v) for k, v in raw.items()}
            if recorder is not None:
                obs_metrics.counter("train.h2d_bytes").inc(
                    sum(getattr(v, "nbytes", 0) for v in raw.values()))
            if sched_mode:
                # per-round sampled consensus: traced argument, same
                # compiled step for every sampled topology
                A = jnp.asarray(sched_slot.matrix_for_round(i))
                if args.dynamic:
                    # renormalize over the silos still active at the end
                    # of this round: a leaver's stale params must not be
                    # mixed in during the one-round lag before the
                    # membership rebuild below
                    ep_active = set(timeline.current_active())
                    flags = [1.0 if v in ep_active else 0.0 for v in active]
                    mask = jnp.asarray(flags, jnp.float32)
                    n_act = int(sum(flags))  # host-side: no device sync
                    if n_act < len(active):
                        print(f"step {i:4d} consensus masked to "
                              f"{n_act}/{len(active)} silos "
                              f"(mid-round churn)", flush=True)
                    with span("train.dispatch", step=i):
                        state, metrics = jstep(state, b, A, mask)
                else:
                    with span("train.dispatch", step=i):
                        state, metrics = jstep(state, b, A)
            else:
                with span("train.dispatch", step=i):
                    state, metrics = jstep(state, b)
            if report is not None:
                losses.append(metrics["loss"])
            if args.dynamic:
                redesign = controller.observe_round(duration)
                if redesign is not None:
                    timeline.set_schedule(redesign.schedule)
                    name = (redesign.overlay.name if redesign.overlay
                            else redesign.schedule.name)
                    rand = ("randomized schedule"
                            if redesign.schedule.is_randomized else "overlay")
                    print(f"step {i:4d} [t={timeline.now_ms/1e3:7.1f}s sim] "
                          f"controller re-design -> {rand} {name} "
                          f"tau {redesign.measured_ms:.1f} -> "
                          f"{redesign.predicted_tau_ms:.1f} ms "
                          f"({redesign.n_candidates} candidates in "
                          f"{redesign.elapsed_s*1e3:.0f} ms), bottleneck "
                          f"{redesign.bottleneck}", flush=True)
                if mem_slot is not None and mem_slot.version != built_mem_version:
                    # elastic membership: rebuild the mesh over the active
                    # silos and migrate the silo-stacked state (survivors
                    # keep their rows, leavers' shards are dropped,
                    # joiners enter at the survivors' consensus average)
                    new_active = mem_slot.active
                    # one host gather serves the migration, the leaver
                    # checkpoints, and the verification below
                    old_state = jax.device_get(state)
                    old_params = old_state["params"]
                    state_host, joined, left = migrate_silo_state(
                        old_state, active, new_active)
                    if args.churn_checkpoint and left:
                        from repro.checkpoint import save_silo_checkpoint

                        for v in left:
                            # full row: params AND optimizer slots (plus
                            # the shared step counter), so a later rejoin
                            # can recover exactly what the silo trained
                            row = slice_silo_row(old_state, active, v)
                            path = save_silo_checkpoint(
                                args.churn_checkpoint, v, row, step=i)
                            print(f"step {i:4d} leaver silo {v} "
                                  f"checkpoint -> {path}", flush=True)
                    n = len(new_active)
                    cfg = dataclasses.replace(cfg, n_silos=n)
                    mesh = make_silo_mesh(n)
                    mesh_stack.close()
                    mesh_stack.enter_context(jax.set_mesh(mesh))
                    state = shard_state(state_host, mesh)
                    jstep = jax.jit(make_counted_step(
                        cfg, fed, opt,
                        None if sched_mode else slot.plan, mesh,
                        consensus_arg=sched_mode), donate_argnums=0)
                    built_version = slot.version if slot is not None else 0
                    built_mem_version = mem_slot.version
                    msg = (f"step {i:4d} membership v{mem_slot.version}: "
                           f"{len(active)} -> {n} silos "
                           f"(left {list(left)}, joined {list(joined)}); "
                           f"mesh+state rebuilt")
                    if args.verify_migration:
                        # re-gather and check the migration invariants —
                        # a full-model host sweep, so opt-in (printed for
                        # the subprocess acceptance test to assert)
                        new_params = jax.device_get(state["params"])
                        oi = {v: k for k, v in enumerate(active)}
                        ni = {v: k for k, v in enumerate(new_active)}
                        survivors = [v for v in new_active if v in oi]
                        srows = [oi[v] for v in survivors]
                        # leaves are host already (device_get above):
                        # asarray is a view, not a transfer
                        olds = [np.asarray(o) for o in  # repro-lint: ignore[effect-purity]
                                jax.tree_util.tree_leaves(old_params)]
                        news = [np.asarray(w) for w in  # repro-lint: ignore[effect-purity]
                                jax.tree_util.tree_leaves(new_params)]
                        ok_surv = all(
                            np.array_equal(o[oi[v]], w[ni[v]])
                            for o, w in zip(olds, news) for v in survivors)
                        ok_join = all(
                            np.array_equal(
                                o[srows]
                                .mean(axis=0, dtype=np.float64)
                                .astype(o.dtype),
                                w[ni[v]])
                            for o, w in zip(olds, news) for v in joined)
                        msg += (f", survivors-bit-identical={ok_surv}, "
                                f"joiners-at-consensus={ok_join}")
                    print(msg, flush=True)
                    active = new_active
                if slot is not None and slot.version != built_version:
                    # hot-swap: re-lower the train step on the new plan
                    jstep = jax.jit(make_counted_step(cfg, fed, opt,
                                                      slot.plan, mesh),
                                    donate_argnums=0)
                    built_version = slot.version
                # sched_slot swaps need no re-lowering: the consensus
                # matrix is a traced input, matrix_for_round follows the
                # new schedule automatically
            if (recorder is not None and args.metrics_interval
                    and i % args.metrics_interval == 0):
                recorder.emit(
                    "round",
                    step=i,
                    duration_ms=duration if args.dynamic else None,
                    predicted_window_ms=(
                        controller.expected_window_ms
                        if controller is not None else None),
                    measured_window_ms=(
                        controller.last_measured_ms
                        if controller is not None else None),
                    drift=(controller.last_drift
                           if controller is not None else None),
                )
                if args.dynamic:
                    obs_metrics.histogram("train.round_ms").observe(duration)
                obs_metrics.gauge("train.recompiles").set(
                    sum(c.count for c in trace_counters))
            if i % max(1, args.steps // 10) == 0 or i == args.steps - 1:
                # intentional sync: ~10 progress lines per run
                with span("train.readback", step=i):
                    loss = float(metrics["loss"])  # repro-lint: ignore[effect-purity]
                print(f"step {i:4d} loss {loss:.4f} ({time.time()-t0:.1f}s)", flush=True)
    if args.dynamic and controller is not None:
        final = controller.schedule
        desc = (f"randomized schedule {final.name} (C_b="
                f"{getattr(final, 'budget', 0):g})"
                if final.is_randomized
                else f"overlay {controller.overlay.name}")
        print(f"dynamic summary: {timeline.rounds_done} rounds in "
              f"{timeline.now_ms/1e3:.1f}s simulated, "
              f"{len(controller.redesigns)} re-design(s), "
              f"{mem_slot.version} membership swap(s) "
              f"({len(active)}/{underlay.num_silos} silos active), "
              f"final {desc} (tau {controller.predicted_tau_ms:.1f} ms)")
    if args.checkpoint:
        from repro.checkpoint import save_checkpoint

        save_checkpoint(args.checkpoint, jax.device_get(state["params"]),
                        step=args.steps)
        print(f"checkpoint -> {args.checkpoint}")
    if recorder is not None:
        obs_metrics.gauge("train.recompiles").set(
            sum(c.count for c in trace_counters))
        recorder.close(
            steps=args.steps,
            recompiles=sum(c.count for c in trace_counters),
            wall_s=time.time() - t0,
        )
        log.info("trace-written", path=args.trace_out,
                 spans=len(span_summary()))
    if report is not None:
        report.update(losses=[float(x) for x in jax.device_get(losses)],
                      state=state, mesh=mesh,
                      plan=slot.plan if slot is not None else plan)
    return 0


if __name__ == "__main__":
    sys.exit(main())
