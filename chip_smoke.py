#!/usr/bin/env python
"""Bring-up check on a TPU: the overlay designer, the Pallas kernels and
the full-width DPASGD trainer, each through the entry point a user calls.

    python chip_smoke.py               # one chip: designer, kernels, trainer
    python chip_smoke.py --four-chip   # four silos, one per chip: gossip

One process drives every chip it uses.  Each phase prints what it
checked; any failed check raises, so the script exits non-zero and
prints no result.  The last line of a passing run is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  With no TPU
the script refuses to run: it never falls back to the CPU.  These are
bring-up checks, not benchmark numbers.

Every phase is a function of its sizes, so the CPU tests call them at
tiny sizes in interpret mode (``tests/test_chip_smoke.py``).
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro.core as C  # noqa: E402
from repro.core.maxplus_sparse import (  # noqa: E402
    batched_cycle_time_sparse_jax,
    batched_overlay_delay_edges,
)
from repro.fed.gossip import gossip_einsum, gossip_shard_map  # noqa: E402
from repro.fed.topology_runtime import plan_for_n_silos  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.flash_attention import flash_attention_pallas  # noqa: E402
from repro.kernels.gossip_mix import gossip_mix_pallas  # noqa: E402
from repro.kernels.mlstm_scan import mlstm_scan_pallas  # noqa: E402
from repro.kernels.segment_max import edge_segment_max_pallas  # noqa: E402
from repro.launch import train  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

# Device tau of the designed overlay against its host f64 repricing: the
# f32 Karp adds up to N arc weights and divides once, each op rounding at
# 2^-24 relative, so N <= 87 stays below 1e-5; 1e-4 leaves margin.
TAU_RTOL = 1e-4
# Kernel outputs against their references (tests/test_kernels.py): bf16
# outputs round at 2^-8 relative; f32 ones are summed in another order.
KERNEL_TOL = {jnp.bfloat16: 2e-2, jnp.float32: 2e-5}
# One gossip round, ppermute schedule against the dense einsum: each
# output sums at most three f32 terms in a different order.
GOSSIP_RTOL, GOSSIP_ATOL = 1e-5, 1e-6
# The first loss of a freshly initialised LM sits near ln(vocab).
FIRST_LOSS_ATOL = 1.0

# internlm2-1.8b at published widths, cut to 4 of its 24 layers (every
# layer is "attn", so any depth is whole periods).  At the trainer's
# default lr (0.05) the loss of a fresh full-width model moves less in a
# few steps than it varies from batch to batch; 0.5 makes the fall clear.
TRAIN_ARGS = ["--arch", "internlm2-1.8b", "--layers", "4", "--seq-len", "2048",
              "--batch-per-silo", "4", "--local-steps", "2", "--steps", "8",
              "--lr", "0.5"]
INTERNLM2_VOCAB = 92544


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


class CompileClock:
    """Sums JAX's backend-compile durations (a persistent-cache hit
    shows up as a short one) and counts persistent-cache hits."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.compiles, self.cache_hits

    def since(self, snap) -> str:
        s, c, h = (a - b for a, b in zip(self.snapshot(), snap))
        return f"compile {s:.2f} s in {c} compiles ({h} persistent-cache hits)"


def _random_edge_batch(rng, B: int, n: int, degree: int):
    """B strongly connected graphs on n nodes with in-degree <= degree:
    a ring, degree - 2 random chords per node and a self-loop."""
    idx = np.arange(n, dtype=np.int32)
    src, dst = [], []
    for _ in range(B):
        offs = [1] + list(rng.choice(np.arange(2, n - 1), size=degree - 2,
                                     replace=False)) + [0]
        src.append(np.concatenate([idx] * len(offs)))
        dst.append(np.concatenate([(idx + o) % n for o in offs]))
    src, dst = np.stack(src), np.stack(dst)
    w = rng.uniform(0.5, 20.0, src.shape).astype(np.float32)
    return src, dst, w


def phase_designer(networks=("gaia", "ebone"), workload="inaturalist", *,
                   n: int = 1024, batch: int = 16, degree: int = 8,
                   seed: int = 0) -> None:
    """``design_overlay("sparse_rewire")`` on paper networks, then the
    batched Karp through the Pallas and XLA segment max at scale."""
    M, Tc = C.WORKLOADS[workload]
    tp = C.TrainingParams(model_size_mbits=M, local_steps=1)
    for name in networks:
        gc = C.make_underlay(name).connectivity_graph(comp_time_ms=Tc)
        ring = C.design_overlay("ring", gc, tp)
        t = time.perf_counter()
        ov = C.design_overlay("sparse_rewire", gc, tp)
        took = time.perf_counter() - t
        # the winner re-scored on the device in f32, kernel chosen as the
        # climb chooses it (Pallas on a TPU); one read-back per network
        eb = batched_overlay_delay_edges(gc, tp, ov.edges,
                                         np.ones((1, len(ov.edges)), bool))
        tau_dev = float(jax.device_get(batched_cycle_time_sparse_jax(  # repro-lint: ignore[effect-purity]
            eb.src, eb.dst, eb.w.astype(np.float32), gc.num_silos))[0])
        rel = abs(tau_dev - ov.cycle_time_ms) / ov.cycle_time_ms
        print(f"designer {name}: N={gc.num_silos} {workload} ring tau "
              f"{ring.cycle_time_ms:.4f} ms, sparse_rewire tau "
              f"{ov.cycle_time_ms:.4f} ms (host f64), device f32 tau "
              f"{tau_dev:.4f} ms (rel err {rel:.2e}), design {took:.2f} s",
              flush=True)
        check(ov.cycle_time_ms <= ring.cycle_time_ms * (1 + 1e-12),
              f"{name}: sparse_rewire tau above the ring's")
        check(rel <= TAU_RTOL, f"{name}: device tau off host f64 by {rel:.2e}")

    src, dst, w = _random_edge_batch(np.random.default_rng(seed), batch, n,
                                     degree)
    taus = {}
    for kernel in ("pallas", "xla"):
        fn = jax.jit(lambda s, d, x, kernel=kernel: batched_cycle_time_sparse_jax(
            s, d, x, n, kernel=kernel))
        t = time.perf_counter()
        # read back inside the loop: the time printed includes the run
        taus[kernel] = np.asarray(jax.device_get(fn(src, dst, w)))  # repro-lint: ignore[effect-purity]
        print(f"karp N={n} B={batch} E={src.shape[1]} kernel={kernel}: "
              f"{time.perf_counter() - t:.2f} s with compile", flush=True)
    same = np.array_equal(taus["pallas"], taus["xla"])
    print(f"karp N={n}: pallas == xla bitwise: {same}", flush=True)
    check(same and bool(np.isfinite(taus["xla"]).all()),
          "Karp through the Pallas segment max differs from XLA's")


def _close(name, got, want, tol) -> None:
    got = np.asarray(jax.device_get(got), np.float32)
    want = np.asarray(jax.device_get(want), np.float32)
    err = np.abs(got - want)
    used = float(np.max(err / (tol + tol * np.abs(want))))
    ok = bool(np.isfinite(got).all()) and used <= 1.0
    print(f"kernel {name}: shape {got.shape}, max |ref| "
          f"{float(np.max(np.abs(want))):.3e}, max abs err "
          f"{float(np.max(err)):.3e}, {used:.3f} of the bound "
          f"atol=rtol={tol:g}: {'ok' if ok else 'FAIL'}", flush=True)
    check(ok, f"{name} off its reference")


def phase_kernels(*, attn=(1, 2048, 8, 2, 128), mlstm=(1, 2048, 4, 512),
                  gossip_n: int = 1 << 20, seg=(16, 8192, 1024),
                  interpret: bool = False, seed: int = 0) -> None:
    """Each Pallas kernel once, compiled, against its reference.

    Shapes: internlm2-1.8b heads for attention ([B, S, K, G, hd]),
    xlstm-350m mLSTM heads ([B, S, H, hd], hd = 2 * 1024 / 4), a ring
    plan's K for the gossip mix, and the designer's segment max at
    N=1024."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    normal = lambda k, s, dt: jax.random.normal(k, s).astype(dt)  # noqa: E731
    hi = jax.default_matmul_precision("highest")

    B, S, K, G, hd = attn
    q, k, v = (normal(ks[0], attn, jnp.bfloat16),
               normal(ks[1], (B, S, K, hd), jnp.bfloat16),
               normal(ks[2], (B, S, K, hd), jnp.bfloat16))
    got = jax.jit(lambda *a: flash_attention_pallas(*a, interpret=interpret))(
        q, k, v)
    with hi:
        want = jax.jit(ref.attention_ref)(q, k, v)
    _close("flash_attention", got, want, KERNEL_TOL[jnp.bfloat16])

    B, S, H, hd = mlstm
    q, k, v = (normal(kk, mlstm, jnp.float32) * 0.5 for kk in ks[3:6])
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    li = jax.nn.log_sigmoid(jax.random.normal(ks[6], (B, S, H)))
    lf = jax.nn.log_sigmoid(jax.random.normal(ks[7], (B, S, H)) + 2.0)
    got = jax.jit(lambda *a: mlstm_scan_pallas(*a, interpret=interpret))(
        q, k, v, li, lf)
    with hi:
        want = jax.jit(ref.mlstm_scan_ref)(q, k, v, li, lf)
    _close("mlstm_scan", got, want, KERNEL_TOL[jnp.bfloat16])

    plan = plan_for_n_silos("ring", 4)
    coeffs = jnp.asarray([c for c, _ in plan.terms], jnp.float32)
    blocks = jax.random.normal(ks[0], (len(plan.terms), gossip_n))
    got = jax.jit(lambda x, c: gossip_mix_pallas(x, c, interpret=interpret))(
        blocks, coeffs)
    with hi:
        want = jax.jit(ref.gossip_mix_ref)(blocks, coeffs)
    _close(f"gossip_mix K={len(plan.terms)}", got, want, KERNEL_TOL[jnp.float32])

    Bs, E, Ns = seg
    rng = np.random.default_rng(seed)
    vals = rng.standard_normal((Bs, E)).astype(np.float32)
    vals[rng.random((Bs, E)) < 0.1] = -np.inf
    ids = rng.integers(-1, Ns + 1, size=(Bs, E)).astype(np.int32)
    got = jax.jit(lambda x, i: edge_segment_max_pallas(
        x, i, Ns, interpret=interpret))(vals, ids)
    want = jax.jit(jax.vmap(lambda x, i: jax.ops.segment_max(
        x, i, num_segments=Ns)))(vals, ids)
    same = np.array_equal(np.asarray(jax.device_get(got)),
                          np.asarray(jax.device_get(want)))
    print(f"kernel segment_max B={Bs} E={E} S={Ns}: bitwise equal to "
          f"jax.ops.segment_max: {same}", flush=True)
    check(same, "segment max differs from jax.ops.segment_max")


def _peak_hbm() -> str:
    """Peak HBM of each device so far: buffers in use, and the space
    reserved beside them (on a TPU that holds the programs' temps)."""
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    if any("peak_bytes_in_use" not in s for s in stats):
        return "peak HBM not reported by this backend"
    return "peak HBM in use / reserved " + ", ".join(
        f"{s['peak_bytes_in_use'] / 2**30:.2f} / "
        f"{s.get('peak_bytes_reserved', 0) / 2**30:.2f} GiB" for s in stats)


def phase_trainer(argv=TRAIN_ARGS + ["--silos", "1"], *,
                  vocab_size: int = INTERNLM2_VOCAB) -> dict:
    """``train.main`` in this process: finite losses, the first near
    ln(vocab), the last below the first."""
    report: dict = {}
    t = time.perf_counter()
    rc = train.main(list(argv), report=report)
    took = time.perf_counter() - t
    losses = report["losses"]
    print(f"trainer: rc={rc}, {len(losses)} steps in {took:.2f} s, losses "
          + " ".join(f"{x:.4f}" for x in losses)
          + f" (ln vocab = {math.log(vocab_size):.4f})", flush=True)
    check(rc == 0, f"train.main returned {rc}")
    check(bool(losses) and all(math.isfinite(x) for x in losses),
          "non-finite loss")
    check(abs(losses[0] - math.log(vocab_size)) <= FIRST_LOSS_ATOL,
          f"first loss {losses[0]:.4f} not near ln(vocab)")
    check(losses[-1] < losses[0], "loss did not fall")
    return report


def phase_four_chip(argv=TRAIN_ARGS + ["--silos", "4", "--topology", "ring",
                                       "--gossip-impl", "ppermute"], *,
                    vocab_size: int = INTERNLM2_VOCAB) -> None:
    """Four silos, one per device: the trainer on the ring ppermute
    schedule, each silo's state on its own device, and one gossip round
    through ``gossip_shard_map`` against ``gossip_einsum`` of the same
    consensus matrix on that sharded state."""
    report = phase_trainer(argv, vocab_size=vocab_size)
    mesh, plan = report["mesh"], report["plan"]
    n = plan.n_silos
    devices = set(mesh.devices.flat)
    placed = True
    for leaf in jax.tree_util.tree_leaves(report["state"]["params"]):
        rows = {s.device: s.index[0] for s in leaf.addressable_shards}
        placed &= (set(rows) == devices and len(devices) == n and sorted(
            (r.start, r.stop) for r in rows.values())
            == [(i, i + 1) for i in range(n)])
    print(f"four-chip: each silo's state on its own device "
          f"({len(devices)} devices): {placed}", flush=True)
    check(placed, "silo rows not one per device")

    A = jnp.asarray(plan.matrix, jnp.float32)

    @jax.jit
    def one_round(w):
        mixed = gossip_shard_map(w, plan, mesh, "data")
        with jax.default_matmul_precision("highest"):
            want = gossip_einsum(w, A)
        return (jnp.max(jnp.abs(mixed - want)),
                jnp.max(jnp.abs(want)), jnp.all(jnp.isfinite(mixed)))

    # leaf by leaf: the dense reference gathers every silo's copy of a
    # leaf onto each device, which the whole model would not fit
    worst, ok = 0.0, True
    with jax.set_mesh(mesh):
        for leaf in jax.tree_util.tree_leaves(report["state"]["params"]):
            # three scalars per leaf, read back before the next leaf's
            # gathered copy is allocated
            err, scale, finite = (float(x) for x in  # repro-lint: ignore[effect-purity]
                                  jax.device_get(one_round(leaf)))
            worst = max(worst, err)
            ok &= bool(finite) and err <= GOSSIP_ATOL + GOSSIP_RTOL * scale
    print(f"four-chip gossip: {plan.num_transfers} ppermute transfer(s), "
          f"shard_map vs einsum max abs err {worst:.3e} (rtol={GOSSIP_RTOL:g}, "
          f"atol={GOSSIP_ATOL:g}): {'ok' if ok else 'FAIL'}", flush=True)
    check(ok, "ppermute gossip differs from the einsum reference")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the four-silo gossip path (needs 4 chips)")
    args = ap.parse_args(argv)
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{d0.platform!r} ({d0.device_kind}); it does not run on the "
              f"CPU", file=sys.stderr)
        return 2
    need = 4 if args.four_chip else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    print(f"chip_smoke: {d0.platform} {d0.device_kind} x{len(devices)}, "
          f"jax {jax.__version__}, compile cache {cache_dir}", flush=True)
    phases = ([("four-chip", phase_four_chip)] if args.four_chip else
              [("designer", phase_designer), ("kernels", phase_kernels),
               ("trainer", phase_trainer)])
    for name, phase in phases:
        snap = clock.snapshot()
        t = time.perf_counter()
        phase()
        print(f"phase {name}: ok in {time.perf_counter() - t:.2f} s, "
              f"{clock.since(snap)}; {_peak_hbm()}", flush=True)
    print(f"chip_smoke: all phases ok, {clock.since((0.0, 0, 0))}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
