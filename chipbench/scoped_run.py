"""A cell's round split by the program's own scopes and spans.

    python3 chipbench/scoped_run.py --workload internlm2-local --seed 7 --seconds 10

Run from the root of a checkout, on the machine that holds the cell's
chips.  It builds the cell from ``--seed`` as ``run.py`` does, drives the
checked rounds (without the reference), and maps each operation of the
compiled step to its scope (``scopes.of_hlo``).  Then it times a window
of ``--seconds`` with the profiler off, and one with the profiler on and
``repro.obs`` enabled, so that the program's spans land in the trace.
The last line on standard output is one JSON object: tokens/s of both
windows, the cell's per-layer metrics and the five that read the
program's scopes and spans, device time per round by scope against busy
time, the dispatch lead, and the ops with most time outside the named
scopes, each with its ``op_name``.  It refuses any platform but a TPU, as ``run.py`` does.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

PROGRAM_METRICS = ("forward_ms", "backward_ms", "optimizer_ms", "gossip_ms", "host_batch_ms")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax

    import repro.obs as obs
    from chipbench import flops, harness, hlo_bytes, peaks, scopes
    from repro.launch.compile_cache import enable_compile_cache

    cell = harness.load_cell(ROOT, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"refusing to run on platform {devices[0].platform!r}")
        return 2
    peak = peaks.peak_for(devices[0].device_kind)
    devices = devices[:cell.chips]
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"compile cache: {enable_compile_cache()}")

    sut = harness.SystemUnderTest(cell, args.seed, devices)
    _, batches = harness.check_rounds(sut)
    text = sut.step.lower(sut.state, harness.device_batch(batches[0])).compile().as_text()
    names = scopes.op_names(text)
    op_scopes = {n: scopes.scope_of(op) for n, op in names.items()}
    collectives = hlo_bytes.collective_bytes(text)
    log(f"set-up: {time.time() - T_START:.3f} s; {len(op_scopes)} instructions")

    tokens = harness.tokens_per_round(cell)
    first = harness.CHECK_ROUNDS
    n_plain, t_plain, *_ = harness.timed_window(sut, first, args.seconds, False)
    tdir = tempfile.mkdtemp(prefix="chipbench-scoped-")
    obs.enable(capture=False)
    jax.profiler.start_trace(tdir, profiler_options=harness._profile_options())
    try:
        with jax.profiler.TraceAnnotation("window"):
            n_traced, t_traced, *_ = harness.timed_window(
                sut, first + n_plain, args.seconds, True)
    finally:
        jax.profiler.stop_trace()
        obs.disable()
    tr = scopes.load(tdir, [d.id for d in devices])
    shutil.rmtree(tdir, ignore_errors=True)

    facts = scopes.ScopedFacts(
        trace=tr, rounds=n_traced, chips=len(devices), peak=peak,
        flops_per_round=tokens * flops.train_flops_per_token(cell.config,
                                                             cell.traffic["seq_len"]),
        collective_bytes=collectives, op_scopes=op_scopes)
    metrics = {}
    for name in [m["name"] for m in cell.per_layer] + list(PROGRAM_METRICS):
        value = importlib.import_module(f"chipbench.metrics.{name}").read(facts)
        if value is not None:
            metrics[name] = value
    by_scope = scopes.device_ms(tr, op_scopes, n_traced)
    busy_ms = tr.busy_s() / n_traced * 1e3
    n_ops = sum(len(ops) for ops in tr.ops.values())
    other = [[n, t / n_traced * 1e3, names.get(n)] for n, t in tr.top_ops(n_ops)
             if op_scopes.get(n, "other") == "other"][:10]
    log(f"device ms per round by scope: {by_scope}; sum {sum(by_scope.values()):.3f}, "
        f"busy {busy_ms:.3f}")
    log(f"dispatch lead: {tr.dispatch_lead_ms()} ms; step executions per device "
        f"{ {d: len(m) for d, m in tr.modules.items()} }, dispatches {len(tr.host_spans('dispatch'))}")
    print(json.dumps({
        "workload": cell.name, "seed": args.seed,
        "device": {"kind": devices[0].device_kind, "count": len(devices)},
        "tokens_per_s_untraced": n_plain * tokens / t_plain,
        "tokens_per_s_traced": n_traced * tokens / t_traced,
        "rounds": [n_plain, n_traced], "metrics": metrics,
        "device_ms_by_scope": by_scope, "busy_ms_per_round": busy_ms,
        "dispatch_lead_ms": tr.dispatch_lead_ms(), "other_top_ms": other,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
