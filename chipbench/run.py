"""Chip benchmark of the DPASGD round: one run of one cell.

    python3 chipbench/run.py --workload internlm2-local --seed 7 \
        --seconds 10 --trace 0

Run from the root of a checkout.  One process holds the cell's chips.
It builds the cell's state from ``--seed``, checks the first rounds
against the plain reference, times ``--seconds`` of rounds, and prints
the result as one JSON line, last on standard output: the end-to-end
metrics with ``--trace 0``, the per-layer metrics (from a profiler trace
of the window) with ``--trace 1``.  On any platform but a TPU whose
``device_kind`` is in ``chipbench/peaks.py`` it exits non-zero and
prints no result.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness, peaks
    from repro.launch.compile_cache import enable_compile_cache

    cell = harness.load_cell(ROOT, args.workload)
    import jax

    devices = jax.devices()
    kind = devices[0].device_kind
    if devices[0].platform != "tpu":
        log(f"refusing to run on platform {devices[0].platform!r}: the benchmark "
            "measures a TPU and never falls back")
        return 2
    try:
        peak = peaks.peak_for(kind)
    except KeyError as e:
        log(str(e))
        return 2
    if len(devices) < cell.chips:
        log(f"{cell.name} needs {cell.chips} chips, found {len(devices)}")
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {devices[0].platform} {kind} x{len(devices)}")
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                              devices[:cell.chips], T_START, peak, log=log)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
