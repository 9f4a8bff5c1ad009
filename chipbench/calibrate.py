"""Readings that the limits of ``limits/<cell>.json`` are set from.

    python3 chipbench/calibrate.py --workload internlm2-local \
        --seeds 101 102 ... --control-seeds 101 102 103 --faults half_batch

For each seed, in one process on the cell's chips: the reference's first
rounds, the program's (sound runs: the lower reading), the program with
float32 parameters swapped for bfloat16 (the control) and each planted
fault (the upper reading), each reduced to the comparison's numbers.
Writes one JSON line per reading to standard output.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", nargs="*", default=[])
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from chipbench import compare, faults, harness
    from chipbench import reference as references
    from chipbench.reference import rounds
    from repro.launch.compile_cache import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    enable_compile_cache()
    cell = harness.load_cell(ROOT, args.workload)
    devices = jax.devices()[: cell.chips]
    model = references.model(cell.config["reference"])

    def program(seed, dtype=jnp.float32):
        sut = harness.SystemUnderTest(cell, seed, devices, param_dtype=dtype)
        readings, batches = harness.check_rounds(sut)
        del sut
        # unload the step: a loaded TPU program keeps its temporaries
        # reserved, and the next variant's would not fit beside them
        jax.clear_caches()
        return readings, batches

    def emit(seed, kind, readings, ref):
        found = compare.gaps(readings, ref)
        row = {"cell": cell.name, "seed": seed, "kind": kind,
               "losses": readings["losses"], "ref_losses": ref["losses"],
               **{k: found[k][0] for k in compare.NUMBERS},
               **{k + "_at": found[k][1] for k in compare.NUMBERS}}
        print(json.dumps(row), flush=True)

    for seed in args.seeds:
        mine, batches = program(seed)
        ref = rounds.run(model, cell.config, cell.traffic, seed, batches, devices)
        emit(seed, "sound", mine, ref)
        if seed in args.control_seeds:
            emit(seed, "control_bf16", program(seed, jnp.bfloat16)[0], ref)
            for name in args.faults:
                with faults.planted(name):
                    emit(seed, name, program(seed)[0], ref)
    return 0


if __name__ == "__main__":
    sys.exit(main())
