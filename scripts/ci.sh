#!/usr/bin/env bash
# Tier-1 verification (see ROADMAP.md): the full test suite must pass.
# Usage: scripts/ci.sh [--fast] [extra pytest args]
#
#   --fast   deselect tests marked `slow` (Monte-Carlo schedule sweeps,
#            subprocess train acceptance runs) — the minutes-scale lane
#            for inner-loop development.  The DEFAULT (no flag) runs the
#            full suite including slow tests: that is the tier-1 gate.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
# CPU lane: Pallas kernels interpret, the trainer uses virtual devices
# (tests/test_tpu_compile.py covers the TPU compile).
export JAX_PLATFORMS=cpu
FAST_ARGS=()
FAST=0
if [[ "${1:-}" == "--fast" ]]; then
  FAST_ARGS=(-m "not slow")
  FAST=1
  shift
fi
# Lint gate: project-invariant static checks (trace safety, RNG
# discipline, NEG_INF sentinel, dtype discipline, engine contracts,
# protocol typestate) against the committed baseline.  The fast lane
# checks only files changed vs the git merge base (the whole tree is
# still parsed for cross-file facts); the full lane lints everything
# and must finish inside its 30 s wall-clock budget — if it doesn't,
# the lint layer has regressed and the budget assert fails the run.
echo "== repro-lint =="
LINT_START=$SECONDS
if [[ "$FAST" == 1 ]]; then
  python scripts/lint_repro.py --changed
else
  python scripts/lint_repro.py
  LINT_TOOK=$((SECONDS - LINT_START))
  if (( LINT_TOOK >= 30 )); then
    echo "repro-lint: full lint took ${LINT_TOOK}s (budget: 30s)" >&2
    exit 1
  fi
fi
# Docs gate first: the README quickstart must run as-is and docs/ must
# not reference dead file paths (tests/test_readme_quickstart.py).
echo "== docs gate =="
python -m pytest -x -q tests/test_readme_quickstart.py
echo "== tier-1 =="
# --ignore: the docs gate already ran that file; don't run it twice
# ${arr[@]+...} guard: empty-array expansion under `set -u` aborts on
# bash < 4.4 (e.g. macOS system bash)
python -m pytest -x -q --ignore=tests/test_readme_quickstart.py \
  ${FAST_ARGS[@]+"${FAST_ARGS[@]}"} "$@"
echo "== pallas kernel smoke =="
# The Pallas segment-max kernel must stay bit-identical to
# jax.ops.segment_max (interpret mode on CPU; the compiled-TPU path is
# the same kernel body).  A one-liner so kernel drift fails loudly even
# when the kernel test file is deselected.
python - <<'PY'
import numpy as np, jax, jax.numpy as jnp
from repro.kernels.segment_max import edge_segment_max_pallas
rng = np.random.default_rng(0)
vals = rng.standard_normal((4, 96)).astype(np.float32)
vals[rng.random((4, 96)) < 0.2] = -np.inf
ids = rng.integers(-1, 33, size=(4, 96)).astype(np.int32)
got = edge_segment_max_pallas(vals, ids, 32, interpret=True)
ref = jax.vmap(lambda v, i: jax.ops.segment_max(v, i, num_segments=32))(
    jnp.asarray(vals), jnp.asarray(ids))
np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
print("pallas segment-max == jax.ops.segment_max (bitwise)")
PY
echo "== bench smoke =="
# Seconds-scale pass over the smoke-capable benchmarks (tiny grids, perf
# asserts off, correctness asserts on) so bench code cannot silently rot.
python -m benchmarks.run --smoke
if [[ "$FAST" == 0 ]]; then
  # Obs trace smoke (full lane only — a subprocess train run is minutes):
  # the closed-loop linkfail scenario must produce a schema-valid flight
  # recording that obs_report can both validate and render.  This is the
  # end-to-end contract for the observability layer: recorder wiring in
  # train.py, controller decision records, and the report toolchain.
  # --objective time_to_eps makes every re-design price (tau, rho)
  # co-design, so the trace also carries the mixing-rate audit fields.
  echo "== obs trace smoke =="
  TRACE=$(mktemp /tmp/obs_trace.XXXXXX.jsonl)
  trap 'rm -f "$TRACE"' EXIT
  python -m repro.launch.train --arch internlm2-1.8b --reduced --dynamic \
    --underlay gaia --scenario linkfail --steps 60 \
    --objective time_to_eps \
    --trace-out "$TRACE" --metrics-interval 5 >/dev/null
  python scripts/obs_report.py --check "$TRACE"
  # Render the full report to /dev/null: a crash here means the trace
  # has records the report code can't handle.  (No `| head`: pipefail
  # turns the reader's SIGPIPE into a spurious CI failure.)
  python scripts/obs_report.py "$TRACE" >/dev/null
fi
