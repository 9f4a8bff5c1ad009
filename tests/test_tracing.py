"""The program's own tracing: ``repro.obs`` stays stdlib-only until
enabled, the training loop's spans name the round's host work, and the
compiled step's collective bytes count an async pair once."""

import json
import os
import subprocess
import sys
from pathlib import Path

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _python(code, **env):
    env = {**os.environ, "PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu", **env}
    r = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout.strip()


def test_obs_imports_jax_profiler_only_when_enabled():
    out = _python(
        "import sys, repro.obs as obs\n"
        "jax = lambda: any(m == 'jax' or m.startswith('jax.') for m in sys.modules)\n"
        "print(jax())\n"
        "with obs.span('x'): pass\n"
        "print(jax())\n"
        "obs.enable()\n"
        "print(jax(), 'jax.profiler' in sys.modules)\n")
    assert out.splitlines() == ["False", "False", "True True"]


def test_train_spans_name_the_rounds_host_work(tmp_path):
    trace_out = tmp_path / "trace.jsonl"
    env = {**os.environ, "PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "internlm2-1.8b",
         "--reduced", "--silos", "1", "--steps", "3", "--seq-len", "16",
         "--batch-per-silo", "2", "--trace-out", str(trace_out)],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    records = [json.loads(line) for line in trace_out.read_text().splitlines()]
    spans = records[-1]["spans"]
    assert spans["train.input"]["count"] == spans["train.dispatch"]["count"] == 3
    assert spans["input.batch"]["count"] == 3
    assert spans["train.readback"]["count"] >= 1
    assert "train.step" not in spans


def test_collective_bytes_count_an_async_pair_once():
    from repro.launch.hlo_analysis import collective_bytes

    hlo = """
  %cp-start = (f32[4,1024]{1,0}, f32[4,1024]{1,0}, u32[], u32[]) collective-permute-start(%p), source_target_pairs={{0,1}}
  %cp-done = f32[4,1024]{1,0} collective-permute-done(%cp-start)
  %ar = f32[] all-reduce(%x), replica_groups={}
  %add = f32[4,1024]{1,0} add(%a, %b)
"""
    got = collective_bytes(hlo)
    assert got["collective-permute"] == 4 * 1024 * 4
    assert got["all-reduce"] == 4
    assert got["collective-count"] == 2
