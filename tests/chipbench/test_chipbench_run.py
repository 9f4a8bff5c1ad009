"""``chipbench/run.py`` refuses what it must not measure, and a run's
last line carries the contract's keys."""

import json
import os
import shutil
import subprocess
import sys
import time
import types

import jax

from chipbench_tiny import DENSE, REPO, cell, traffic

from chipbench import harness, peaks

CONTRACT = {"correct", "attempted", "failed", "metrics", "device"}


def _run(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "internlm2-local",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_the_cpu_platform():
    r = _run(REPO)
    assert r.returncode != 0
    assert "refusing to run on platform 'cpu'" in r.stderr
    assert r.stdout.strip() == ""


def test_refuses_an_unknown_device_kind(monkeypatch, capsys):
    from chipbench import run

    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v0 imaginary")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    assert run.main(["--workload", "internlm2-local", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) != 0
    out = capsys.readouterr()
    assert out.out.strip() == ""
    assert "no published peaks" in out.err


def test_fails_with_only_the_benchmark_files(tmp_path):
    """A directory with BENCHMARK.json and the benchmark's paths alone
    has no program to measure."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in json.loads((REPO / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_last_line_has_the_contract_keys():
    c = cell(DENSE, traffic(), "internlm2-local")
    result = harness.run_cell(c, 2 ** 31 + 3, 0.5, False, jax.devices()[:1],
                              time.time(), peaks.PEAKS["TPU v5 lite"], log=lambda m: None)
    line = json.loads(json.dumps(result))
    assert set(line) == CONTRACT | {"checks"}
    assert list(line)[-1] == "checks"
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {m["name"] for m in c.end_to_end}
    assert all(set(m) == {"value", "unit"} for m in line["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    assert all(set(v) == {"value", "limit"} for v in line["checks"].values())


def test_traced_run_reads_the_programs_scopes_and_spans(monkeypatch):
    """A traced run hands the per-layer readers the compiled step's op
    names and a trace with the program's ``input.batch`` spans.  The CPU
    profile has no device plane, so each dispatch is given one device
    operation per instruction of the step, in its host span's place."""
    from chipbench import scopes, trace
    from repro.obs import spans

    names = {}
    parse = scopes.op_names
    monkeypatch.setattr(scopes, "op_names", lambda text: names.update(parse(text)) or names)

    def load(directory, device_ids=None):
        spans = trace.host_events(trace.profile(directory))
        sent = [s for s, _, n in spans if n == "dispatch"]
        ops = [(s + k, s + k + 1, n) for s in sent for k, n in enumerate(sorted(names))]
        return trace.Trace(ops={0: ops}, spans=spans,
                           window=next((s, e) for s, e, n in spans if n == "window"))

    monkeypatch.setattr(trace, "load", load)
    c = cell(DENSE, traffic(), "internlm2-local")
    result = harness.run_cell(c, 2 ** 31 + 11, 0.5, True, jax.devices()[:1],
                              time.time(), peaks.PEAKS["TPU v5 lite"], log=lambda m: None)
    assert result["correct"] is True
    metrics = result["metrics"]
    gossip = {"gossip_exposed_ms", "gossip_bytes", "gossip_ms"}
    assert set(metrics) == {m["name"] for m in c.per_layer} - gossip
    assert {"forward_ms", "backward_ms", "optimizer_ms", "host_batch_ms"} <= set(metrics)
    assert 0 < metrics["host_batch_ms"]["value"] < metrics["host_input_ms"]["value"]
    assert result["device"]["busy_s"] > 0 and result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert not spans.enabled()
