"""The benchmark finds its files by name, and its yardstick agrees with
the program's own arithmetic where it was copied from."""

import importlib
import json
import math
import re

import pytest

from chipbench_tiny import REPO

from chipbench import compare, flops, harness, hlo_bytes, peaks
from chipbench import reference as references

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
# the bounds PERF.md sets from the measured spreads: a rate and a memory
# reading at the 1% floor, the set-up time at the 25% ceiling
BOUNDS = {"tokens_per_s": 0.01, "peak_hbm_gib": 0.01, "setup_s": 0.25}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_entries_have_the_contract_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert {m["name"] for m in BENCH["end_to_end"]} >= {"setup_s"}
    layers = {}
    for m in BENCH["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        layers.setdefault(m["layer"], []).append(m["name"])
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        if m["name"] in BOUNDS:
            assert m["bound"] == BOUNDS[m["name"]], m["name"]
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_files_are_found_by_name(cell):
    c = harness.load_cell(REPO, cell)
    assert c.chips in (1, 4)
    assert set(c.limits) == set(compare.NUMBERS)
    assert all(0 < v < 1 for v in c.limits.values())
    references.model(c.config["reference"]).layout(c.config)
    for m in c.per_layer:
        assert callable(importlib.import_module(f"chipbench.metrics.{m['name']}").read)


@pytest.mark.parametrize("name,layers", [("internlm2-1.8b", 4), ("xlstm-350m", 6)])
def test_config_file_is_the_program_config_at_its_depth(name, layers):
    """The configuration file holds what the program runs: the
    registry's config cut to the file's depth, with the file's RoPE base
    (the published one, where the registry keeps another)."""
    import dataclasses

    from repro.configs import get_config

    cfg = json.loads((REPO / "chipbench" / "configs" / f"{name}.json").read_text())
    assert cfg["num_hidden_layers"] == layers
    want = get_config(cfg["arch_id"], n_layers=layers)
    if "rope_theta" in cfg:
        want = dataclasses.replace(want, rope_theta=cfg["rope_theta"])
    assert harness.program_config(cfg, 1) == want


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_reduced_names_every_key_the_file_changed(entry):
    """``reduced`` in BENCHMARK.json and in the file list the same keys,
    and none of them is a width."""
    cfg = json.loads((REPO / entry["file"]).read_text())
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    for key in entry["reduced"]:
        assert cfg[key] != cfg["reduced"][key]["published"]
        assert not re.search(r"size|_dim$|_rank$|heads|factor", key), key


@pytest.mark.parametrize("name", ["internlm2-1.8b", "xlstm-350m"])
def test_flops_equal_the_programs_analytic_model(name):
    from repro.configs import get_config
    from repro.launch.analytic_model import forward_flops

    cfg = json.loads((REPO / "chipbench" / "configs" / f"{name}.json").read_text())
    program = get_config(cfg["arch_id"], n_layers=cfg["num_hidden_layers"])
    for S in (128, 2048):
        assert flops.forward_flops(cfg, S) == forward_flops(program, S, S)


def test_reference_layout_counts_the_published_parameters():
    for name in ("internlm2-1.8b", "xlstm-350m"):
        cfg = json.loads((REPO / "chipbench" / "configs" / f"{name}.json").read_text())
        layout = references.model(cfg["reference"]).layout(cfg)
        n = sum(math.prod(s) for s, _, _ in layout.values())
        assert n == cfg["parameters"]


def test_unknown_device_kind_has_no_peaks():
    assert peaks.peak_for("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(KeyError):
        peaks.peak_for("cpu")


def test_collective_bytes_count_an_async_pair_once():
    hlo = """
  %cp-start = (f32[4,1024]{1,0}, f32[4,1024]{1,0}, u32[], u32[]) collective-permute-start(%p), source_target_pairs={{0,1}}
  %cp-done = f32[4,1024]{1,0} collective-permute-done(%cp-start)
  %ar = f32[] all-reduce(%x), replica_groups={}
  %add = f32[4,1024]{1,0} add(%a, %b)
"""
    got = hlo_bytes.collective_bytes(hlo)
    assert got["collective-permute"] == 4 * 1024 * 4
    assert got["all-reduce"] == 4
    assert got["collective-count"] == 2
