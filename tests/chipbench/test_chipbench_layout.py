"""The benchmark finds its files by name, and its yardstick agrees with
the program's own arithmetic where it was copied from."""

import dataclasses
import importlib
import json
import math
import re
import sys
import types

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


CONFIG_FILES = sorted((REPO / "chipbench" / "configs").glob("*.json"))
CONFIGS = {p.stem: json.loads(p.read_text()) for p in CONFIG_FILES}
# forward FLOPs of one sequence of 2048 tokens: the counts mfu has
# divided by since the benchmark began
PINNED_FLOPS = {"internlm2-1.8b": (1_875_860_520_960, 2_747_842_560),
                "xlstm-350m": (676_994_220_032, 991_690_752)}


@pytest.mark.parametrize("name", sorted(CONFIGS),
                         ids=lambda n: f"{n}-{CONFIGS[n]['num_hidden_layers']}")
def test_config_file_is_the_program_config_at_its_depth(name):
    """The configuration file holds what the program runs: the
    registry's config cut to the file's depth (whole layer periods),
    with the file's RoPE base (the published one, where the registry
    keeps another) and its shares of the vocabulary and the experts."""
    from repro.configs import get_config

    cfg = CONFIGS[name]
    want = get_config(cfg["arch_id"], n_layers=cfg["num_hidden_layers"])
    if "rope_theta" in cfg:
        want = dataclasses.replace(want, rope_theta=cfg["rope_theta"])
    if "vocab_size" in cfg["reduced"]:
        want = dataclasses.replace(want, vocab_size=cfg["vocab_size"])
    for key, field in harness.share_fields(cfg):
        want = harness._with_field(want, field, cfg[key])
    assert harness.program_config(cfg, 1) == want


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_reduced_names_every_key_the_file_changed(entry):
    """``reduced`` in BENCHMARK.json and in the file list the same keys,
    and the harness admits each cut."""
    cfg = json.loads((REPO / entry["file"]).read_text())
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert harness.reduced_refusals(cfg) == []


def _cut(key, value, published, **entry):
    return {key: value, "reduced": {key: {"published": published, "why": "a test", **entry}}}


@pytest.mark.parametrize("cfg,refused", [
    (_cut("num_hidden_layers", 4, 24), []),
    (_cut("num_hidden_layers", 24, 24), ["num_hidden_layers"]),     # not changed
    (_cut("vocab_size", 12800, 102400, share=8), []),
    (_cut("vocab_size", 12800, 102400), ["vocab_size"]),            # no share
    (_cut("vocab_size", 12800, 102400, share=1), ["vocab_size"]),
    (_cut("vocab_size", 6400, 102400, share=16), ["vocab_size"]),   # under an eighth
    (_cut("vocab_size", 12000, 102400, share=8), ["vocab_size"]),   # not 1/share
    (_cut("vocab_size", 16541, 66163, share=4), []),                # rounded up
    (_cut("n_routed_experts", 8, 64, share=8), []),
    (_cut("n_routed_experts", 8, 64), ["n_routed_experts"]),
    (_cut("n_routed_experts", 4, 64, share=16), ["n_routed_experts"]),  # under 8
    (_cut("num_local_experts", 8, 32, share=4), []),
    (_cut("hidden_size", 1024, 2048, share=2), ["hidden_size"]),    # a width
    (_cut("sliding_window", 512, 4096), ["sliding_window"]),
    (_cut("n_shared_experts", 1, 2), ["n_shared_experts"]),
    (_cut("first_k_dense_replace", 0, 1), ["first_k_dense_replace"]),
    (_cut("moe_num_experts", 8, 64, share=8), ["moe_num_experts"]),  # no share key
    (_cut("moe_intermediate_size", 704, 1408), ["moe_intermediate_size"]),
    (_cut("kv_lora_rank", 256, 512), ["kv_lora_rank"]),
    (_cut("qk_rope_head_dim", 32, 64), ["qk_rope_head_dim"]),
    (_cut("num_attention_heads", 8, 16, share=2), ["num_attention_heads"]),
    (_cut("num_experts_per_tok", 2, 6), ["num_experts_per_tok"]),
    (_cut("mlstm_proj_factor", 1, 2), ["mlstm_proj_factor"]),
])
def test_reduced_admits_a_chips_share_and_no_width(cfg, refused):
    """Only the depth and a chip's share of the vocabulary or of the
    routed experts are cut; a share states the chips that share the
    layer and keeps the floors (an eighth of the vocabulary, 8 experts);
    every other key, every width among them, stays refused."""
    got = harness.reduced_refusals(cfg)
    assert [line.split(":")[0] for line in got] == refused, got


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_flops_equal_the_programs_analytic_model(name):
    from repro.launch.analytic_model import forward_flops

    cfg = CONFIGS[name]
    program = harness.program_config(cfg, 1)
    for S in (128, 2048):
        assert flops.forward_flops(cfg, S) == forward_flops(program, S, S)


@pytest.mark.parametrize("name", sorted(PINNED_FLOPS))
def test_flops_are_pinned(name):
    """The FLOP counts, and with them ``mfu``, do not move when a count
    moves between files."""
    forward, per_token = PINNED_FLOPS[name]
    assert flops.forward_flops(CONFIGS[name], 2048) == forward
    assert flops.train_flops_per_token(CONFIGS[name], 2048) == per_token


def test_a_family_brings_its_flop_count_as_a_module(monkeypatch):
    """A block kind ``flops.py`` has never heard of is counted from the
    reference module the configuration names."""
    toy = types.ModuleType("chipbench.reference.toyfamily")

    def layer_flops(cfg, kind, S):
        if kind != "toy":
            raise KeyError(kind)
        return 2 * S * cfg["hidden_size"] * cfg["toy_width"]

    toy.layer_flops = layer_flops
    monkeypatch.setitem(sys.modules, "chipbench.reference.toyfamily", toy)
    cfg = {"reference": "toyfamily", "layer_pattern": ["toy"], "num_hidden_layers": 3,
           "hidden_size": 64, "toy_width": 96, "vocab_size": 200}
    assert flops.forward_flops(cfg, 16) == 3 * 2 * 16 * 64 * 96 + 2 * 16 * 64 * 256
    with pytest.raises(KeyError):
        flops.forward_flops({**cfg, "layer_pattern": ["attn"]}, 16)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_layout_counts_the_published_parameters(name):
    cfg = CONFIGS[name]
    layout = references.model(cfg["reference"]).layout(cfg)
    n = sum(math.prod(s) for s, _, _ in layout.values())
    assert n == cfg["parameters"]


def _deepseek(reduced=(), share_fields=None, monkeypatch=None):
    """A configuration file of ``deepseek-v2-lite-16b`` at 2 layers with
    the ``share`` cuts ``reduced`` of the guide's example (8 chips share
    each layer), its reference a toy module with ``share_fields`` as its
    ``SHARE_FIELDS``."""
    toy = types.ModuleType("chipbench.reference.toymoe")
    if share_fields is not None:
        toy.SHARE_FIELDS = share_fields
    monkeypatch.setitem(sys.modules, "chipbench.reference.toymoe", toy)
    cuts = {"vocab_size": (12800, 102400), "n_routed_experts": (8, 64)}
    cfg = {"arch_id": "deepseek-v2-lite-16b", "reference": "toymoe", "hidden_size": 2048,
           "num_hidden_layers": 2, "layer_pattern": ["mla", "mla_moe"],
           "num_attention_heads": 16, "num_key_value_heads": 16, "head_dim": 128,
           "intermediate_size": 10944, "vocab_size": 102400, "n_routed_experts": 64,
           "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
           "reduced": {"num_hidden_layers": {"published": 27, "why": "a test"}}}
    for key in reduced:
        cfg[key] = cuts[key][0]
        cfg["reduced"][key] = {"published": cuts[key][1], "share": 8, "why": "a test"}
    assert harness.reduced_refusals(cfg) == []
    return cfg


def test_a_share_goes_to_the_field_its_family_names(monkeypatch):
    """The vocabulary's share is the program's vocabulary; the experts'
    share goes to the field the family's ``SHARE_FIELDS`` names (here
    ``moe.n_shared``, standing in for the experts one chip computes,
    which the program has no field for yet), and nothing else moves."""
    base = harness.program_config(_deepseek(monkeypatch=monkeypatch), 1)
    got = harness.program_config(_deepseek(("vocab_size", "n_routed_experts"),
                                           {"n_routed_experts": "moe.n_shared"}, monkeypatch), 1)
    assert got.vocab_size == 12800 and got.moe.n_shared == 8
    assert got.moe.n_experts == 64 and got.moe.top_k == 6 and got.moe.d_expert == 1408
    assert dataclasses.replace(got, vocab_size=base.vocab_size, moe=base.moe) == base
    only_vocab = harness.program_config(_deepseek(("vocab_size",), monkeypatch=monkeypatch), 1)
    assert dataclasses.replace(base, vocab_size=12800) == only_vocab


@pytest.mark.parametrize("share_fields,says", [
    (None, "names no program field"),
    ({"num_experts": "moe.n_shared"}, "names no program field"),
    ({"n_routed_experts": "moe.n_experts"}, "router's width"),
    ({"n_routed_experts": "moe.no_such_field"}, "no field 'moe.no_such_field'"),
    ({"n_routed_experts": "n_local_experts"}, "no field 'n_local_experts'"),
    ({"n_routed_experts": "d_model.n"}, "no field 'd_model.n'"),
])
def test_a_share_of_the_experts_is_refused(share_fields, says, monkeypatch):
    """A share of the experts with no field of the family to hold it, a
    field the program lacks, and the router's own count (its width) are
    errors."""
    cfg = _deepseek(("n_routed_experts",), share_fields, monkeypatch)
    with pytest.raises(ValueError, match=re.escape(says)):
        harness.program_config(cfg, 1)


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
