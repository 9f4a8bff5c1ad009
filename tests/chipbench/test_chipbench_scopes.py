"""The program's scopes and spans as the benchmark reads them: op ->
scope from compiled HLO, device time per round by scope, the per-layer
readers of the scopes (``named_ms`` among them), ``repro.obs`` spans in
a profiler trace, and the dispatch lead on the recorded TPU v5e trace."""

import importlib
import os
import re
import subprocess
import sys
import tempfile

import jax
import pytest

from chipbench_tiny import DENSE, REPO, cell, traffic

from chipbench import harness, peaks, scopes, trace
from chipbench.trace import Trace

FIXTURE = REPO / "chipbench" / "testdata"
NEW_METRICS = ("forward_ms", "backward_ms", "optimizer_ms", "gossip_ms", "host_batch_ms")

HLO = r"""HloModule jit_step_fn, is_scheduled=true

%fused_computation.1 (param_0: f32[4,4], param_1: f32[4,4]) -> f32[4,4] {
  %param_0 = f32[4,4]{1,0} parameter(0)
  %param_1 = f32[4,4]{1,0} parameter(1)
  %dot.1 = f32[4,4]{1,0} dot(%param_0, %param_1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step_fn)/while/body/closed_call/transpose(jvp(forward))/dot_general" stack_frame_id=3}
  ROOT %sub.1 = f32[4,4]{1,0} subtract(%param_0, %dot.1), metadata={op_name="jit(step_fn)/while/body/closed_call/optimizer/sub"}
}

%fused_computation.2 (param_0.1: f32[4,4]) -> f32[4,4] {
  %param_0.1 = f32[4,4]{1,0} parameter(0)
  ROOT %mul.2 = f32[4,4]{1,0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(step_fn)/while/body/closed_call/optimizer/mul"}
}

%add (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%x, %y)
}

ENTRY %main.9 (Arg_0.1: f32[4,4], Arg_1.2: f32[4,4]) -> f32[4,4] {
  %Arg_0.1 = f32[4,4]{1,0} parameter(0), metadata={op_name="state[\'params\']"}
  %Arg_1.2 = f32[4,4]{1,0} parameter(1)
  %multiply_subtract_fusion = f32[4,4]{1,0} fusion(%Arg_0.1, %Arg_1.2), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step_fn)/while/body/closed_call/optimizer/sub"}
  %fusion.2 = f32[4,4]{1,0} fusion(%multiply_subtract_fusion), kind=kLoop, calls=%fused_computation.2
  %fusion.2.remat = f32[4,4]{1,0} fusion(%multiply_subtract_fusion), kind=kLoop, calls=%fused_computation.2
  %convolution.5 = f32[4,4]{1,0} convolution(%fusion.2, %Arg_1.2), dim_labels=bf_io->bf, metadata={op_name="jit(step_fn)/while/body/closed_call/jvp(forward)/dot_general;jit(step_fn)/optimizer/mul"}
  %collective-permute-start.3 = (f32[4,4]{1,0}, f32[4,4]{1,0}, u32[], u32[]) collective-permute-start(%convolution.5), source_target_pairs={{0,1},{1,0}}, metadata={op_name="jit(step_fn)/gossip/shard_map/ppermute"}
  %collective-permute-done.3 = f32[4,4]{1,0} collective-permute-done(%collective-permute-start.3), metadata={op_name="jit(step_fn)/gossip/shard_map/ppermute"}
  ROOT %all-reduce = f32[4,4]{1,0} all-reduce(%collective-permute-done.3), replica_groups={}, to_apply=%add, metadata={op_name="jit(step_fn)/reduce_sum"}
}
"""


def test_scope_of_reads_the_first_entry_by_name_stack_component():
    assert scopes.scope_of("jit(f)/while/body/closed_call/jvp(forward)/dot_general") == "forward"
    assert scopes.scope_of("jit(f)/transpose(jvp(forward))/jvp(forward)/checkpoint/"
                           "rematted_computation/mul") == "backward"
    assert scopes.scope_of("jit(f)/vmap()/while/body/closed_call/optimizer/sub") == "optimizer"
    assert scopes.scope_of("jit(f)/gossip/shard_map/ppermute") == "gossip"
    assert scopes.scope_of("jit(f)/optimizer/mul;jit(f)/gossip/add") == "optimizer"
    assert scopes.scope_of("state['opt_state']") == "other"
    assert scopes.scope_of("jit(f)/reduce_sum") == "other"
    assert scopes.scope_of("") == "other"


def of_hlo(text):
    """``{instruction name: scope}`` of an HLO module's text."""
    return {name: scopes.scope_of(op) for name, op in scopes.op_names(text).items()}


def test_of_hlo_on_hand_made_text():
    got = of_hlo(HLO)
    # a fusion takes its matmul's scope: a weight gradient fused with
    # its update is backward
    assert got["multiply_subtract_fusion"] == "backward"
    # no matmul: the root's scope; .remat clones under their own names
    assert got["fusion.2"] == "optimizer"
    assert got["fusion.2.remat"] == "optimizer"
    assert got["convolution.5"] == "forward"        # first of a ;-joined list
    assert got["collective-permute-start.3"] == got["collective-permute-done.3"] == "gossip"
    assert got["all-reduce"] == "other"
    assert got["Arg_0.1"] == got["Arg_1.2"] == "other"
    assert got["dot.1"] == "backward" and got["sub.1"] == "optimizer"
    assert got["add.9"] == "other"


def _instruction_names(text):
    return {m.group(1) for m in re.finditer(r"^\s+(?:ROOT )?%([\w.\-]+) = ", text, re.M)}


def test_of_hlo_on_the_compiled_tiny_step():
    c = cell(DENSE, traffic(), "internlm2-local")
    sut = harness.SystemUnderTest(c, 2 ** 31 + 5, jax.devices()[:1])
    text = sut.step.lower(sut.state, harness.device_batch(sut.host_batch(0))).compile().as_text()
    got = of_hlo(text)
    assert set(got) == _instruction_names(text)
    assert set(got.values()) <= set(scopes.SCOPES)
    assert {"forward", "backward", "optimizer"} <= set(got.values())
    assert "gossip" not in got.values()


RING = """
import sys
import jax
sys.path[:0] = [{tests!r}, {repo!r}, {src!r}]
from chipbench_tiny import DENSE, cell, traffic
from chipbench import harness, scopes
sut = harness.SystemUnderTest(cell(DENSE, traffic(silos=4), "internlm2-ring4"), 2 ** 31 + 7,
                              jax.devices()[:4])
text = sut.step.lower(sut.state, harness.device_batch(sut.host_batch(0))).compile().as_text()
got = {{n: scopes.scope_of(op) for n, op in scopes.op_names(text).items()}}
print(" ".join(sorted(set(got.values()))))
print(" ".join(sorted({{got[n] for n in got if "permute" in n}})))
"""


def test_of_hlo_finds_the_gossip_of_a_four_silo_ring():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    code = RING.format(tests=str(REPO / "tests" / "chipbench"), repo=str(REPO),
                       src=str(REPO / "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    every, permutes = r.stdout.strip().splitlines()
    assert set(every.split()) == set(scopes.SCOPES)
    assert permutes == "gossip"


def _facts(tr, op_names, rounds=2):
    return trace.Facts(trace=tr, rounds=rounds, chips=len(tr.ops),
                       peak=peaks.peak_for("TPU v5 lite"), flops_per_round=1.0,
                       collective_bytes={"collective-count": 0}, op_names=op_names)


def _read(name, facts):
    return importlib.import_module(f"chipbench.metrics.{name}").read(facts)


FORWARD = "jit(step_fn)/while/body/closed_call/jvp(forward)/dot_general"
BACKWARD = "jit(step_fn)/while/body/closed_call/transpose(jvp(forward))/dot_general"


def test_new_readers_on_a_hand_built_trace():
    ops = {0: [(0, 40, "fusion.1"), (10, 20, "convolution.2"),      # nested
               (40, 60, "collective-permute-done.1"), (60, 70, "add.3"),
               (80, 100, "all-reduce")],
           1: [(0, 100, "fusion.1")]}
    spans = [(0, 100, "window"), (5, 9, "input.batch"), (50, 56, "input.batch")]
    tr = Trace(ops=ops, spans=spans, window=(0, 100))
    op_names = {"fusion.1": FORWARD, "convolution.2": BACKWARD,
                "collective-permute-done.1": "jit(step_fn)/gossip/shard_map/ppermute",
                "add.3": "jit(step_fn)/optimizer/add", "all-reduce": "jit(step_fn)/reduce_sum"}
    facts = _facts(tr, op_names)
    # self time per chip, mean over the chips, per round: ns * 1e-6 = ms
    assert _read("forward_ms", facts) == pytest.approx((30 + 100) / 2 / 2 * 1e-6)
    assert _read("backward_ms", facts) == pytest.approx(10 / 2 / 2 * 1e-6)
    assert _read("gossip_ms", facts) == pytest.approx(20 / 2 / 2 * 1e-6)
    assert _read("optimizer_ms", facts) == pytest.approx(10 / 2 / 2 * 1e-6)
    assert _read("host_batch_ms", facts) == pytest.approx(5 * 1e-6)
    by_scope = scopes.device_ms(tr, op_names, 2)
    assert by_scope["other"] == pytest.approx(20 / 2 / 2 * 1e-6)
    assert sum(by_scope.values()) == pytest.approx(tr.busy_s() / 2 * 1e3)


def test_named_ms_reads_a_scope_in_forward_and_backward_alike():
    """A scope nested under ``forward`` is found in the forward pass, in
    the backward pass and in the backward's recompute; a scope of the
    same name as a part of another component is not it."""
    ops = {0: [(0, 10, "fusion.1"), (10, 40, "fusion.2"), (40, 45, "fusion.3"),
               (45, 60, "fusion.4"), (60, 100, "fusion.5")],
           1: [(0, 20, "fusion.1"), (20, 100, "fusion.5")]}
    tr = Trace(ops=ops, spans=[(0, 100, "window")], window=(0, 100))
    op_names = {
        "fusion.1": "jit(step_fn)/jvp(forward)/layer/moe_dispatch/scatter-add",
        "fusion.2": "jit(step_fn)/transpose(jvp(forward))/layer/moe_dispatch/gather",
        "fusion.3": "jit(step_fn)/transpose(jvp(forward))/jvp(forward)/checkpoint/"
                    "rematted_computation/transpose(jvp(moe_dispatch))/mul;jit(step_fn)/x",
        "fusion.4": "jit(step_fn)/jvp(forward)/moe_dispatch_weights/dot_general",
        "fusion.5": "jit(step_fn)/optimizer/sub;jit(step_fn)/moe_dispatch/add",
    }
    facts = _facts(tr, op_names, rounds=5)
    want = ((10 + 30 + 5) + 20) / 2 / 5 * 1e-6
    assert scopes.named_ms(facts, "moe_dispatch") == pytest.approx(want)
    assert scopes.named_ms(facts, "forward") == pytest.approx((10 + 30 + 5 + 15 + 20) / 2 / 5 * 1e-6)
    assert scopes.named_ms(facts, "moe_combine") is None
    assert scopes.named_ms(_facts(tr, {}), "moe_dispatch") is None


def test_new_readers_read_nothing_where_the_program_left_nothing():
    tr = Trace(ops={0: [(0, 5, "fusion.1")]}, spans=[(0, 10, "window")], window=(0, 10))
    plain = trace.Facts(trace=tr, rounds=1, chips=1, peak=peaks.peak_for("TPU v5 lite"),
                        flops_per_round=1.0, collective_bytes={"collective-count": 0})
    for name in NEW_METRICS:
        assert _read(name, plain) is None
    local = _facts(tr, {"fusion.1": FORWARD}, rounds=1)
    assert _read("forward_ms", local) == pytest.approx(5e-6)
    assert _read("gossip_ms", local) is None
    assert _read("backward_ms", local) is None
    assert _read("host_batch_ms", local) is None


def test_obs_span_lands_in_the_profiler_trace():
    from repro.data import FederatedBatcher, SyntheticLMStream
    from repro.obs import spans

    batcher = FederatedBatcher(SyntheticLMStream(64, 8, n_silos=2), 1, 2)
    spans.reset()
    spans.enable()
    try:
        with tempfile.TemporaryDirectory() as tdir:
            with jax.profiler.trace(tdir):
                with spans.span("window"):
                    batcher.batch(3)
            found = [e for e in trace.host_events(trace.profile(tdir))
                     if e[2] in scopes.PROGRAM_SPANS]
    finally:
        spans.disable()
    records = spans.pop_finished()
    spans.reset()
    batch = next(r for r in records if r.name == "input.batch")
    window = next(r for r in records if r.name == "window")
    assert batch.attrs == {"step": 3} and batch.parent == "window"
    assert [n for _, _, n in found] == ["input.batch"]
    (s, e, _), = found
    # the profiler's event lies inside the span's perf_counter interval
    assert 0 < e - s <= batch.duration_s * 1e9 <= window.duration_s * 1e9


def test_recorded_trace_dispatch_lead_and_scopes():
    tr = trace.load(FIXTURE)
    assert sorted(tr.modules) == [0, 1, 2, 3]
    assert all(len(m) == 3 for m in tr.modules.values())
    assert len(tr.host_spans("dispatch")) == 3
    # each step starts on the device about a millisecond before its
    # dispatch span opens on the host: the clocks disagree by that much
    assert tr.dispatch_lead_ms() == pytest.approx(-1.089566, abs=1e-6)
    facts = _facts(tr, {}, rounds=3)
    for name in NEW_METRICS:
        assert _read(name, facts) is None
    every_op_other = scopes.device_ms(tr, {}, 3)
    assert every_op_other["other"] == pytest.approx(tr.busy_s() / 3 * 1e3)
    assert set(every_op_other.values()) - {every_op_other["other"]} == {0.0}


def test_dispatch_lead_leaves_out_a_device_it_cannot_pair():
    """A device whose trace holds more step executions than the window
    has rounds is left out."""
    spans = [(100, 1000, "window"), (110, 120, "dispatch"), (300, 310, "dispatch"),
             (500, 505, "dispatch")]
    early = [(10, 60), (60, 95)]
    tr = Trace(ops={0: [(130, 900, "fusion.1")]}, spans=spans, window=(100, 1000),
               modules={0: early + [(130, 400), (400, 700), (700, 900)],
                        1: [(125, 400), (400, 700), (700, 900)]})
    assert tr.dispatch_lead_ms() == pytest.approx(15e-6)    # device 1, first round
    assert Trace(ops={}, spans=spans, window=(100, 1000)).dispatch_lead_ms() is None
