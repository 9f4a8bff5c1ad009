"""The reduction from a profiler trace to busy, idle and exposed
collective time: on hand-made intervals, and on a small trace recorded
on a TPU v5e (four chips, a matmul and a ring ``ppermute`` a step)."""

import dataclasses

import pytest

from chipbench_tiny import REPO

from chipbench import trace
from chipbench.trace import Trace

FIXTURE = REPO / "chipbench" / "testdata"


def test_interval_arithmetic():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.overlap([(0, 10)], [(2, 3), (5, 20)]) == 6
    assert trace.length([(0, 4), (6, 9)]) == 7


def test_busy_idle_and_exposed_collective_on_hand_made_ops():
    ops = {0: [(0, 40, "fusion.1"), (10, 20, "convolution.2"),      # nested
               (40, 60, "collective-permute-done.1"),
               (50, 55, "fusion.3"),                                 # hides 5
               (80, 100, "fusion.4")],
           1: [(0, 100, "fusion.5")]}
    spans = [(0, 100, "window"), (60, 75, "readback"), (74, 80, "input")]
    tr = Trace(ops=ops, spans=spans, window=(0, 100))
    assert tr.window_s() == pytest.approx(1e-7)
    assert tr.busy_s() == pytest.approx((80 + 100) / 2 * 1e-9)
    assert tr.exposed_collective_s(0) == pytest.approx(15e-9)
    assert tr.exposed_collective_s(1) == 0
    assert tr.idle_gaps(5) == [["readback", pytest.approx(20e-9)]]
    top = dict(tr.top_ops(10))
    assert top["fusion.1"] == pytest.approx(30 / 2 * 1e-9)    # self time
    assert top["convolution.2"] == pytest.approx(10 / 2 * 1e-9)
    assert top["fusion.5"] == pytest.approx(100 / 2 * 1e-9)


def _raw_sums():
    """Per device: total and collective durations of the recorded ops,
    summed straight from the file (no op overlaps another there)."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(FIXTURE / "ring4-ppermute.xplane.pb"))
    out = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    evs = list(line.events)
                    out[int(plane.name.rsplit(":", 1)[1])] = (
                        sum(e.duration_ns for e in evs) * 1e-9,
                        sum(e.duration_ns for e in evs
                            if "collective-permute" in e.name.split(" = ")[0]) * 1e-9,
                        min(e.start_ns for e in evs),
                        max(e.start_ns + e.duration_ns for e in evs))
    return out


def test_recorded_v5e_trace():
    tr = trace.load(FIXTURE)
    assert sorted(tr.ops) == [0, 1, 2, 3]
    assert all(len(ops) == 12 for ops in tr.ops.values())
    assert tr.has_collectives()
    raw = _raw_sums()
    lo = min(r[2] for r in raw.values())
    hi = max(r[3] for r in raw.values())
    whole = Trace(ops=tr.ops, spans=tr.spans, window=(lo, hi))
    for d, (total, coll, _, _) in raw.items():
        assert trace.length(whole.busy(d)) * 1e-9 == pytest.approx(total)
        assert whole.exposed_collective_s(d) == pytest.approx(coll)
    # in the window the host recorded: device time sits about a
    # millisecond early against the host's clock, so the first round's
    # first ops fall before the window's start
    assert tr.window_s() == pytest.approx(4.741279e-3)
    assert tr.busy_s() == pytest.approx(1.3477325e-3)
    assert [tr.exposed_collective_s(d) for d in range(4)] == pytest.approx(
        [1.039283e-3, 1.038667e-3, 1.03859e-3, 1.039253e-3])
    assert [n for n, _ in tr.top_ops(4)] == ["collective-permute-done", "fusion.5",
                                           "add_multiply_fusion", "collective-permute-start"]
    assert tr.idle_gaps(1) == [["readback", pytest.approx(1.775785e-3)]]
    assert len(tr.host_spans("input")) == 3


def test_metric_readers_on_the_recorded_trace():
    import importlib

    from chipbench import peaks

    tr = trace.load(FIXTURE)
    facts = trace.Facts(trace=tr, rounds=3, chips=4, peak=peaks.peak_for("TPU v5 lite"),
                        flops_per_round=1e12,
                        collective_bytes={"collective-permute": 16 << 20, "collective-count": 1})
    read = lambda m, f=facts: importlib.import_module(f"chipbench.metrics.{m}").read(f)  # noqa: E731
    assert read("device_idle_share") == pytest.approx(100 * (1 - 1.3477325e-3 / 4.741279e-3))
    assert read("gossip_exposed_ms") == pytest.approx(1.03895e-3 / 3 * 1e3, rel=1e-3)
    assert read("gossip_bytes") == 16 << 20
    assert read("host_input_ms") == pytest.approx((584711 + 516430 + 337060) / 3 * 1e-6)
    assert read("mfu") == pytest.approx(100 * 3e12 / 4.741279e-3 / (4 * 197e12))
    quiet = trace.Facts(trace=Trace(ops={0: [(0, 5, "fusion")]}, spans=[], window=(0, 10)),
                        rounds=1, chips=1, peak=facts.peak, flops_per_round=1.0,
                        collective_bytes={"collective-count": 0})
    assert read("gossip_exposed_ms", quiet) is None
    assert read("gossip_bytes", quiet) is None
    assert read("host_input_ms", quiet) is None


def test_gossip_exposed_ms_leaves_out_other_collectives():
    """The loss's all-reduce is no gossip: only the exposed
    ``collective-permute`` time counts."""
    import importlib

    from chipbench import peaks

    ops = {0: [(0, 30, "fusion.1"), (30, 50, "collective-permute-done.2"),
               (50, 90, "all-reduce.3"), (90, 100, "fusion.4")]}
    tr = Trace(ops=ops, spans=[(0, 100, "window")], window=(0, 100))
    facts = trace.Facts(trace=tr, rounds=2, chips=1, peak=peaks.peak_for("TPU v5 lite"),
                        flops_per_round=1.0, collective_bytes={"collective-count": 2})
    read = importlib.import_module("chipbench.metrics.gossip_exposed_ms").read
    assert read(facts) == pytest.approx(20e-9 / 2 * 1e3)
    assert tr.exposed_collective_s(0) == pytest.approx(60e-9)
    only_reduce = Trace(ops={0: [(0, 40, "all-reduce.3")]}, spans=[], window=(0, 100))
    assert read(dataclasses.replace(facts, trace=only_reduce)) is None
