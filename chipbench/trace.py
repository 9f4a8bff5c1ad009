"""From a profiler trace (``*.xplane.pb``) to per-device operation
intervals and the benchmark's own host spans, and the reductions the
per-layer metrics share: busy time, idle gaps named by the host span
open in them, and collective time that no other operation hides.

Device operations are the events of each TPU plane's ``XLA Ops`` line;
host spans are the ``TraceAnnotation`` events the harness writes
(``window``, ``input``, ``dispatch``, ``readback``).  Times are in
nanoseconds on the profiler's clock, which host and device share.
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
HOST_SPANS = ("window", "input", "dispatch", "readback")
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of ``intervals``."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of (union a) intersected with (union b)."""
    a, b = union(a), union(b)
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Tuple[float, float, str]]]   # device id -> (start, end, name)
    spans: List[Tuple[float, float, str]]            # host spans
    window: Interval

    def device_ops(self, dev: int) -> List[Tuple[float, float, str]]:
        lo, hi = self.window
        return [(max(s, lo), min(e, hi), n) for s, e, n in self.ops[dev] if e > lo and s < hi]

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy(self, dev: int) -> List[Interval]:
        return union([(s, e) for s, e, _ in self.device_ops(dev)])

    def busy_s(self) -> float:
        """Seconds in which an operation ran, mean over the devices."""
        return sum(length(self.busy(d)) for d in self.ops) / len(self.ops) * 1e-9

    def exposed_collective_s(self, dev: int, kind: re.Pattern = COLLECTIVE) -> float:
        """Seconds in the collectives whose names match ``kind`` during
        which no other operation ran."""
        ops = self.device_ops(dev)
        coll = [(s, e) for s, e, n in ops if kind.search(n)]
        rest = [(s, e) for s, e, n in ops if not kind.search(n)]
        return (length(union(coll)) - overlap(coll, rest)) * 1e-9

    def has_collectives(self, kind: re.Pattern = COLLECTIVE) -> bool:
        return any(kind.search(n) for d in self.ops for _, _, n in self.ops[d])

    def host_spans(self, name: str) -> List[Interval]:
        lo, hi = self.window
        return [(s, e) for s, e, n in self.spans if n == name and s >= lo and e <= hi]

    def top_ops(self, k: int) -> List[list]:
        """The ``k`` operations with most self time, seconds per device."""
        total: Dict[str, float] = {}
        for d in self.ops:
            for name, t in _self_times(self.device_ops(d)):
                total[name] = total.get(name, 0.0) + t
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
        return [[n, t / len(self.ops) * 1e-9] for n, t in ranked]

    def idle_gaps(self, k: int) -> List[list]:
        """The ``k`` longest idle gaps of any device, each named by the
        host span that covers most of it (``none`` if no span does)."""
        gaps = []
        lo, hi = self.window
        for d in self.ops:
            edges = [lo] + [x for iv in self.busy(d) for x in iv] + [hi]
            gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                     if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        named = []
        for g in gaps[:k]:
            cover = {n: overlap([g], self.host_spans(n)) for n in HOST_SPANS[1:]}
            best = max(cover, key=cover.get)
            named.append([best if cover[best] > 0 else "none", (g[1] - g[0]) * 1e-9])
        return named


def _self_times(ops: Sequence[Tuple[float, float, str]]):
    """(name, self time) of possibly nested events on one line."""
    stack: List[list] = []
    out = []
    for s, e, n in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            out.append((top[2], top[3]))
        if stack:
            stack[-1][3] -= min(e, stack[-1][1]) - s
        stack.append([s, e, n, e - s])
    out += [(t[2], t[3]) for t in stack]
    return out


def op_name(text: str) -> str:
    """``fusion.12`` for the HLO text ``%fusion.12 = f32[...] fusion(...)``."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(directory, device_ids: Optional[Sequence[int]] = None) -> Trace:
    """The trace under ``directory`` (the newest ``*.xplane.pb``)."""
    import jax

    files = sorted(Path(directory).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {directory}")
    data = jax.profiler.ProfileData.from_file(str(files[-1]))
    ops: Dict[int, list] = {}
    spans = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if device_ids is not None and dev not in device_ids:
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[dev] = [(e.start_ns, e.start_ns + e.duration_ns, op_name(e.name))
                                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in line.events if e.name in HOST_SPANS]
    if not ops:
        raise ValueError(f"no device operations in {files[-1]}")
    windows = [(s, e) for s, e, n in spans if n == "window"]
    if not windows:
        raise ValueError(f"no 'window' span in {files[-1]}")
    return Trace(ops=ops, spans=spans, window=windows[0])


@dataclasses.dataclass
class Facts:
    """What a per-layer metric reads: the traced window, the rounds run
    in it, the devices' peaks, and counts from the compiled step."""
    trace: Trace
    rounds: int
    chips: int
    peak: object
    flops_per_round: float
    collective_bytes: Dict[str, int]
