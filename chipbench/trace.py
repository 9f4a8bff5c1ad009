"""From a profiler trace (``*.xplane.pb``) to per-device operation
intervals, executed steps and host spans, and the reductions the
per-layer metrics share: busy time, idle gaps named by the host span
open in them, collective time that no other operation hides, and how
long after its dispatch a step starts on the device.

Device operations are the events of each TPU plane's ``XLA Ops`` line,
executed steps those of its ``XLA Modules`` line.  Host spans are the
``TraceAnnotation`` events the harness writes (``window``, ``input``,
``dispatch``, ``readback``) and the program's own ``repro.obs`` spans
named in ``scopes.PROGRAM_SPANS``.  Times are in nanoseconds on the
profiler's clock, which host and device share.
"""

from __future__ import annotations

import collections
import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import scopes

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
    # device id -> (start, end) of each execution of the step, in order
    modules: Dict[int, List[Interval]] = dataclasses.field(default_factory=dict)

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

    def dispatch_lead_ms(self) -> Optional[float]:
        """The least, over the window's rounds and the devices, of a
        step's start on the device less the start of the ``dispatch``
        span that sent it, in milliseconds.  Negative: the two clocks
        disagree by at least that much.  A device whose trace holds
        another number of step executions than the window has rounds
        cannot be paired and is left out."""
        sent = self.host_spans("dispatch")
        leads = [(m[0] - d[0]) * 1e-6 for mods in self.modules.values()
                 if len(mods) == len(sent) for m, d in zip(mods, sent)]
        return min(leads) if leads else None


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


def profile(directory):
    """The newest ``*.xplane.pb`` under ``directory``, read."""
    import jax

    files = sorted(Path(directory).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {directory}")
    return jax.profiler.ProfileData.from_file(str(files[-1]))


def host_events(data) -> List[Tuple[float, float, str]]:
    """(start, end, name) of the host spans of a read trace that the
    harness or the program writes (``HOST_SPANS``,
    ``scopes.PROGRAM_SPANS``)."""
    names = set(HOST_SPANS) | set(scopes.PROGRAM_SPANS)
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events if e.name in names]


def _steps(events) -> List[Interval]:
    """(start, end) of each execution of the most frequent module of an
    ``XLA Modules`` line, the step, in time order."""
    if not events:
        return []
    step = collections.Counter(e.name for e in events).most_common(1)[0][0]
    return sorted((e.start_ns, e.start_ns + e.duration_ns) for e in events if e.name == step)


def load(directory, device_ids: Optional[Sequence[int]] = None) -> Trace:
    """The trace under ``directory`` (the newest ``*.xplane.pb``)."""
    data = profile(directory)
    ops: Dict[int, list] = {}
    modules: Dict[int, List[Interval]] = {}
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if not m or (device_ids is not None and int(m.group(1)) not in device_ids):
            continue
        dev = int(m.group(1))
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops[dev] = [(e.start_ns, e.start_ns + e.duration_ns, op_name(e.name))
                            for e in line.events]
            elif line.name == "XLA Modules":
                modules[dev] = _steps(list(line.events))
    if not ops:
        raise ValueError(f"no device operations in the trace under {directory}")
    spans = host_events(data)
    windows = [(s, e) for s, e, n in spans if n == "window"]
    if not windows:
        raise ValueError(f"no 'window' span in the trace under {directory}")
    return Trace(ops=ops, spans=spans, window=windows[0],
                 modules={d: v for d, v in modules.items() if v})


@dataclasses.dataclass
class Facts:
    """What a per-layer metric reads: the traced window, the rounds run
    in it, the devices' peaks, and from the compiled step its
    collective bytes and, for each instruction, the ``op_name`` its
    scope is read from (``scopes.op_names``)."""
    trace: Trace
    rounds: int
    chips: int
    peak: object
    flops_per_round: float
    collective_bytes: Dict[str, int]
    op_names: Dict[str, str] = dataclasses.field(default_factory=dict)
