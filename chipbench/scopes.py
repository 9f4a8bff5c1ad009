"""What the program itself puts in a profiler trace, and the reductions
that read it.

- **Scopes.**  ``fed/dpasgd.py`` and ``fed/gossip.py`` run the round's
  work under ``jax.named_scope``s, which the compiled HLO keeps in each
  instruction's ``op_name`` metadata; the trace names each device
  operation by its instruction alone.  :func:`of_hlo` maps instruction
  names to ``forward``, ``backward``, ``optimizer``, ``gossip`` or
  ``other`` from the compiled step's text, and :func:`device_ms` sums
  device self time per round by scope.
- **Program spans.**  ``repro.obs`` spans, while enabled, are also
  profiler annotations on the device trace's clock; :func:`load` keeps
  those named in ``PROGRAM_SPANS`` beside the benchmark's own spans,
  and each device's ``XLA Modules`` events, one per executed step.
- **Clock.**  :meth:`ProgramTrace.dispatch_lead_ms`: how long after the
  host's ``dispatch`` span opens the step starts on the device.
"""

from __future__ import annotations

import collections
import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence

from chipbench import trace

SCOPES = ("forward", "backward", "optimizer", "gossip", "other")
PROGRAM_SPANS = ("input.batch",)

_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([\w\-]+)\(")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')
_MATMUL = ("dot", "convolution")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def scope_of(op_name: str) -> str:
    """The program scope of an ``op_name`` such as
    ``jit(step_fn)/while/body/closed_call/transpose(jvp(forward))/...``:
    its first ``;``-joined entry, split into name-stack components."""
    parts = op_name.split(";", 1)[0].split("/")
    if "transpose(jvp(forward))" in parts:
        return "backward"
    if "jvp(forward)" in parts:
        return "forward"
    for scope in ("optimizer", "gossip"):
        if scope in parts:
            return scope
    return "other"


@dataclasses.dataclass
class _Instruction:
    opcode: str
    op_name: str
    calls: Optional[str]
    root: bool


def _parse(text: str):
    """(computation -> [instruction name], instruction name -> _Instruction)."""
    computations: Dict[str, List[str]] = {}
    instructions: Dict[str, _Instruction] = {}
    body: List[str] = []
    for line in text.splitlines():
        if line and not line[0].isspace():
            if line.rstrip().endswith("{"):
                body = computations.setdefault(_HEADER.match(line).group(1), [])
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        op_name = _OP_NAME.search(line)
        calls = _CALLS.search(line)
        instructions[m.group(1)] = _Instruction(
            opcode=m.group(2), op_name=op_name.group(1) if op_name else "",
            calls=calls.group(1) if calls else None, root=line.lstrip().startswith("ROOT "))
        body.append(m.group(1))
    return computations, instructions


def _fusion_op_name(name: str, computations, instructions) -> str:
    """The ``op_name`` a fusion is counted under: that of the first
    ``dot`` or ``convolution`` in its fused computation (nested fusions
    searched in order), else that of its root, else its own."""
    def first_matmul(comp: str) -> Optional[str]:
        for n in computations.get(comp, ()):
            ins = instructions[n]
            if ins.opcode in _MATMUL:
                return ins.op_name
            if ins.opcode == "fusion" and ins.calls:
                found = first_matmul(ins.calls)
                if found is not None:
                    return found
        return None

    own = instructions[name]
    found = first_matmul(own.calls)
    if found is not None:
        return found
    root = next((instructions[n] for n in computations.get(own.calls, ())
                 if instructions[n].root), None)
    return root.op_name if root is not None and root.op_name else own.op_name


def op_names(text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` for every instruction of an HLO
    module's text (``compiled.as_text()``), each under the ``op_name``
    its scope is read from.  A fusion takes that of its first matmul, so
    the LM head's weight-gradient matmul fused with its momentum update
    counts as backward; any other instruction, ``.remat`` clones and
    async ``*-start``/``*-done`` halves included, its own."""
    computations, instructions = _parse(text)
    return {name: (_fusion_op_name(name, computations, instructions)
                   if ins.opcode == "fusion" and ins.calls else ins.op_name)
            for name, ins in instructions.items()}


def of_hlo(text: str) -> Dict[str, str]:
    """``{instruction name: scope}`` (``SCOPES``) for every instruction
    of an HLO module's text, from :func:`op_names`."""
    return {name: scope_of(op) for name, op in op_names(text).items()}


def device_ms(tr: trace.Trace, op_scopes: Dict[str, str], rounds: int) -> Dict[str, float]:
    """Device self time per round and chip by scope, mean over the
    chips, in milliseconds; operations the step's text does not name
    count as ``other``.  The scopes add up to the busy time per round."""
    total = dict.fromkeys(SCOPES, 0.0)
    n_ops = sum(len(ops) for ops in tr.ops.values())
    for name, seconds in tr.top_ops(n_ops):
        total[op_scopes.get(name, "other")] += seconds
    return {k: v / rounds * 1e3 for k, v in total.items()}


def read_ms(facts, scope: str) -> Optional[float]:
    """A per-layer metric's reading of ``scope``, or ``None`` when the
    facts carry no op scopes or the step has no operation in it."""
    op_scopes = getattr(facts, "op_scopes", None)
    if not op_scopes or scope not in op_scopes.values() or facts.rounds == 0:
        return None
    return device_ms(facts.trace, op_scopes, facts.rounds)[scope]


@dataclasses.dataclass
class ScopedFacts(trace.Facts):
    """``trace.Facts`` with the compiled step's ``{op: scope}``."""
    op_scopes: Dict[str, str] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class ProgramTrace(trace.Trace):
    """A ``trace.Trace`` whose spans include the program's own, with
    each device's executed steps (``XLA Modules`` events)."""
    modules: Dict[int, List[trace.Interval]] = dataclasses.field(default_factory=dict)

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


def _newest(directory) -> Path:
    files = sorted(Path(directory).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {directory}")
    return files[-1]


def program_spans(directory) -> List[trace.Interval]:
    """(start, end, name) of the host events named in ``PROGRAM_SPANS``
    in the newest trace under ``directory``."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(_newest(directory)))
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events if e.name in PROGRAM_SPANS]


def modules(directory, device_ids: Optional[Sequence[int]] = None) -> Dict[int, List[trace.Interval]]:
    """Per TPU device, the (start, end) of each execution of its most
    frequent ``XLA Modules`` module (the step), in time order."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(_newest(directory)))
    out = {}
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if not m or (device_ids is not None and int(m.group(1)) not in device_ids):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                events = [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]
                if events:
                    step = collections.Counter(n for _, _, n in events).most_common(1)[0][0]
                    out[int(m.group(1))] = sorted((s, e) for s, e, n in events if n == step)
    return out


def load(directory, device_ids: Optional[Sequence[int]] = None) -> ProgramTrace:
    """``trace.load``, with the program's spans and the executed steps."""
    tr = trace.load(directory, device_ids)
    return ProgramTrace(ops=tr.ops, spans=tr.spans + program_spans(directory),
                        window=tr.window, modules=modules(directory, device_ids))
