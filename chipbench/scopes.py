"""What the program itself puts in a profiler trace, and the reductions
that read it.

- **Scopes.**  ``fed/dpasgd.py`` and ``fed/gossip.py`` run the round's
  work under ``jax.named_scope``s, which the compiled HLO keeps in each
  instruction's ``op_name`` metadata; the trace names each device
  operation by its instruction alone.  :func:`op_names` maps instruction
  names to their ``op_name`` from the compiled step's text,
  :func:`scope_of` an ``op_name`` to ``forward``, ``backward``,
  ``optimizer``, ``gossip`` or ``other``, and :func:`device_ms` sums
  device self time per round by scope.
  :func:`named_ms` reads any scope by name, so that the reader of a
  new ``jax.named_scope`` is one call.
- **Program spans.**  ``repro.obs`` spans, while enabled, are also
  profiler annotations on the device trace's clock; ``trace.load``
  keeps those named in ``PROGRAM_SPANS`` beside the benchmark's own.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional

SCOPES = ("forward", "backward", "optimizer", "gossip", "other")
PROGRAM_SPANS = ("input.batch",)

_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([\w\-]+)\(")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')
_MATMUL = ("dot", "convolution")


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


def device_ms(tr, names: Dict[str, str], rounds: int) -> Dict[str, float]:
    """Device self time per round and chip by scope (``SCOPES``) of the
    trace ``tr`` (``trace.Trace``), mean over the chips, in
    milliseconds; ``names`` is :func:`op_names` of the step, and an
    operation it does not name counts as ``other``.  The scopes add up
    to the busy time per round."""
    total = dict.fromkeys(SCOPES, 0.0)
    for name, seconds in _all_ops(tr):
        total[scope_of(names.get(name, ""))] += seconds
    return {k: v / rounds * 1e3 for k, v in total.items()}


def read_ms(facts, scope: str) -> Optional[float]:
    """A per-layer metric's reading of ``scope`` from ``facts``
    (``trace.Facts``), or ``None`` when the facts carry no op names or
    the step has no operation in it."""
    if facts.rounds == 0 or scope not in {scope_of(op) for op in facts.op_names.values()}:
        return None
    return device_ms(facts.trace, facts.op_names, facts.rounds)[scope]


def named_ms(facts, name: str) -> Optional[float]:
    """Device self time per round and chip, mean over the chips, in
    milliseconds, of the operations whose name stack holds the component
    ``name`` (a ``jax.named_scope`` of the program), in forward and
    backward alike: ``moe_dispatch`` is found in
    ``jvp(forward)/moe_dispatch`` and in
    ``transpose(jvp(forward))/moe_dispatch``, and ``forward`` in both.
    ``None`` where the step has no such operation."""
    inside = {n for n, op in facts.op_names.items() if name in _components(op)}
    if facts.rounds == 0 or not inside:
        return None
    return sum(t for n, t in _all_ops(facts.trace) if n in inside) / facts.rounds * 1e3


_TRANSFORM = re.compile(r"^(?:[\w\-]+\()+([^()]*)\)+$")


def _components(op_name: str) -> List[str]:
    """The name-stack components of an ``op_name``'s first ``;``-joined
    entry, each out of its transforms: ``transpose(jvp(forward))`` is
    ``forward``."""
    parts = op_name.split(";", 1)[0].split("/")
    return [m.group(1) if (m := _TRANSFORM.match(p)) else p for p in parts]


def _all_ops(tr):
    """(name, seconds per chip) of every operation of the trace."""
    return tr.top_ops(sum(len(ops) for ops in tr.ops.values()))
