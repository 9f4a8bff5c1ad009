"""Collective bytes of a compiled program, from its optimized HLO text:
the output-shape bytes of every all-gather / all-reduce / reduce-scatter
/ all-to-all / collective-permute summed over the module (the standard
per-device wire-volume approximation).  An async pair counts once, by
its ``-done``, whose output is the result; the ``-start``'s output tuple
holds the operand and the result again.
"""

from __future__ import annotations

import re
from typing import Dict

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """bytes of 'bf16[16,512]{1,0}' — also handles tuple shapes."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind output bytes summed over the module, and
    ``collective-count``."""
    out = {k: 0 for k in _COLLECTIVES}
    out["collective-count"] = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        # '%name = <shape> <op>(' — match op name after the shape
        m = re.match(r"%?[\w.\-]+\s*=\s*(.+?)\s+([\w\-]+)\(", s)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        if op.endswith("-start"):
            continue
        for kind in _COLLECTIVES:
            if op == kind or op.startswith(kind + "-"):
                out[kind] += _shape_bytes(shape_str)
                out["collective-count"] += 1
                break
    return out
