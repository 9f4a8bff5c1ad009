"""Shared interpret-mode resolution for the Pallas kernels.

``interpret=None`` everywhere means *auto*: lower via Mosaic when the
default backend is a TPU, fall back to the Pallas interpreter otherwise
(CPU test runs).  An explicit ``interpret=False`` compiles for the TPU
wherever the caller lowers (the ahead-of-time compile tests pass it).
"""

from __future__ import annotations

from typing import Optional

import jax


def interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Explicit True/False wins; None auto-detects."""
    if interpret is None:
        return interpret_default()
    return bool(interpret)
