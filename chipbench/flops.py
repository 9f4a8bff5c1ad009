"""Analytic forward FLOPs of one sequence, from a configuration file.

Each block kind's count comes from its family's reference module, the
one the configuration's ``reference`` key names:
``reference/<reference>.layer_flops(cfg, kind, S)``.  Here are the sum
over the layer pattern, the LM head and the training multiple, so that
a new family brings its count as a file of its own.  The counts were
copied from the program's ``launch/analytic_model.forward_flops``, so
that the yardstick does not move with the program.  Every matmul is
counted from the shapes.  Training costs three forward passes (forward,
and a backward of twice its FLOPs); recomputed activations are not
counted.
"""

from __future__ import annotations

from typing import Mapping

from chipbench import reference as references


def _layer_pattern(cfg: Mapping) -> list:
    pattern = list(cfg["layer_pattern"])
    n = cfg["num_hidden_layers"]
    if n % len(pattern):
        raise ValueError(f"{n} layers are not whole periods of {pattern}")
    return pattern * (n // len(pattern))


def padded_vocab(cfg: Mapping) -> int:
    return (cfg["vocab_size"] + 127) // 128 * 128


def forward_flops(cfg: Mapping, S: int) -> float:
    """Forward FLOPs of one sequence of ``S`` tokens."""
    family = references.model(cfg["reference"])
    total = sum(family.layer_flops(cfg, k, S) for k in _layer_pattern(cfg))
    return total + 2 * S * cfg["hidden_size"] * padded_vocab(cfg)  # LM head


def train_flops_per_token(cfg: Mapping, S: int) -> float:
    """Forward and backward FLOPs per token of a sequence of ``S``."""
    return 3.0 * forward_flops(cfg, S) / S
