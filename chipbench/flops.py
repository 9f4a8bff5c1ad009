"""Analytic forward FLOPs of one sequence, from a configuration file.

Copied from the program's ``launch/analytic_model.forward_flops`` for the
block kinds the benchmark's configurations use, so that the yardstick
does not move with the program.  Every matmul is counted from the
shapes; a causal attention sees (S + 1) / 2 keys on average.  Training
costs three forward passes (forward, and a backward of twice its
FLOPs); recomputed activations are not counted.
"""

from __future__ import annotations

from typing import Mapping


def _layer_pattern(cfg: Mapping) -> list:
    pattern = list(cfg["layer_pattern"])
    n = cfg["num_hidden_layers"]
    if n % len(pattern):
        raise ValueError(f"{n} layers are not whole periods of {pattern}")
    return pattern * (n // len(pattern))


def padded_vocab(cfg: Mapping) -> int:
    return (cfg["vocab_size"] + 127) // 128 * 128


def _layer_flops(cfg: Mapping, kind: str, S: int) -> float:
    D = cfg["hidden_size"]
    if kind == "attn":
        H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        hd = cfg["head_dim"]
        Te = (S + 1) / 2
        f = 2 * S * D * (H + 2 * K) * hd          # q, k, v projections
        f += 4 * S * Te * H * hd                   # q k^T and p v
        f += 2 * S * H * hd * D                    # output projection
        f += 6 * S * D * cfg["intermediate_size"]  # SwiGLU
        return f
    if kind == "mlstm":
        H = cfg["num_heads"]
        Di = cfg["mlstm_proj_factor"] * D
        hdi = Di // H
        C = min(cfg["mlstm_chunk"], S)
        f = 2 * S * D * 2 * Di                     # up-projection, x and gate
        f += 3 * 2 * S * Di * Di                   # q, k, v
        f += H * (4 * S * C * hdi + 4 * S * hdi * hdi)  # chunked scan
        f += 2 * S * Di * D                        # down-projection
        return f
    if kind == "slstm":
        H = cfg["num_heads"]
        dh = D // H
        f = 2 * S * D * 4 * D + 8 * S * D * dh     # gate pre-activations, recurrence
        f += 2 * S * D * D                         # down-projection
        return f
    raise KeyError(f"no FLOP count for block kind {kind!r}")


def forward_flops(cfg: Mapping, S: int) -> float:
    """Forward FLOPs of one sequence of ``S`` tokens."""
    total = sum(_layer_flops(cfg, k, S) for k in _layer_pattern(cfg))
    return total + 2 * S * cfg["hidden_size"] * padded_vocab(cfg)  # LM head


def train_flops_per_token(cfg: Mapping, S: int) -> float:
    """Forward and backward FLOPs per token of a sequence of ``S``."""
    return 3.0 * forward_flops(cfg, S) / S
