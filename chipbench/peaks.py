"""Published peaks of the accelerators the benchmark runs on, keyed by
JAX's ``device_kind``.  A device that is not in the table is an error:
no metric is computed against a guessed peak."""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peak:
    bf16_flops: float          # FLOP/s, dense bf16 matmul
    hbm_bytes_per_s: float     # HBM bandwidth
    hbm_bytes: float           # HBM capacity
    ici_bits_per_s: float      # chip-to-chip interconnect, all links
    source: str


PEAKS = {
    "TPU v5 lite": Peak(
        bf16_flops=197e12,
        hbm_bytes_per_s=819e9,
        hbm_bytes=16e9,
        ici_bits_per_s=1600e9,
        source="Google Cloud documentation, 'TPU v5e' (system architecture)",
    ),
}


def peak_for(device_kind: str) -> Peak:
    """The peaks of ``device_kind``; ``KeyError`` for an unknown device."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
