"""Architecture registry: ``get_config("<arch-id>")`` / ``--arch <id>``.

Ten assigned architectures (public-literature pool) spanning dense, MoE,
SSM, hybrid, VLM and audio families — see each module's docstring for the
source citation.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, List

from repro.models import ModelConfig

_MODULES: Dict[str, str] = {
    "h2o-danube-1.8b": "h2o_danube_1_8b",
    "xlstm-350m": "xlstm_350m",
    "internvl2-76b": "internvl2_76b",
    "internlm2-1.8b": "internlm2_1_8b",
    "qwen3-moe-30b-a3b": "qwen3_moe_30b_a3b",
    "deepseek-v2-lite-16b": "deepseek_v2_lite_16b",
    "granite-20b": "granite_20b",
    "mistral-large-123b": "mistral_large_123b",
    "whisper-large-v3": "whisper_large_v3",
    "hymba-1.5b": "hymba_1_5b",
}

ARCH_IDS: List[str] = list(_MODULES)


def get_config(arch_id: str, **overrides) -> ModelConfig:
    """The published config of ``arch_id`` with ``overrides`` applied.

    ``n_layers=k`` is a depth cut (:meth:`ModelConfig.with_depth`): the
    first k layers, whole periods of the published layer pattern, with
    every width untouched."""
    key = arch_id.lower()
    if key not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; available: {ARCH_IDS}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[key]}")
    cfg: ModelConfig = mod.CONFIG
    if "n_layers" in overrides:
        cfg = cfg.with_depth(overrides.pop("n_layers"))
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg
