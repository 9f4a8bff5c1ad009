"""Plain float32 references: one module per model family, named by the
``reference`` key of a configuration file, and the training round they
share (``rounds.py``).  Nothing here imports the program."""

from __future__ import annotations

import importlib


def model(name: str):
    """The reference module ``name``: ``layout(cfg)`` and
    ``loss(params, cfg, tokens, labels)``."""
    return importlib.import_module(f"chipbench.reference.{name}")
