"""Plain float32 references: one module per model family, named by the
``reference`` key of a configuration file, and the training round they
share (``rounds.py``).  Nothing here imports the program."""

from __future__ import annotations

import importlib


def model(name: str):
    """The reference module ``name``: ``layout(cfg)``, ``loss(params,
    cfg, tokens, labels)`` and ``layer_flops(cfg, kind, S)``, the
    forward FLOPs of one layer of each block kind the family has; and,
    for a family whose files cut a chip's share of the routed experts,
    ``SHARE_FIELDS`` (``harness.share_fields``)."""
    return importlib.import_module(f"chipbench.reference.{name}")
