"""JAX's persistent compilation cache at one fixed place.

A cold process on a chip otherwise recompiles every kernel and step.
The cache is keyed by its directory too, so the directory never moves:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads
that variable itself), else ``<repo root>/.jax_cache``.
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use.

    Called by the entry points (``launch/train.py``, ``chip_smoke.py``),
    never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
