"""Random weights drawn from the seed, by parameter name.

The benchmark makes the weights, not the program, so that the plain
reference can draw the same ones without taking anything the program
made.  A layout maps each parameter's dotted path (``layers.0.attn.wq``)
to ``(shape, init, scale)``: ``ones``, ``zeros`` or ``normal`` with
standard deviation ``scale``.  Each leaf draws from its own key, folded
from the silo's key and a checksum of its path, so a leaf's values do not
depend on which other leaves exist.
"""

from __future__ import annotations

import zlib
from typing import Dict, Mapping, Tuple

import jax
import jax.numpy as jnp

Layout = Mapping[str, Tuple[Tuple[int, ...], str, float]]


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size: the low 32 bits seed it and the
    rest is folded in, so seeds above 2**32 do not collide."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def silo_key(seed: int, silo: int) -> jax.Array:
    return jax.random.fold_in(seed_key(seed), silo)


def leaf_fold(path: str) -> int:
    """The number folded into the silo's key for the leaf at ``path``."""
    return zlib.crc32(path.encode()) & 0x7FFFFFFF


def draw(key: jax.Array, fold, shape, init: str, scale: float, dtype=jnp.float32):
    """One leaf; ``fold`` may be traced, so leaves of one shape share a
    compiled program."""
    if init == "ones":
        return jnp.ones(shape, dtype)
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    if init == "normal":
        k = jax.random.fold_in(key, fold)
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)
    raise ValueError(f"unknown init {init!r}")


def generate(key: jax.Array, layout: Layout, dtype=jnp.float32) -> Dict[str, jax.Array]:
    """Every leaf of ``layout`` drawn from ``key``; traceable."""
    return {path: draw(key, leaf_fold(path), shape, init, scale, dtype)
            for path, (shape, init, scale) in layout.items()}


def path_name(keypath) -> str:
    """``layers.0.attn.wq`` for a pytree key path."""
    parts = []
    for k in keypath:
        parts.append(str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k)))))
    return ".".join(parts)


def flatten(tree) -> Dict[str, jax.Array]:
    """A parameter pytree as ``{dotted path: leaf}``."""
    return {path_name(p): x for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def unflatten_like(tree, flat: Mapping[str, jax.Array]):
    """``flat`` put into the structure of ``tree`` (matched by path)."""
    paths = [path_name(p) for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
    return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(tree),
                                        [flat[p] for p in paths])
