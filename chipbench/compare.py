"""The comparison that decides ``correct``.

Four numbers, each the worst case over what it covers, are compared
with their limits (``limits/<cell>.json``):

- ``loss_gap``: over the first three rounds, |program loss - reference
  loss| / reference loss.
- ``grad1_gap``: the momentum after the first round (the first gradient,
  as the optimizer holds it, when a round is one local step), per silo
  and leaf: |program norm - reference norm| / max(reference norm, median
  leaf's reference norm).
- ``change3_gap``: the same for the parameters' change over the first
  three rounds, gossip included.  Leaves whose reference gradient is
  under a thousandth of the median leaf's are left out: under momentum
  they move by round-off alone.
- ``avg_change3_gap``: the same for the change of the silos' average
  parameters.  The gossip mix leaves the average where it is, so this is
  the training's own change even where the mix moves each silo's
  parameters far more; with one silo it equals ``change3_gap``.
"""

from __future__ import annotations

import functools
import json
from pathlib import Path
from typing import Dict, Mapping

import jax
import jax.numpy as jnp
import numpy as np

NUMBERS = ("loss_gap", "grad1_gap", "change3_gap", "avg_change3_gap")
NEGLIGIBLE_GRAD = 1e-3


def _silo_norm(x, stacked):
    x = x.astype(jnp.float32) if stacked else x.astype(jnp.float32)[None]
    return jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim))))


@functools.partial(jax.jit, static_argnums=1)
def leaf_norms(flat: Mapping[str, jax.Array], stacked: bool) -> Dict[str, jax.Array]:
    """L2 norm of each leaf, ``[silos]``; ``stacked`` says whether the
    leaves carry a leading silo axis (else they are one silo's)."""
    return {k: _silo_norm(x, stacked) for k, x in flat.items()}


@functools.partial(jax.jit, static_argnums=2)
def change_norm(now: jax.Array, then: jax.Array, stacked: bool) -> jax.Array:
    """The norm of ``now - then``, ``[silos]``."""
    return _silo_norm(now.astype(jnp.float32) - then, stacked)


@functools.partial(jax.jit, static_argnums=2)
def avg_change_norm(now: jax.Array, then: jax.Array, stacked: bool) -> jax.Array:
    """The norm of the silos' average of ``now - then``, ``[1]``."""
    d = now.astype(jnp.float32) - then
    return _silo_norm(jnp.mean(d, axis=0) if stacked else d, False)


def _worst_leaf_gap(prog: Mapping[str, np.ndarray], ref: Mapping[str, np.ndarray],
                    keep=None) -> tuple:
    keys = [k for k in ref if keep is None or keep[k].all()]
    r = np.stack([np.asarray(ref[k], np.float64) for k in keys])     # [leaves, silos]
    p = np.stack([np.asarray(prog[k], np.float64) for k in keys])
    floor = np.maximum(r, np.median(r, axis=0, keepdims=True))
    gap = np.abs(p - r) / floor
    i = np.unravel_index(np.argmax(gap), gap.shape)
    return float(gap[i]), f"{keys[i[0]]}[silo {i[1]}]"


def gaps(prog: Mapping, ref: Mapping) -> Dict[str, tuple]:
    """``{number: (value, where)}`` from the program's and the
    reference's readings (``losses``, ``grad1``, ``change3``,
    ``avg_change3``)."""
    pl = np.asarray(prog["losses"], np.float64)
    rl = np.asarray(ref["losses"], np.float64)
    loss = np.abs(pl - rl) / np.abs(rl)
    g1 = {k: np.atleast_1d(v) for k, v in ref["grad1"].items()}
    med = np.median(np.stack(list(g1.values())), axis=0)
    keep = {k: v >= NEGLIGIBLE_GRAD * med for k, v in g1.items()}
    as1d = lambda d: {k: np.atleast_1d(v) for k, v in d.items()}  # noqa: E731
    keep_avg = {k: v.all(keepdims=True) for k, v in keep.items()}
    return {
        "loss_gap": (float(loss.max()), f"round {int(loss.argmax())}"),
        "grad1_gap": _worst_leaf_gap(as1d(prog["grad1"]), g1),
        "change3_gap": _worst_leaf_gap(as1d(prog["change3"]), as1d(ref["change3"]), keep),
        "avg_change3_gap": _worst_leaf_gap(as1d(prog["avg_change3"]),
                                           as1d(ref["avg_change3"]), keep_avg),
    }


def load_limits(root: Path, cell: str) -> Dict[str, float]:
    data = json.loads((root / "chipbench" / "limits" / f"{cell}.json").read_text())
    return {k: float(data[k]["limit"]) for k in NUMBERS}


def judge(found: Mapping[str, tuple], limits: Mapping[str, float]) -> bool:
    """Correct when every number is finite and at or under its limit."""
    return all(np.isfinite(found[k][0]) and found[k][0] <= limits[k] for k in NUMBERS)
