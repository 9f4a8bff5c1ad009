"""DPASGD (Eq. 2) — decentralized periodic averaging SGD.

Each silo performs ``s`` local mini-batch steps, then mixes its model with
its overlay in-neighbours through the consensus matrix A:

    w_i(k+1) = sum_{j in N_i^+ u {i}} A_ij w_j(k)        (mix rounds)
    w_i(k+1) = w_i(k) - alpha * grad f_i(w_i(k))          (local rounds)

Federation axes:
* ``n_silos == 1``      — degenerate: centralized data-parallel training
                          (the STAR-inside-one-pod baseline).
* ``n_silos == |axis|`` — every index of the silo mesh axis ("data" on a
                          single pod, "pod" across pods) hosts one silo;
                          params carry a leading silo dim sharded over that
                          axis and the gossip runs as ppermute schedules.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models import ModelConfig
from repro.models import transformer as T
from repro.optim import Optimizer
from .gossip import GossipPlan, gossip_einsum, gossip_shard_map


@dataclass(frozen=True)
class DPASGDConfig:
    """Federation knobs of the DPASGD train step.

    ``local_steps`` is the paper's s (local mini-batch steps between
    mixes); ``gossip_impl`` picks the consensus lowering (see module
    docstring); ``silo_axis`` names the mesh axis hosting one silo per
    index; ``mix_every``/``accum_steps`` are runtime extensions (gossip
    every k-th step, gradient accumulation within a local step).
    """

    local_steps: int = 1            # s
    gossip_impl: str = "ppermute"   # "einsum" | "ppermute" | "pallas" | "none"
    silo_axis: Optional[str] = None  # mesh axis hosting silo replicas
    mix_every: int = 1              # gossip every k-th call (paper: 1)
    accum_steps: int = 1            # gradient-accumulation chunks per local step


def make_loss_fn(cfg: ModelConfig):
    """The loss a silo differentiates, under the ``forward`` scope: its
    ops carry ``jvp(forward)`` in their HLO ``op_name``, and the backward
    pass's (recomputed ops included) ``transpose(jvp(forward))``."""
    def loss(params, batch):
        with jax.named_scope("forward"):
            return T.loss_fn(params, cfg, batch)

    return loss


def masked_consensus(A, active_mask):
    """Renormalize a consensus matrix over the active silos.

    ``A`` is ``[n, n]`` row-stochastic, ``active_mask`` is ``[n]``
    (bool/0-1).  Arcs touching an inactive silo are dropped and each
    surviving row is renormalized to sum to 1, so the weight a silo gave
    its departed in-neighbours is returned to the survivors
    proportionally — consensus keeps averaging over exactly the silos
    still training.  Inactive rows (and active rows whose in-neighbours
    all left) become identity: a departed silo's stale parameters are
    frozen, not pulled toward the survivors.  Pure jnp, so it can run on
    a *traced* mask inside the ``consensus_arg`` train step."""
    A = jnp.asarray(A)
    m = (jnp.asarray(active_mask) > 0).astype(A.dtype)
    Am = A * m[None, :] * m[:, None]
    rows = Am.sum(axis=1, keepdims=True)
    keep = rows > 0
    out = Am / jnp.where(keep, rows, 1.0)
    return jnp.where(keep, out, jnp.eye(A.shape[0], dtype=A.dtype))


def _is_silo_stacked(x, n_silos: int) -> bool:
    """One rule for "does this leaf carry the leading silo dimension":
    shared by the migration and the leaver-row slicer so they cannot
    drift apart."""
    return getattr(x, "ndim", 0) > 0 and x.shape[0] == n_silos


def slice_silo_row(state, active, silo):
    """One silo's row of a silo-stacked train state (host numpy).

    ``active`` is the label tuple the state's leading dim is stacked by.
    Stacked leaves are indexed at the silo's mesh position; shared leaves
    (the step counter) pass through — the shape a leaver's shard is
    checkpointed in (:func:`repro.checkpoint.save_silo_checkpoint`)."""
    row = tuple(active).index(silo)
    n = len(active)

    def pick(x):
        x = np.asarray(jax.device_get(x))
        return x[row] if _is_silo_stacked(x, n) else x

    return jax.tree_util.tree_map(pick, state)


def migrate_silo_state(state, old_active, new_active):
    """Re-stack the silo-stacked train state from one active set to another.

    ``old_active`` / ``new_active`` are the sorted silo-label tuples the
    state's leading dimension is (was / will be) stacked by — mesh
    position k holds silo ``active[k]``.  Gathers every leaf to host and
    re-indexes the silo dimension:

    * **survivors** (labels in both sets) keep their rows *bit-identical*
      — parameters and optimizer slots migrate untouched;
    * **leavers'** rows are dropped (checkpoint them first if wanted —
      see ``launch/train.py --churn-checkpoint``);
    * **joiners** are initialized at the survivors' consensus average
      (uniform mean, accumulated in float64 and cast back to the leaf
      dtype) — the model a silo syncing from its overlay neighbours
      would converge to.

    Leaves without a leading ``len(old_active)`` dimension (the shared
    step counter) pass through unchanged.  Returns
    ``(new_state, joined, left)`` with host-numpy leaves; the caller
    re-shards onto the rebuilt mesh."""
    old_active = tuple(old_active)
    new_active = tuple(new_active)
    old_index = {v: k for k, v in enumerate(old_active)}
    survivors = [v for v in new_active if v in old_index]
    if not survivors:
        raise ValueError(
            f"no surviving silos between {old_active} and {new_active}: "
            "cannot migrate state"
        )
    joined = tuple(v for v in new_active if v not in old_index)
    left = tuple(v for v in old_active if v not in set(new_active))
    surv_rows = [old_index[v] for v in survivors]

    def move(x):
        x = np.asarray(jax.device_get(x))
        if not _is_silo_stacked(x, len(old_active)):
            return x  # shared (unstacked) leaf, e.g. the step counter
        if joined:  # consensus average only needed when someone joins
            avg = x[surv_rows].mean(axis=0, dtype=np.float64).astype(x.dtype)
            rows = [
                x[old_index[v]] if v in old_index else avg for v in new_active
            ]
            return np.stack(rows)
        return x[surv_rows]  # fancy indexing: already a fresh array

    return jax.tree_util.tree_map(move, state), joined, left


def local_sgd_steps(
    loss_fn,
    optimizer: Optimizer,
    params,
    opt_state,
    microbatches,  # pytree with leading dim s (+ optional accum dim)
    step,
    accum_steps: int = 1,
    grad_pspecs=None,
):
    """Run s local optimizer steps via lax.scan over microbatches.

    With ``accum_steps > 1`` each local step's batch carries an extra
    leading accumulation dim [s, A, B_micro, ...]: gradients are averaged
    over the A chunks before the (single) optimizer update — numerically
    identical to one step on the full local batch, but with peak
    activation memory divided by A.
    """

    def _constrain_grads(g):
        # Keep the fp32 accumulators sharded exactly like the params —
        # without this, GSPMD keeps them only model-sharded (fp32 full-
        # FSDP-axis replicas: +7.5 GB/device on qwen3-30B).
        if grad_pspecs is None:
            return g
        from repro.models.act_sharding import constrain

        return jax.tree_util.tree_map(
            lambda x, sp: constrain(x, sp), g, grad_pspecs)

    def one(carry, micro):
        p, o, st = carry
        if accum_steps > 1:
            def acc_fn(g_acc_loss, chunk):
                g_acc, l_acc = g_acc_loss
                l, g = jax.value_and_grad(loss_fn)(p, chunk)
                g_acc = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                return (_constrain_grads(g_acc), l_acc + l), None

            g0 = _constrain_grads(jax.tree_util.tree_map(
                lambda x: jnp.zeros(x.shape, jnp.float32), p))
            (g, l), _ = jax.lax.scan(acc_fn, (g0, 0.0), micro)
            g = jax.tree_util.tree_map(lambda x: x / accum_steps, g)
            l = l / accum_steps
        else:
            l, g = jax.value_and_grad(loss_fn)(p, micro)
        with jax.named_scope("optimizer"):
            p, o = optimizer.update(g, o, p, st)
        return (p, o, st + 1), l

    (params, opt_state, step), losses = jax.lax.scan(
        one, (params, opt_state, step), microbatches
    )
    return params, opt_state, step, losses.mean()


def make_train_step(
    cfg: ModelConfig,
    fed: DPASGDConfig,
    optimizer: Optimizer,
    plan: Optional[GossipPlan],
    mesh: Optional[jax.sharding.Mesh] = None,
    grad_pspecs=None,
    *,
    consensus_arg: bool = False,
) -> Callable:
    """Build the jittable DPASGD train step.

    state  = {"params", "opt_state", "step"}; when n_silos > 1 every leaf
    has a leading silo dimension.
    batch  = {"tokens": [n_silos?, s, B, S], "labels": ...}

    With ``consensus_arg=True`` the step takes the consensus matrix as a
    *traced* third argument — ``step_fn(state, batch, A)`` — and mixes
    via :func:`gossip_einsum`.  That is the lowering for randomized
    schedules (:class:`~repro.fed.gossip.ScheduleSlot`): the sampled
    topology changes every round, so it must be data, not a baked
    constant, or every round would recompile.  ``plan`` is ignored then.

    The traced path also takes an optional fourth argument —
    ``step_fn(state, batch, A, active_mask)`` — an ``[n]`` 0/1 mask that
    renormalizes the consensus over the active silos
    (:func:`masked_consensus`): under elastic membership a silo can
    depart mid-round-window, and the mask keeps the mix from averaging
    in its stale parameters during the one-round lag before the
    controller swaps membership and the loop rebuilds the mesh.
    """
    loss_fn = make_loss_fn(cfg)
    n_silos = cfg.n_silos
    if consensus_arg and fed.gossip_impl not in ("einsum", "none"):
        raise ValueError(
            "consensus_arg=True lowers gossip as a traced einsum; "
            f"gossip_impl={fed.gossip_impl!r} bakes the plan into the "
            "step and cannot follow a per-round matrix"
        )

    def step_fn(state, batch, consensus=None, active_mask=None):
        params, opt_state, step = state["params"], state["opt_state"], state["step"]
        if n_silos == 1:
            params, opt_state, step, loss = local_sgd_steps(
                loss_fn, optimizer, params, opt_state, batch, step,
                accum_steps=fed.accum_steps, grad_pspecs=grad_pspecs,
            )
        else:
            # vmap over the silo dimension: independent local training.
            def per_silo(p, o, b, st):
                p2, o2, _, l = local_sgd_steps(loss_fn, optimizer, p, o, b, st,
                                               accum_steps=fed.accum_steps,
                                               grad_pspecs=grad_pspecs)
                return p2, o2, l

            vm = jax.vmap(per_silo, in_axes=(0, 0, 0, None))
            if fed.silo_axis and mesh is not None:
                # Each device trains its own silos on per-device shapes,
                # so kernels (Pallas custom calls, which GSPMD cannot
                # partition) see one silo's arrays.
                silo = P(fed.silo_axis)
                vm = jax.shard_map(vm, mesh=mesh, in_specs=(silo, silo, silo, P()),
                                   out_specs=(silo, silo, silo), check_vma=False)
            params, opt_state, losses = vm(params, opt_state, batch, step)
            loss = losses.mean()
            # consensus mix (the paper's technique)
            if consensus_arg and fed.gossip_impl != "none":
                A = jnp.asarray(consensus)
                if active_mask is not None:
                    A = masked_consensus(A, active_mask)
                params = gossip_einsum(params, A)
            elif fed.gossip_impl == "einsum":
                params = gossip_einsum(params, jnp.asarray(plan.matrix))
            elif fed.gossip_impl in ("ppermute", "pallas"):
                assert mesh is not None and fed.silo_axis is not None
                params = gossip_shard_map(
                    params, plan, mesh, fed.silo_axis,
                    use_pallas=(fed.gossip_impl == "pallas"),
                )
            elif fed.gossip_impl == "none":
                pass
            else:
                raise KeyError(fed.gossip_impl)
            step = step + fed.local_steps
        return {"params": params, "opt_state": opt_state, "step": step}, {
            "loss": loss
        }

    return step_fn


def init_state(cfg: ModelConfig, optimizer: Optimizer, key: jax.Array,
               dtype=jnp.float32):
    """Initialize training state for :func:`make_train_step`.

    Returns ``{"params", "opt_state", "step"}``; with ``cfg.n_silos > 1``
    every params/opt-state leaf gains a leading ``[n_silos]`` dimension
    (one independently-seeded model per silo) meant to be sharded over
    the silo mesh axis."""
    from repro.models import init_params
    from repro.models.transformer import model_specs

    specs = model_specs(cfg)
    if cfg.n_silos == 1:
        params = init_params(key, specs, dtype)
    else:
        keys = jax.random.split(key, cfg.n_silos)
        params = jax.vmap(lambda k: init_params(k, specs, dtype))(keys)
    opt_state = (
        optimizer.init(params)
        if cfg.n_silos == 1
        else jax.vmap(optimizer.init)(params)
    )
    return {"params": params, "opt_state": opt_state, "step": jnp.zeros((), jnp.int32)}
