"""Multi-device federated tests, executed in a subprocess with 8 virtual
host devices (the main pytest process keeps the default single device,
per the dry-run isolation rule).  Each check prints PASS:<name>."""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.fed import DPASGDConfig, make_train_step, init_state
from repro.fed.gossip import gossip_einsum, gossip_shard_map
from repro.fed.topology_runtime import plan_for_n_silos
from repro.models import ModelConfig
from repro.optim import sgd
from repro.data import SyntheticLMStream, FederatedBatcher


def small_cfg(n_silos):
    return ModelConfig("tiny", "dense", 2, 64, 2, 2, 128, 256, n_silos=n_silos)


from repro.launch.mesh import make_silo_mesh as make_mesh


def shard_state(state, mesh):
    def put(x):
        if getattr(x, "ndim", 0) > 0:
            return jax.device_put(
                x, NamedSharding(mesh, P(*(("data",) + (None,) * (x.ndim - 1)))))
        return x

    return jax.tree_util.tree_map(put, state)


def check_gossip_impls_agree():
    n = 4
    mesh = make_mesh(n)
    cfg = small_cfg(n)
    state = init_state(cfg, sgd(0.1), jax.random.PRNGKey(0))
    params = shard_state(state, mesh)["params"]
    for kind in ("ring", "star", "chain"):
        plan = plan_for_n_silos(kind, n)
        A = jnp.asarray(plan.matrix)
        with jax.set_mesh(mesh):
            ein = gossip_einsum(params, A)
            ppm = gossip_shard_map(params, plan, mesh, "data")
            pal = gossip_shard_map(params, plan, mesh, "data", use_pallas=True)
        for a, b in zip(jax.tree_util.tree_leaves(ein),
                        jax.tree_util.tree_leaves(ppm)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(ein),
                        jax.tree_util.tree_leaves(pal)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    print("PASS:gossip_impls_agree")


def check_dpasgd_trains_and_converges():
    n = 4
    mesh = make_mesh(n)
    cfg = small_cfg(n)
    opt = sgd(0.05)
    plan = plan_for_n_silos("ring", n)
    fed = DPASGDConfig(local_steps=2, gossip_impl="ppermute", silo_axis="data")
    step_fn = make_train_step(cfg, fed, opt, plan, mesh)
    state = shard_state(init_state(cfg, opt, jax.random.PRNGKey(0)), mesh)
    stream = SyntheticLMStream(cfg.vocab_size, 32, n_silos=n)
    batcher = FederatedBatcher(stream, local_steps=2, batch_per_silo=4)
    jstep = jax.jit(step_fn)
    losses = []
    with jax.set_mesh(mesh):
        for i in range(8):
            b = {k: jnp.asarray(v) for k, v in batcher.batch(i).items()}
            state, m = jstep(state, b)
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses
    w = np.asarray(state["params"]["embed"])
    spread = np.abs(w - w.mean(0, keepdims=True)).max()
    assert spread < 0.5 * np.abs(w).max()
    print("PASS:dpasgd_trains_and_converges")


def check_full_mixing_equals_single_worker():
    n = 4
    mesh = make_mesh(n)
    cfg = small_cfg(n)
    opt = sgd(0.1)
    plan = plan_for_n_silos("star", n)
    fed = DPASGDConfig(local_steps=1, gossip_impl="ppermute", silo_axis="data")
    step_fn = make_train_step(cfg, fed, opt, plan, mesh)
    key = jax.random.PRNGKey(1)
    from repro.models import init_params
    from repro.models.transformer import model_specs

    p0 = init_params(key, model_specs(cfg))
    params = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (n,) + x.shape), p0)
    state = {"params": params,
             "opt_state": jax.vmap(opt.init)(params),
             "step": jnp.zeros((), jnp.int32)}
    state = shard_state(state, mesh)
    stream = SyntheticLMStream(cfg.vocab_size, 16, n_silos=1, seed=3)
    one = stream.sample(0, 4, 0)
    batch = {k: jnp.broadcast_to(jnp.asarray(v)[None, None], (n, 1) + v.shape)
             for k, v in one.items()}
    with jax.set_mesh(mesh):
        state, _ = jax.jit(step_fn)(state, batch)
    from repro.fed.dpasgd import local_sgd_steps, make_loss_fn

    loss_fn = make_loss_fn(ModelConfig("tiny", "dense", 2, 64, 2, 2, 128, 256))
    ref_p, _, _, _ = local_sgd_steps(
        loss_fn, opt, p0, opt.init(p0),
        {k: jnp.asarray(v)[None] for k, v in one.items()},
        jnp.zeros((), jnp.int32))
    got = jax.tree_util.tree_map(lambda x: np.asarray(x[0]), state["params"])
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref_p)):
        np.testing.assert_allclose(a, np.asarray(b), atol=1e-5)
    print("PASS:full_mixing_equals_single_worker")


if __name__ == "__main__":
    check_gossip_impls_agree()
    check_dpasgd_trains_and_converges()
    check_full_mixing_equals_single_worker()
    print("ALL_FED_CHECKS_PASSED")
