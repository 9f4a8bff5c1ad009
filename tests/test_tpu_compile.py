"""Ahead-of-time compiles for a described TPU v5e: the Pallas kernels of
the main paths, lowered by Mosaic (``interpret=False``) at the shapes
of the configs and deployments that use them, and one full-width
DPASGD step.

Nothing runs, so these tests say nothing about results or speed; they
catch what only the chip's compiler refuses (tiling, VMEM, layouts)
without a chip.  The topology is described inside a module fixture,
never at import: only one process may load the TPU library, and pytest
workers import every test file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.fed.topology_runtime import plan_for_n_silos
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.gossip_mix import gossip_mix_pallas
from repro.kernels.mlstm_scan import mlstm_scan_pallas
from repro.kernels.segment_max import edge_segment_max_pallas


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("B,E,S", [
    (16, 33, 11),       # Gaia climb: 16 restarts, 2N arc slots + N loops
    (16, 8192, 1024),   # N=1024, in-degree <= 8
])
def test_segment_max_compiles(one_chip, B, E, S):
    _compile(lambda v, i: edge_segment_max_pallas(v, i, S, interpret=False),
             ((B, E), jnp.float32), ((B, E), jnp.int32), sharding=one_chip)


def test_gossip_mix_compiles(one_chip):
    K = len(plan_for_n_silos("ring", 4).terms)
    _compile(lambda x, w: gossip_mix_pallas(x, w, interpret=False),
             ((K, 1 << 20), jnp.float32), ((K,), jnp.float32),
             sharding=one_chip)


def test_flash_attention_compiles_at_internlm2_heads(one_chip):
    cfg = get_config("internlm2-1.8b")
    K, G, hd, S = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.head_dim, 2048
    _compile(lambda q, k, v: flash_attention_pallas(q, k, v, interpret=False),
             ((1, S, K, G, hd), jnp.bfloat16), ((1, S, K, hd), jnp.bfloat16),
             ((1, S, K, hd), jnp.bfloat16), sharding=one_chip)


def test_flash_attention_grad_compiles_at_internlm2_heads(one_chip):
    """The kernel's forward and both backward kernels at the benchmark
    cells' shapes: batch 4, 2048 tokens, f32 activations."""
    cfg = get_config("internlm2-1.8b")
    B, S = 4, 2048
    K, G, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.head_dim

    def loss(q, k, v):
        return jnp.sum(flash_attention_pallas(q, k, v, block_q=512, block_kv=512,
                                              mxu_dtype=jnp.bfloat16,
                                              interpret=False))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *[jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
          for s in ((B, S, K, G, hd), (B, S, K, hd), (B, S, K, hd))]
    ).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3


def _on_tpu(monkeypatch):
    """Let the program's backend checks see the described chip: the
    process itself runs on the CPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _state_shapes(cfg, opt, sharding_of):
    from repro.fed import init_state

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding_of(x)),
        jax.eval_shape(lambda k: init_state(cfg, opt, k), jax.random.PRNGKey(0)))


def test_one_silo_step_compiles_with_flash_attention(one_chip, monkeypatch):
    """The benchmark's one-silo round (batch 4 x 2048 tokens, one local
    step) with the kernel on its path: each layer's attention is the
    kernel's forward, its remat recompute and its two backward kernels,
    and no buffer holds the jnp path's f32 score blocks."""
    from repro.fed import DPASGDConfig, make_train_step
    from repro.optim import momentum

    _on_tpu(monkeypatch)
    cfg = get_config("internlm2-1.8b", n_layers=1)
    opt = momentum(0.05, 0.9)
    step = make_train_step(cfg, DPASGDConfig(local_steps=1, gossip_impl="none"),
                           opt, None)
    tokens = jax.ShapeDtypeStruct((1, 4, 2048), jnp.int32, sharding=one_chip)
    text = jax.jit(step, donate_argnums=0).lower(
        _state_shapes(cfg, opt, lambda x: one_chip),
        {"tokens": tokens, "labels": tokens}).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    assert re.search(r"f32\[[\d,]*2048,8,2,1024\]", text) is None


def test_ring4_step_compiles_with_per_chip_flash_attention(topo, monkeypatch):
    """Four silos on four described chips, the ring's one ppermute: each
    chip runs the kernels on its own silo's shapes (no all-gather), and
    the only collectives are the exchange of the parameters and the
    loss's reduction."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

    from repro.fed import DPASGDConfig, make_train_step
    from repro.launch.hlo_analysis import collective_bytes
    from repro.optim import momentum

    _on_tpu(monkeypatch)
    mesh = Mesh(np.array(topo.devices[:4]), ("data",), axis_types=(AxisType.Auto,))
    cfg = get_config("internlm2-1.8b", n_layers=1, n_silos=4)
    opt = momentum(0.05, 0.9)
    step = make_train_step(
        cfg, DPASGDConfig(local_steps=1, gossip_impl="ppermute", silo_axis="data"),
        opt, plan_for_n_silos("ring", 4), mesh)
    silo = lambda x: NamedSharding(mesh, P("data") if x.ndim else P())  # noqa: E731
    state = _state_shapes(cfg, opt, silo)
    tokens = jax.ShapeDtypeStruct((4, 1, 4, 2048), jnp.int32,
                                  sharding=NamedSharding(mesh, P("data")))
    text = jax.jit(step, donate_argnums=0).lower(
        state, {"tokens": tokens, "labels": tokens}).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    assert "all-gather" not in text
    param_bytes = sum(x.size // 4 * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(state["params"]))
    got = collective_bytes(text)
    assert got["collective-permute"] == param_bytes
    assert got["all-reduce"] == 4


def test_mlstm_scan_compiles_at_xlstm_heads(one_chip):
    cfg = get_config("xlstm-350m")
    H, S = cfg.n_heads, 2048
    hd = cfg.ssm.expand * cfg.d_model // H  # models/ssm.py: inner width / heads
    _compile(lambda q, k, v, i, f: mlstm_scan_pallas(q, k, v, i, f,
                                                     interpret=False),
             ((1, S, H, hd), jnp.bfloat16), ((1, S, H, hd), jnp.bfloat16),
             ((1, S, H, hd), jnp.bfloat16), ((1, S, H), jnp.float32),
             ((1, S, H), jnp.float32), sharding=one_chip)


def test_one_silo_step_compiles_at_full_width(one_chip):
    """One silo's DPASGD step of internlm2-1.8b at published widths, one
    layer deep, with the state donated as ``launch/train.py`` does: the
    whole program compiles for the chip and fits its 16 GiB."""
    from repro.fed import DPASGDConfig, init_state, make_train_step
    from repro.optim import momentum

    cfg = get_config("internlm2-1.8b", n_layers=1)
    opt = momentum(0.05, 0.9)
    step = make_train_step(cfg, DPASGDConfig(local_steps=2, gossip_impl="none"),
                           opt, None)
    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(lambda k: init_state(cfg, opt, k),
                       jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((2, 4, 2048), jnp.int32, sharding=one_chip)
    compiled = jax.jit(step, donate_argnums=0).lower(
        state, {"tokens": tokens, "labels": tokens}).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes > 0  # the state is updated in place
    used = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert used < 16 * 2 ** 30
