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
def one_chip():
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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


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
