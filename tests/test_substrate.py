"""Substrate layers: optimizers, data pipeline, checkpointing, params."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.optim import sgd, momentum, adam, adamw, clip_by_global_norm
from repro.optim.optimizers import inverse_sqrt_decay
from repro.data import SyntheticLMStream, FederatedBatcher
from repro.data.partition import dirichlet_vocab_partition, lognormal_sizes, jensen_shannon
from repro.checkpoint import save_checkpoint, load_checkpoint, tree_to_bytes, tree_from_bytes
from repro.models import ModelConfig, init_params, count_params, param_pspecs, FSDP_TP
from repro.models.transformer import model_specs


# ---------------------------------------------------------------------------
# optimizers


def quad_loss(p, _=None):
    return jnp.sum((p["w"] - 3.0) ** 2)


@pytest.mark.parametrize("make_opt", [
    lambda: sgd(0.1),
    lambda: momentum(0.05, 0.9),
    lambda: adam(0.5),
    lambda: adamw(0.5, weight_decay=0.0),
])
def test_optimizers_minimize_quadratic(make_opt):
    opt = make_opt()
    p = {"w": jnp.zeros(4)}
    o = opt.init(p)
    for step in range(200):
        g = jax.grad(quad_loss)(p)
        p, o = opt.update(g, o, p, jnp.int32(step))
    np.testing.assert_allclose(np.asarray(p["w"]), 3.0, atol=0.05)


def test_adamw_weight_decay_shrinks():
    opt = adamw(0.1, weight_decay=0.5)
    p = {"w": jnp.ones(4) * 10.0}
    o = opt.init(p)
    zero_g = {"w": jnp.zeros(4)}
    for step in range(50):
        p, o = opt.update(zero_g, o, p, jnp.int32(step))
    assert float(jnp.abs(p["w"]).max()) < 10.0


def test_clip_by_global_norm():
    g = {"a": jnp.ones(100) * 10.0}
    clipped, norm = clip_by_global_norm(g, 1.0)
    total = jnp.sqrt(sum(jnp.sum(x ** 2) for x in jax.tree_util.tree_leaves(clipped)))
    assert float(total) == pytest.approx(1.0, rel=1e-5)
    assert float(norm) == pytest.approx(100.0, rel=1e-5)


def test_inverse_sqrt_decay():
    lr = inverse_sqrt_decay(0.1)
    assert float(lr(jnp.int32(1))) == pytest.approx(0.1)
    assert float(lr(jnp.int32(100))) == pytest.approx(0.01)


# ---------------------------------------------------------------------------
# data pipeline


def test_stream_is_deterministic_and_non_iid():
    s = SyntheticLMStream(vocab_size=128, seq_len=16, n_silos=4, alpha=0.1, seed=1)
    a = s.sample(0, 8, 0)
    b = s.sample(0, 8, 0)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    # different silos see different distributions
    js = jensen_shannon(
        np.bincount(s.sample(0, 64, 1)["tokens"].ravel(), minlength=128) + 1e-9,
        np.bincount(s.sample(1, 64, 1)["tokens"].ravel(), minlength=128) + 1e-9,
    )
    assert js > 0.05


def test_labels_are_next_tokens():
    s = SyntheticLMStream(vocab_size=64, seq_len=10, n_silos=1)
    b = s.sample(0, 4, 0)
    assert b["tokens"].shape == (4, 10)
    assert b["labels"].shape == (4, 10)


def test_federated_batcher_shapes():
    s = SyntheticLMStream(vocab_size=64, seq_len=8, n_silos=3)
    fb = FederatedBatcher(s, local_steps=2, batch_per_silo=4)
    b = fb.batch(0)
    assert b["tokens"].shape == (3, 2, 4, 8)


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 30), st.integers(100, 10_000))
def test_lognormal_sizes_property(n, total):
    sizes = lognormal_sizes(n, total)
    assert len(sizes) == n
    assert (sizes >= 1).all()


def test_dirichlet_partition_rows_are_distributions():
    p = dirichlet_vocab_partition(5, 100, alpha=0.5)
    np.testing.assert_allclose(p.sum(1), 1.0, atol=1e-9)
    assert (p >= 0).all()


# ---------------------------------------------------------------------------
# checkpointing


def test_checkpoint_roundtrip():
    cfg = ModelConfig("t", "dense", 2, 64, 2, 2, 128, 256)
    params = init_params(jax.random.PRNGKey(0), model_specs(cfg))
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ckpt.msgpack")
        save_checkpoint(path, params, step=7)
        like = init_params(jax.random.PRNGKey(1), model_specs(cfg))
        restored = load_checkpoint(path, like)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_shape_mismatch_raises():
    cfg = ModelConfig("t", "dense", 2, 64, 2, 2, 128, 256)
    cfg2 = ModelConfig("t", "dense", 2, 64, 2, 2, 256, 256)
    params = init_params(jax.random.PRNGKey(0), model_specs(cfg))
    blob = tree_to_bytes(params)
    like = init_params(jax.random.PRNGKey(0), model_specs(cfg2))
    with pytest.raises(ValueError):
        tree_from_bytes(blob, like)


# ---------------------------------------------------------------------------
# param spec system


def test_param_pspecs_structure_matches_params():
    cfg = ModelConfig("t", "dense", 2, 128, 4, 2, 256, 512)
    specs = model_specs(cfg)
    params = init_params(jax.random.PRNGKey(0), specs)
    pspecs = param_pspecs(specs, FSDP_TP)
    jax.tree_util.tree_map(lambda a, b: None, params, pspecs)  # same structure
    # no duplicate mesh axes within one spec
    from jax.sharding import PartitionSpec as P

    for spec in jax.tree_util.tree_leaves(pspecs, is_leaf=lambda x: isinstance(x, P)):
        axes = [a for a in spec if a is not None]
        assert len(axes) == len(set(axes))


def test_padded_vocab_round():
    cfg = ModelConfig("t", "audio", 2, 128, 4, 4, 256, 51866)
    assert cfg.padded_vocab_size % 128 == 0
    assert cfg.padded_vocab_size >= cfg.vocab_size
    specs = model_specs(cfg)
    assert specs["embed"].shape[0] == cfg.padded_vocab_size
