"""The plain references agree with the program's loss and gradients at
small sizes on the CPU, from the same weights and tokens."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_tiny import DENSE, XLSTM

from chipbench import harness, weights
from chipbench import reference as references
from chipbench.reference import rounds


def _as_file(cfg):
    """A configuration file for the program's ``ModelConfig``."""
    out = {"arch_id": cfg.arch_id.removesuffix("-smoke"), "hidden_size": cfg.d_model,
           "num_hidden_layers": cfg.n_layers, "layer_pattern": list(cfg.block_pattern),
           "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
           "rms_norm_eps": cfg.norm_eps, "tie_word_embeddings": cfg.tie_embeddings}
    if "attn" in cfg.block_pattern:
        out.update(reference="dense", num_attention_heads=cfg.n_heads,
                   num_key_value_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                   rope_theta=cfg.rope_theta)
    else:
        out.update(reference="xlstm", num_heads=cfg.n_heads,
                   mlstm_proj_factor=cfg.ssm.expand, mlstm_chunk=128)
    return out


def _reduced(arch):
    from repro.configs import get_config

    return _as_file(get_config(arch).reduced())


CASES = [
    pytest.param(DENSE, 16, id="dense-gqa"),
    pytest.param({**DENSE, "rope_theta": 1e6}, 16, id="dense-gqa-rope1e6"),
    pytest.param(_reduced("internlm2-1.8b"), 32, id="internlm2-reduced"),
    pytest.param(XLSTM, 64, id="xlstm-tiny"),
    pytest.param(_reduced("xlstm-350m"), 64, id="xlstm-reduced"),
]


@pytest.mark.parametrize("cfg,seq", CASES)
def test_reference_loss_and_gradients_match_the_program(cfg, seq):
    from repro.fed import init_state
    from repro.models import transformer as T
    from repro.optim import momentum

    program = harness.program_config(cfg, 1)
    assert program == dataclasses.replace(program, n_silos=1)
    model = references.model(cfg["reference"])
    flat = weights.generate(weights.silo_key(2 ** 31 + 7, 0), model.layout(cfg))
    shapes = jax.eval_shape(lambda k: init_state(program, momentum(0.1), k),
                            jax.random.PRNGKey(0))
    params = weights.unflatten_like(shapes["params"], flat)
    rng = np.random.default_rng(0)
    tok = jnp.asarray(rng.integers(0, cfg["vocab_size"], (2, seq)), jnp.int32)
    lab = jnp.asarray(rng.integers(0, cfg["vocab_size"], (2, seq)), jnp.int32)

    lp, gp = jax.value_and_grad(
        lambda p: T.loss_fn(p, program, {"tokens": tok, "labels": lab}))(params)
    lr, gr = jax.value_and_grad(lambda f: model.loss(f, cfg, tok, lab))(flat)
    assert abs(float(lp) - float(lr)) <= 1e-5 * abs(float(lr))
    gp = weights.flatten(gp)
    for k in gr:
        scale = float(jnp.max(jnp.abs(gr[k]))) + 1e-12
        assert float(jnp.max(jnp.abs(gp[k] - gr[k]))) <= 1e-4 * scale, k


@pytest.mark.parametrize("n", [1, 2, 4, 7])
def test_ring_consensus_matches_the_programs_plan(n):
    from repro.fed.topology_runtime import plan_for_n_silos

    A = rounds.consensus_matrix("ring", n)
    np.testing.assert_allclose(A.sum(axis=1), 1.0)
    if n > 1:
        np.testing.assert_array_equal(A, plan_for_n_silos("ring", n).matrix)


def test_weights_are_one_draw_per_leaf_and_seed():
    layout = references.model("dense").layout(DENSE)
    a = weights.generate(weights.silo_key(5, 0), layout)
    b = weights.generate(weights.silo_key(5, 0), {"lm_head": layout["lm_head"]})
    c = weights.generate(weights.silo_key(5 + 2 ** 32, 0), layout)
    np.testing.assert_array_equal(a["lm_head"], b["lm_head"])
    assert not np.array_equal(a["lm_head"], c["lm_head"])
    assert float(a["layers.0.ln1"].min()) == 1.0
