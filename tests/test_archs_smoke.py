"""Per-architecture smoke tests: a REDUCED variant of each assigned
architecture family (2 layers, d_model<=512, <=4 experts) runs one
forward pass and one train step on CPU; output shapes asserted, no NaNs.
The FULL configs are exercised only by the dry-run (ShapeDtypeStructs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import init_params, count_params
from repro.models import transformer as T
from repro.fed.dpasgd import local_sgd_steps, make_loss_fn
from repro.optim import sgd


def _extras(cfg, B):
    out = {}
    if cfg.is_encdec:
        out["enc_frames"] = jnp.ones((B, cfg.encoder.seq_len, 128), jnp.float32)
    if cfg.vision_prefix_len:
        out["vision_embeds"] = jnp.ones((B, cfg.vision_prefix_len, 1024), jnp.float32)
    return out


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_reduced_forward_shapes_no_nan(arch_id):
    cfg = get_config(arch_id).reduced()
    assert cfg.n_layers == 2 and cfg.d_model <= 512
    if cfg.moe:
        assert cfg.moe.n_experts <= 4
    key = jax.random.PRNGKey(0)
    params = init_params(key, T.model_specs(cfg))
    B, S = 2, 32
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    extras = _extras(cfg, B)
    logits, aux = T.forward(params, cfg, tokens, **extras)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all()), f"{arch_id}: NaN in logits"
    assert bool(jnp.isfinite(aux)), f"{arch_id}: NaN aux loss"


@pytest.mark.slow  # train acceptance over the whole zoo (~5 min of the
# tier-1 wall time): ci.sh --fast skips; the forward-shape smoke above
# still covers every arch in the fast lane
@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_reduced_train_step_decreases_loss(arch_id):
    cfg = get_config(arch_id).reduced()
    key = jax.random.PRNGKey(1)
    params = init_params(key, T.model_specs(cfg))
    opt = sgd(0.1)
    opt_state = opt.init(params)
    B, S = 2, 16
    tokens = jax.random.randint(key, (1, B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    batch.update({k: v[None] for k, v in _extras(cfg, B).items()})
    loss_fn = make_loss_fn(cfg)
    l0 = loss_fn(params, jax.tree_util.tree_map(lambda x: x[0], batch))
    p, o, s_, l1 = local_sgd_steps(loss_fn, opt, params, opt_state, batch,
                                   jnp.zeros((), jnp.int32))
    for _ in range(4):
        p, o, s_, l2 = local_sgd_steps(loss_fn, opt, p, o, batch, s_)
    assert bool(jnp.isfinite(l2))
    assert float(l2) < float(l0), f"{arch_id}: loss did not decrease"


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_reduced_decode_step(arch_id):
    cfg = get_config(arch_id).reduced()
    if cfg.vision_prefix_len:
        pytest.skip("VLM decode exercised via dry-run serve_step")
    key = jax.random.PRNGKey(2)
    params = init_params(key, T.model_specs(cfg))
    B = 2
    cache = T.init_cache(cfg, B, 64, jnp.float32)
    if cfg.is_encdec:
        enc_out = T.encode(params, cfg, _extras(cfg, B)["enc_frames"])
        xc = T.prefill_cross_cache(params, cfg, enc_out)
        for i, (xk, xv) in enumerate(xc):
            cache[i]["xk"] = xk
            cache[i]["xv"] = xv
    tok = jnp.zeros((B,), jnp.int32)
    for pos in range(3):
        logits, cache = T.decode_step(params, cfg, tok, cache, jnp.int32(pos))
        assert logits.shape == (B, cfg.vocab_size)
        assert bool(jnp.isfinite(logits).all())
        tok = logits.argmax(-1).astype(jnp.int32)


def test_full_config_dims_match_assignment():
    expect = {
        "h2o-danube-1.8b": (24, 2560, 32, 8, 6912, 32000),
        "xlstm-350m": (24, 1024, 4, 4, 0, 50304),
        "internvl2-76b": (80, 8192, 64, 8, 28672, 128256),
        "internlm2-1.8b": (24, 2048, 16, 8, 8192, 92544),
        "qwen3-moe-30b-a3b": (48, 2048, 32, 4, 768, 151936),
        "deepseek-v2-lite-16b": (27, 2048, 16, 16, 10944, 102400),
        "granite-20b": (52, 6144, 48, 1, 24576, 49152),
        "mistral-large-123b": (88, 12288, 96, 8, 28672, 32768),
        "whisper-large-v3": (32, 1280, 20, 20, 5120, 51866),
        "hymba-1.5b": (32, 1600, 25, 5, 5504, 32001),
    }
    for arch, (L, D, H, K, F, V) in expect.items():
        cfg = get_config(arch)
        assert cfg.n_layers == L and cfg.d_model == D, arch
        assert cfg.n_heads == H and cfg.n_kv_heads == K, arch
        assert cfg.vocab_size == V, arch
        if arch == "qwen3-moe-30b-a3b":
            assert cfg.moe.d_expert == 768 and cfg.moe.n_experts == 128
            assert cfg.moe.top_k == 8
        elif arch == "deepseek-v2-lite-16b":
            assert cfg.moe.n_experts == 64 and cfg.moe.top_k == 6
            assert cfg.mla.kv_lora_rank == 512
        else:
            assert cfg.d_ff == F, arch


def test_param_counts_in_family_range():
    """Total parameter counts should be near the advertised sizes."""
    import repro.models.transformer as TT

    targets = {
        "xlstm-350m": (0.2e9, 0.6e9),
        "internlm2-1.8b": (1.5e9, 2.3e9),
        "h2o-danube-1.8b": (1.4e9, 2.2e9),
        "hymba-1.5b": (1.0e9, 2.2e9),
        "granite-20b": (15e9, 25e9),
        "qwen3-moe-30b-a3b": (25e9, 36e9),
        "deepseek-v2-lite-16b": (12e9, 20e9),
        "mistral-large-123b": (100e9, 135e9),
        "whisper-large-v3": (1.2e9, 2.2e9),
        "internvl2-76b": (60e9, 85e9),
    }
    for arch, (lo, hi) in targets.items():
        cfg = get_config(arch)
        n = count_params(TT.model_specs(cfg))
        assert lo <= n <= hi, f"{arch}: {n / 1e9:.2f}B params out of [{lo/1e9},{hi/1e9}]"


@pytest.mark.parametrize("arch,period,cut", [
    ("internlm2-1.8b", 1, 4),    # all "attn"
    ("xlstm-350m", 6, 12),       # sLSTM at every 6th position
    ("hymba-1.5b", 16, 16),      # full attention every 16th layer
])
def test_depth_cut_keeps_whole_periods_and_published_widths(arch, period, cut):
    full = get_config(arch)
    assert full.layer_period == period
    cfg = get_config(arch, n_layers=cut)
    assert cfg.n_layers == cut
    assert cfg.block_pattern == full.block_pattern[:cut]
    assert [cfg.layer_uses_window(i) for i in range(cut)] == \
        [full.layer_uses_window(i) for i in range(cut)]
    widths = ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
              "vocab_size", "sliding_window", "ssm", "moe", "mla")
    assert all(getattr(cfg, w) == getattr(full, w) for w in widths)
    if period > 1:
        with pytest.raises(ValueError, match="layer period"):
            get_config(arch, n_layers=period + 1)
    with pytest.raises(ValueError, match="layer period"):
        get_config(arch, n_layers=full.n_layers + period)
