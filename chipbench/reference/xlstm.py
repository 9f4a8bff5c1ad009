"""xLSTM stack (arXiv:2405.04517) as the program defines its mLSTM and
sLSTM blocks, written out plainly: the mLSTM in its parallel (quadratic)
form, the sLSTM as a step-by-step recurrence.

Block equations (x is the residual stream, pre-norm RMSNorm ``ln1``):

mLSTM: [u, z] = x W_up; q, k, v = u W_q, u W_k / sqrt(hd), u W_v per head;
  i~, f~ = u W_if + b_if; log i = log sigmoid(i~), log f = log sigmoid(f~);
  h_t = sum_{s<=t} (q_t . k_s) i_s prod_{s<r<=t} f_r v_s;
  out = (RMSNorm(h) * silu(z)) W_down.
sLSTM: per head, pre-activations x W + b plus the block-diagonal
  recurrence h_{t-1} R, gate order i, f, z, o; with stabiliser m:
  m_t = max(f~_t + m_{t-1}, i~_t), i = exp(i~ - m_t), f = exp(f~ + m_{t-1} - m_t),
  c_t = f c + i tanh(z~), n_t = f n + i, h_t = sigmoid(o~) c_t / max(n_t, 1);
  out = RMSNorm(h) W_down.

Departures from the paper, all the program's:
- mLSTM input gate is a sigmoid, not an exponential; there is no
  stabiliser state and no normaliser n_t (no division by
  max(|n_t . q_t|, 1)); an RMSNorm over the whole inner width stands in
  for the paper's per-head group norm.
- mLSTM block: no causal convolution before q and k, no learnable skip,
  dense (not block-diagonal) q/k/v projections.
- sLSTM block: no causal convolution, no gated MLP after the block, RMSNorm
  in place of group norm, and h divided by max(n, 1) rather than n.
- One sLSTM block in every 6 (5:1), where the 350M model is xLSTM[7:1];
  RMSNorm in place of LayerNorm before each block.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import lm_layout, lm_loss, rms_norm


def layout(cfg):
    D, H = cfg["hidden_size"], cfg["num_heads"]
    Di = cfg["mlstm_proj_factor"] * D
    dh = D // H

    def block(kind):
        if kind == "mlstm":
            return {
                "ln1": ((D,), "ones", 1.0),
                "mlstm.w_up": ((D, 2 * Di), "normal", D ** -0.5),
                "mlstm.w_q": ((Di, Di), "normal", Di ** -0.5),
                "mlstm.w_k": ((Di, Di), "normal", Di ** -0.5),
                "mlstm.w_v": ((Di, Di), "normal", Di ** -0.5),
                "mlstm.w_if": ((Di, 2 * H), "normal", Di ** -0.5),
                "mlstm.b_if": ((2 * H,), "zeros", 0.0),
                "mlstm.out_ln": ((Di,), "ones", 1.0),
                "mlstm.w_down": ((Di, D), "normal", Di ** -0.5),
            }
        if kind == "slstm":
            return {
                "ln1": ((D,), "ones", 1.0),
                "slstm.w": ((D, 4 * D), "normal", D ** -0.5),
                "slstm.r": ((H, dh, 4 * dh), "normal", dh ** -0.5),
                "slstm.b": ((4 * D,), "zeros", 0.0),
                "slstm.out_ln": ((D,), "ones", 1.0),
                "slstm.w_down": ((D, D), "normal", D ** -0.5),
            }
        raise KeyError(kind)

    return lm_layout(cfg, block)


def layer_flops(cfg, kind, S):
    """Forward FLOPs of one ``mlstm`` or ``slstm`` layer over a sequence
    of ``S``, as the program computes it: the mLSTM in chunks of
    ``mlstm_chunk``, every matmul from its shapes."""
    D, H = cfg["hidden_size"], cfg["num_heads"]
    if kind == "mlstm":
        Di = cfg["mlstm_proj_factor"] * D
        hdi = Di // H
        C = min(cfg["mlstm_chunk"], S)
        f = 2 * S * D * 2 * Di                     # up-projection, x and gate
        f += 3 * 2 * S * Di * Di                   # q, k, v
        f += H * (4 * S * C * hdi + 4 * S * hdi * hdi)  # chunked scan
        f += 2 * S * Di * D                        # down-projection
        return f
    if kind == "slstm":
        dh = D // H
        f = 2 * S * D * 4 * D + 8 * S * D * dh     # gate pre-activations, recurrence
        f += 2 * S * D * D                         # down-projection
        return f
    raise KeyError(f"no FLOP count for block kind {kind!r}")


def _segment_log_decay(log_f):
    """L[b, h, t, s] = sum_{s<r<=t} log_f[b, r, h] for s <= t.

    Summed in two levels (within blocks of 128 steps, then block totals)
    so that nearby positions do not lose digits to the difference of two
    long prefix sums."""
    B, S, H = log_f.shape
    C = 128 if S % 128 == 0 else S
    lf = log_f.reshape(B, S // C, C, H)
    local = jnp.cumsum(lf, axis=2).reshape(B, S, H)          # within block
    block_tot = jnp.cumsum(lf.sum(axis=2), axis=1)           # [B, n, H]
    offset = jnp.concatenate([jnp.zeros_like(block_tot[:, :1]), block_tot[:, :-1]], axis=1)
    blk = jnp.repeat(jnp.arange(S // C), C)
    # prefix(t) = offset[block(t)] + local[t]; L = prefix(t) - prefix(s),
    # with the block offsets subtracted first
    off = jnp.repeat(offset, C, axis=1)                      # [B, S, H]
    d_off = off[:, :, None, :] - off[:, None, :, :]          # [B, t, s, H]
    d_loc = local[:, :, None, :] - local[:, None, :, :]
    same = (blk[:, None] == blk[None, :])[None, :, :, None]
    L = jnp.where(same, d_loc, d_off + d_loc)
    return L.transpose(0, 3, 1, 2)                           # [B, H, t, s]


def mlstm(p, cfg, x):
    B, S, D = x.shape
    H = cfg["num_heads"]
    Di = cfg["mlstm_proj_factor"] * D
    hd = Di // H
    u, z = jnp.split(x @ p["mlstm.w_up"], 2, axis=-1)
    q = (u @ p["mlstm.w_q"]).reshape(B, S, H, hd)
    k = (u @ p["mlstm.w_k"]).reshape(B, S, H, hd) / jnp.sqrt(jnp.float32(hd))
    v = (u @ p["mlstm.w_v"]).reshape(B, S, H, hd)
    i_pre, f_pre = jnp.split(u @ p["mlstm.w_if"] + p["mlstm.b_if"], 2, axis=-1)
    log_i, log_f = jax.nn.log_sigmoid(i_pre), jax.nn.log_sigmoid(f_pre)  # [B,S,H]
    L = _segment_log_decay(log_f) + log_i.transpose(0, 2, 1)[:, :, None, :]
    causal = jnp.tril(jnp.ones((S, S), bool))[None, None]
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, L, 0.0)), 0.0)
    w = jnp.einsum("bthd,bshd->bhts", q, k) * decay
    h = jnp.einsum("bhts,bshd->bthd", w, v).reshape(B, S, Di)
    h = rms_norm(h, p["mlstm.out_ln"], cfg["rms_norm_eps"]) * jax.nn.silu(z)
    return h @ p["mlstm.w_down"]


def slstm(p, cfg, x):
    B, S, D = x.shape
    H = cfg["num_heads"]
    dh = D // H
    pre = (x @ p["slstm.w"] + p["slstm.b"]).reshape(B, S, 4, H, dh)

    def step(carry, pre_t):                    # pre_t [B, 4, H, dh]
        c, n, h, m = carry
        rec = jnp.einsum("bhd,hde->bhe", h, p["slstm.r"]).reshape(B, H, 4, dh)
        zi, zf, zz, zo = (pre_t[:, g] + rec[:, :, g] for g in range(4))
        m_new = jnp.maximum(zf + m, zi)
        i = jnp.exp(zi - m_new)
        f = jnp.exp(zf + m - m_new)
        c = f * c + i * jnp.tanh(zz)
        n = f * n + i
        h = jax.nn.sigmoid(zo) * c / jnp.maximum(n, 1.0)
        return (c, n, h, m_new), h

    zero = jnp.zeros((B, H, dh), jnp.float32)
    _, hs = jax.lax.scan(step, (zero, zero, zero, zero), pre.transpose(1, 0, 2, 3, 4))
    h = hs.transpose(1, 0, 2, 3).reshape(B, S, D)
    return rms_norm(h, p["slstm.out_ln"], cfg["rms_norm_eps"]) @ p["slstm.w_down"]


def _block(cfg):
    eps = cfg["rms_norm_eps"]

    def block(p, kind, x):
        h = rms_norm(x, p["ln1"], eps)
        return x + (mlstm(p, cfg, h) if kind == "mlstm" else slstm(p, cfg, h))

    return block


def loss(params, cfg, tokens, labels):
    """Mean next-token cross-entropy of tokens/labels [B, S]."""
    return lm_loss(params, cfg, tokens, labels, _block(cfg))
