"""Model configuration covering the 10 assigned architecture families.

A single ``ModelConfig`` describes dense GQA/SWA transformers, MoE
(top-k routed + shared experts), MLA (DeepSeek multi-head latent
attention), xLSTM stacks (mLSTM/sLSTM), Hymba-style hybrid
attention+mamba blocks, Whisper encoder-decoder, and VLM backbones with a
stubbed vision frontend.  ``block_pattern`` selects the per-layer block
type; everything else is dimensionality.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Literal, Optional, Sequence, Tuple

BlockKind = Literal[
    "attn",       # attention + MLP (dense)
    "attn_moe",   # attention + MoE FFN
    "mla_moe",    # MLA attention + MoE FFN (deepseek)
    "mla",        # MLA attention + dense MLP
    "mlstm",      # xLSTM mLSTM block (internal up-proj, no separate FFN)
    "slstm",      # xLSTM sLSTM block
    "hymba",      # parallel attention + mamba heads, + MLP
]

Family = Literal["dense", "moe", "ssm", "hybrid", "vlm", "audio"]


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert: int            # hidden size of each routed expert
    n_shared: int = 0        # shared (always-on) experts
    d_shared: int = 0        # hidden size of the shared expert MLP
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01


@dataclass(frozen=True)
class MLAConfig:
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    q_lora_rank: int = 0     # 0 = no query compression (deepseek-v2-lite)


@dataclass(frozen=True)
class SSMConfig:
    d_state: int = 16        # mamba state size / mLSTM key dim factor
    expand: int = 2          # inner expansion
    d_conv: int = 4          # depthwise conv width (mamba)
    n_ssm_heads: int = 0     # hymba: number of mamba heads in parallel


@dataclass(frozen=True)
class EncoderConfig:
    """Whisper-style encoder (conv frontend stubbed: inputs are frame
    embeddings) or VLM vision prefix (patch embeddings)."""

    n_layers: int = 0
    seq_len: int = 1500      # encoder frames (whisper-large-v3: 1500)
    is_causal: bool = False


@dataclass(frozen=True)
class ModelConfig:
    arch_id: str
    family: Family
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                      # 0 -> d_model // n_heads
    block_pattern: Tuple[str, ...] = ()    # len == n_layers; default "attn"
    sliding_window: Optional[int] = None   # SWA window (danube/hymba)
    global_attn_every: int = 0             # hymba: every k-th layer full attn
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    encoder: Optional[EncoderConfig] = None
    vision_prefix_len: int = 0             # VLM: stub patch embeddings
    mlp_variant: Literal["swiglu", "gelu"] = "swiglu"
    tie_embeddings: bool = False
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    # federated / distribution knobs
    n_silos: int = 1
    # Pallas mLSTM scan (models/ssm.py) on TPU; attention picks its
    # kernel from the backend and shapes (attention.attention_impl)
    use_flash_kernel: bool = False
    remat: bool = True
    # Fully unroll inner attention chunk scans so that XLA's
    # cost_analysis counts every block (it counts a while body once).
    analysis_unroll: bool = False
    # §Perf: banded sliding-window attention (touch only the visible KV
    # band per query block -> O(S*window) instead of O(S^2) masked work).
    banded_swa: bool = False
    # §Perf: flash-style custom VJP — backward recomputes probability
    # blocks instead of storing them (dominant train-memory term).
    flash_vjp: bool = False

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if not self.block_pattern:
            kind: str
            if self.moe is not None and self.mla is not None:
                kind = "mla_moe"
            elif self.moe is not None:
                kind = "attn_moe"
            elif self.mla is not None:
                kind = "mla"
            else:
                kind = "attn"
            object.__setattr__(self, "block_pattern", (kind,) * self.n_layers)
        if len(self.block_pattern) != self.n_layers:
            raise ValueError("block_pattern length must equal n_layers")
        if self.n_heads % max(self.n_kv_heads, 1) != 0:
            raise ValueError("n_heads must be divisible by n_kv_heads")

    @property
    def padded_vocab_size(self) -> int:
        """Vocab padded to a multiple of 128 so the embedding / LM head can
        shard over the model axis (whisper: 51866->51968, hymba:
        32001->32128).  Logits are sliced back to ``vocab_size``."""
        return ((self.vocab_size + 127) // 128) * 128

    @property
    def is_encdec(self) -> bool:
        return self.encoder is not None and self.encoder.n_layers > 0

    @property
    def is_subquadratic(self) -> bool:
        """True if decode memory is bounded (SWA / recurrent)."""
        kinds = set(self.block_pattern)
        if kinds <= {"mlstm", "slstm"}:
            return True
        if "hymba" in kinds:
            return self.sliding_window is not None
        return self.sliding_window is not None and not self.is_encdec

    def layer_uses_window(self, layer: int) -> bool:
        if self.sliding_window is None:
            return False
        if self.global_attn_every and (layer % self.global_attn_every == 0):
            return False
        return True

    @property
    def layer_period(self) -> int:
        """Smallest p such that every layer i has the same block kind and
        attention span (windowed or global) as layer ``i % p``."""
        kinds = [(b, self.layer_uses_window(i))
                 for i, b in enumerate(self.block_pattern)]
        return next(p for p in range(1, self.n_layers + 1)
                    if all(k == kinds[i % p] for i, k in enumerate(kinds)))

    def with_depth(self, n_layers: int) -> "ModelConfig":
        """The first ``n_layers`` layers, a whole number of layer-pattern
        periods, with every width as published (a one-chip depth cut)."""
        p = self.layer_period
        if not (0 < n_layers <= self.n_layers and n_layers % p == 0):
            raise ValueError(
                f"{self.arch_id}: depth cut to {n_layers} layers must be a "
                f"multiple of its layer period {p}, at most {self.n_layers}")
        return dataclasses.replace(
            self, n_layers=n_layers, block_pattern=self.block_pattern[:n_layers])

    def reduced(self, *, n_layers: int = 2, d_model: int = 256) -> "ModelConfig":
        """A tiny same-family variant for CPU smoke tests."""
        scale = d_model / self.d_model
        n_heads = max(2, min(self.n_heads, d_model // 64))
        n_kv = max(1, min(self.n_kv_heads, n_heads))
        while n_heads % n_kv:
            n_kv -= 1
        moe = None
        if self.moe is not None:
            n_exp = min(4, self.moe.n_experts)
            moe = dataclasses.replace(
                self.moe,
                n_experts=n_exp,
                top_k=min(2, self.moe.top_k),
                d_expert=max(32, int(self.moe.d_expert * scale)),
                n_shared=min(1, self.moe.n_shared),
                d_shared=max(32, int(self.moe.d_shared * scale)) if self.moe.n_shared else 0,
                # dropless at smoke scale: capacity >= any possible expert
                # load, so prefill/decode/forward agree exactly
                capacity_factor=float(n_exp),
            )
        mla = None
        if self.mla is not None:
            mla = MLAConfig(kv_lora_rank=64, qk_nope_dim=32, qk_rope_dim=16,
                            v_head_dim=32, q_lora_rank=0)
        enc = None
        if self.encoder is not None:
            enc = dataclasses.replace(self.encoder, n_layers=min(2, self.encoder.n_layers),
                                      seq_len=min(64, self.encoder.seq_len))
        pattern = self.block_pattern[: n_layers]
        # keep family diversity: make sure at least one of each kind survives
        kinds = tuple(dict.fromkeys(self.block_pattern))
        if len(kinds) > 1 and n_layers >= len(kinds):
            pattern = kinds + pattern[len(kinds):]
            pattern = pattern[:n_layers]
        return dataclasses.replace(
            self,
            arch_id=self.arch_id + "-smoke",
            n_layers=n_layers,
            d_model=d_model,
            n_heads=n_heads,
            n_kv_heads=n_kv,
            head_dim=0,
            d_ff=max(64, int(self.d_ff * scale)) if self.d_ff else 0,
            vocab_size=min(512, self.vocab_size),
            block_pattern=pattern,
            sliding_window=min(self.sliding_window, 32) if self.sliding_window else None,
            moe=moe,
            mla=mla,
            encoder=enc,
            vision_prefix_len=min(8, self.vision_prefix_len),
            use_flash_kernel=False,
        )
