"""Flash attention blocks on a TPU: forward and backward of the Pallas
kernel (``repro.kernels.flash_attention``) at the internlm2 cells'
shapes for candidate ``(block_q, block_kv)``, against the jnp
``chunked_attention`` that XLA differentiates, each with its gap to an
f32 reference at ``Precision.HIGHEST``.

    PYTHONPATH=src python -m benchmarks.attention_blocks [--batch 4 --seq 2048]

Refuses any platform but a TPU.  Prints one JSON line per candidate:
milliseconds per forward and per forward + backward (mean of
``--iters`` back-to-back calls), and the relative gaps (max |x - ref|
over max |ref|) of out, dq, dk and dv.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention_pallas
from repro.models.attention import chunked_attention, naive_attention

CANDIDATES = ((128, 128), (256, 256), (256, 512), (512, 256), (512, 512),
              (512, 1024), (1024, 512), (1024, 1024))


def _ms(fn, args, iters):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def _gaps(got, want):
    return [float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
            for a, b in zip(got, want)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("refusing to run off a TPU", file=sys.stderr)
        return 2
    cfg = get_config("internlm2-1.8b")
    B, S = args.batch, args.seq
    K, G, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, S, K, G, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, K, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, K, hd), jnp.float32)
    w = jax.random.normal(ks[3], (B, S, K, G, hd), jnp.float32)
    pos = jnp.arange(S, dtype=jnp.int32)

    def both(attn):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v) * w)
        return jax.jit(attn), jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))

    def outputs(attn):
        fwd, grad = both(attn)
        return [fwd(q, k, v), *grad(q, k, v)[1]]

    with jax.default_matmul_precision("highest"):
        want = outputs(lambda q, k, v: naive_attention(q, k, v, pos, pos,
                                                       causal=True, window=None))
    runs = {"chunked": lambda q, k, v: chunked_attention(q, k, v, pos, pos,
                                                         causal=True, window=None)}
    for bq, bkv in CANDIDATES:
        runs[f"pallas_{bq}x{bkv}"] = (
            lambda q, k, v, bq=bq, bkv=bkv: flash_attention_pallas(
                q, k, v, block_q=bq, block_kv=bkv, mxu_dtype=jnp.bfloat16))
    for name, attn in runs.items():
        fwd, grad = both(attn)
        print(json.dumps({
            "impl": name, "shape": [B, S, K, G, hd],
            "fwd_ms": _ms(fwd, (q, k, v), args.iters),
            "fwd_bwd_ms": _ms(grad, (q, k, v), args.iters),
            "gaps_out_dq_dk_dv": _gaps(outputs(attn), want),
            "device": jax.devices()[0].device_kind,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
