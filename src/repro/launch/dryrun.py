import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (architecture x input-shape x mesh)
combination lowers and compiles on the production mesh, and extract the
roofline terms from the compiled artifact.

MUST be run as a module entry point (never imported by tests — the
XLA_FLAGS line above forces 512 host devices before jax initializes):

    PYTHONPATH=src python -m repro.launch.dryrun --arch mistral-large-123b \
        --shape train_4k [--multi-pod] [--gossip ring]
    PYTHONPATH=src python -m repro.launch.dryrun --all
"""

import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config, shape_supported
from repro.launch.mesh import make_production_mesh, CHIPS_PER_POD
from repro.launch import input_specs as IS
from repro.launch.steps import build_train_step, build_prefill_step, build_decode_step
from repro.launch.hlo_analysis import (
    make_roofline,
    model_flops_estimate,
    collective_bytes,
)
from repro.launch.analytic_model import analytic_step_flops
from repro.models import count_params
from repro.models import transformer as T
from repro.models.act_sharding import activation_sharding
from repro.optim import adamw

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "experiments", "dryrun")


def active_param_count(cfg) -> float:
    """Parameters touched per token: full count minus routed experts not in
    the top-k (MoE 6*N_active*D convention)."""
    specs = T.model_specs(cfg)
    total = 0.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: hasattr(x, "shape") and hasattr(x, "axes")
    )[0]:
        keys = [getattr(p, "key", None) for p in path]
        n = float(np.prod(leaf.shape))
        if cfg.moe is not None and "moe" in keys and any(
            k in ("w_gate", "w_up", "w_down") for k in keys
        ):
            n *= cfg.moe.top_k / cfg.moe.n_experts
        total += n
    return total


def _mem_analysis(compiled) -> Dict[str, Any]:
    out = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            if hasattr(ma, k):
                out[k] = int(getattr(ma, k))
        out["peak_bytes_per_device"] = int(
            out.get("argument_size_in_bytes", 0)
            - out.get("alias_size_in_bytes", 0)
            + out.get("output_size_in_bytes", 0)
            + out.get("temp_size_in_bytes", 0)
        )
    except Exception as e:  # pragma: no cover
        out["error"] = str(e)
        out["peak_bytes_per_device"] = 0
    return out


def dryrun_one(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    gossip: str = "ring",
    local_steps: int = 1,
    save: bool = True,
    verbose: bool = True,
    config_overrides: Optional[Dict[str, Any]] = None,
    tag: str = "",
) -> Dict[str, Any]:
    t0 = time.time()
    spec = INPUT_SHAPES[shape_name]
    kind = spec["kind"]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    chips = int(np.prod(mesh.devices.shape))

    overrides = dict(config_overrides or {})
    n_silos = 2 if (multi_pod and kind == "train") else 1
    overrides.setdefault("n_silos", n_silos)
    # Unrolled attention scans make cost_analysis exact but (a) slow
    # compiles and (b) keep many live fp32 score buffers at 32k prefill.
    # Unroll only single-pod train shapes (small per-microbatch blocks);
    # prefill/decode/multi-pod rely on the analytic FLOP cross-check.
    # unroll inflates compile time ~linearly with layers; for the 52-88
    # layer giants rely on the analytic FLOP cross-check instead
    overrides.setdefault(
        "analysis_unroll",
        (not multi_pod) and kind == "train"
        and get_config(arch).n_layers <= 48)
    # NOTE: flash_vjp / banded_swa stay OFF here — the sweep records the
    # paper-faithful/naive BASELINE; §Perf runs opt in via overrides.
    overrides.setdefault("flash_vjp", False)
    cfg = get_config(arch, **overrides)
    if not shape_supported(cfg, shape_name):
        result = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                  "status": "skipped", "reason": "full attention: long_500k "
                  "requires sub-quadratic decode (DESIGN.md §4)"}
        if save:
            os.makedirs(RESULTS_DIR, exist_ok=True)
            fn = f"{arch}_{shape_name}_{mesh_name.replace('x','-')}.json"
            with open(os.path.join(RESULTS_DIR, fn), "w") as f:
                json.dump(result, f, indent=2)
        return result

    result: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "gossip": gossip if kind == "train" else None, "status": "?",
    }
    try:
        if kind == "train":
            per_silo_batch = spec["global_batch"] // max(cfg.n_silos, 1)
            accum = max(1, per_silo_batch // 16)
            batch = IS.train_input_specs(cfg, shape_name,
                                         local_steps=local_steps,
                                         accum_steps=accum)
            batch_ps = IS.train_batch_pspecs(cfg, batch, multi_pod=multi_pod,
                                             accum_steps=accum)
            params_abs = IS.abstract_model_params(cfg, jnp.bfloat16)
            params_ps = IS.model_param_pspecs(cfg, multi_pod_training=multi_pod)
            opt = adamw(1e-4)
            from repro.fed.topology_runtime import plan_for_n_silos

            plan = plan_for_n_silos(gossip, cfg.n_silos) if cfg.n_silos > 1 else None
            # grads constrained to the per-tensor param specs (without the
            # leading silo dim — the vmap adds it back)
            from repro.models import FSDP_TP
            from repro.models.params import param_pspecs as _pps

            grad_ps = _pps(T.model_specs(cfg), FSDP_TP)
            step_fn = build_train_step(
                cfg, optimizer=opt, gossip_impl="ppermute", silo_axis="pod",
                plan=plan, mesh=mesh, local_steps=local_steps,
                accum_steps=accum, grad_pspecs=grad_ps,
            )
            opt_abs = jax.eval_shape(
                opt.init if cfg.n_silos == 1 else jax.vmap(opt.init), params_abs)
            opt_ps = jax.tree_util.tree_map(
                lambda _: None, opt_abs) if not jax.tree_util.tree_leaves(opt_abs) else {
                "mu": params_ps, "nu": params_ps}
            state_abs = {"params": params_abs, "opt_state": opt_abs,
                         "step": jax.ShapeDtypeStruct((), jnp.int32)}
            state_ps = {"params": params_ps, "opt_state": opt_ps, "step": P()}
            state_sh = IS.named(state_ps, mesh)
            batch_sh = IS.named(batch_ps, mesh)
            with jax.set_mesh(mesh), activation_sharding(("data",)):
                lowered = jax.jit(
                    step_fn,
                    in_shardings=(state_sh, batch_sh),
                    out_shardings=(state_sh, None),
                ).lower(state_abs, batch)
                compiled = lowered.compile()
        elif kind == "prefill":
            batch = IS.serve_input_specs(cfg, shape_name)
            batch_ps = IS.serve_batch_pspecs(cfg, batch, mesh)
            params_abs = IS.abstract_model_params(cfg, jnp.bfloat16)
            params_ps = IS.model_param_pspecs(cfg)
            step_fn = build_prefill_step(cfg, max_len=spec["seq_len"])
            B = spec["global_batch"]
            batch_axes = (("pod", "data") if (multi_pod and B >= 32)
                          else ("data",) if B >= 16 else None)
            with jax.set_mesh(mesh), activation_sharding(batch_axes):
                lowered = jax.jit(
                    step_fn,
                    in_shardings=(IS.named(params_ps, mesh), IS.named(batch_ps, mesh)),
                ).lower(params_abs, batch)
                compiled = lowered.compile()
        else:  # decode
            batch = IS.serve_input_specs(cfg, shape_name)
            batch_ps = IS.serve_batch_pspecs(cfg, batch, mesh)
            params_abs = IS.abstract_model_params(cfg, jnp.bfloat16)
            params_ps = IS.model_param_pspecs(cfg)
            step_fn = build_decode_step(cfg)
            B = spec["global_batch"]
            batch_axes = (("pod", "data") if (multi_pod and B >= 32)
                          else ("data",) if B >= 16 else None)
            with jax.set_mesh(mesh), activation_sharding(batch_axes):
                lowered = jax.jit(
                    step_fn,
                    in_shardings=(IS.named(params_ps, mesh), IS.named(batch_ps, mesh)),
                    out_shardings=(None, IS.named(batch_ps["cache"], mesh)),
                ).lower(params_abs, batch)
                compiled = lowered.compile()

        cost = dict(compiled.cost_analysis() or {})
        mem = _mem_analysis(compiled)
        hlo = compiled.as_text()
        n_active = active_param_count(cfg)
        mf = model_flops_estimate(cfg, spec, n_active, kind)
        scale = (local_steps * accum) if kind == "train" else 1.0
        roof = make_roofline(
            arch=arch, shape=shape_name, mesh_name=mesh_name, chips=chips,
            cost=cost, hlo_text=hlo,
            peak_bytes_per_device=mem.get("peak_bytes_per_device", 0),
            model_flops=mf, cost_scale=scale,
            analytic_flops=analytic_step_flops(cfg, spec, kind),
        )
        result.update(
            status="ok",
            seconds=round(time.time() - t0, 1),
            memory=mem,
            roofline=json.loads(roof.to_json()),
            n_params=count_params(T.model_specs(cfg)),
            n_params_active=n_active,
        )
        if verbose:
            peak_gb = mem.get("peak_bytes_per_device", 0) / 2 ** 30
            print(f"[OK ] {arch:22s} {shape_name:12s} {mesh_name:8s} "
                  f"compile={result['seconds']:6.1f}s peak={peak_gb:6.2f}GiB/dev "
                  f"bottleneck={roof.bottleneck:10s} "
                  f"terms(ms) C={roof.compute_ms:.2f} M={roof.memory_ms:.2f} "
                  f"X={roof.collective_ms:.2f}")
    except Exception as e:
        result.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-2000:],
                      seconds=round(time.time() - t0, 1))
        if verbose:
            print(f"[ERR] {arch:22s} {shape_name:12s} {mesh_name:8s} {e}")
    if save:
        os.makedirs(RESULTS_DIR, exist_ok=True)
        suffix = ("_" + tag) if tag else ""
        fn = f"{arch}_{shape_name}_{mesh_name.replace('x','-')}{suffix}.json"
        with open(os.path.join(RESULTS_DIR, fn), "w") as f:
            json.dump(result, f, indent=2)
    return result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) on the single-pod mesh")
    ap.add_argument("--gossip", default="ring",
                    choices=["ring", "star", "chain", "none"])
    ap.add_argument("--local-steps", type=int, default=1)
    args = ap.parse_args()

    failures = 0
    if args.all:
        for arch in ARCH_IDS:
            for shape in INPUT_SHAPES:
                r = dryrun_one(arch, shape, multi_pod=args.multi_pod,
                               gossip=args.gossip, local_steps=args.local_steps)
                if r["status"] == "error":
                    failures += 1
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        r = dryrun_one(args.arch, args.shape, multi_pod=args.multi_pod,
                       gossip=args.gossip, local_steps=args.local_steps)
        if r["status"] == "error":
            print(r.get("traceback", ""))
            failures = 1
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
