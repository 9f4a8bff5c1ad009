import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""§Perf hillclimb — the paper's technique on TPU: DPASGD gossip schedule
comparison with 16 silos on one pod (mode A: silo axis = "data", each
silo a 16-chip TP group).

    PYTHONPATH=src python -m repro.launch.perf_gossip
"""

import json
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.fed import DPASGDConfig, make_train_step
from repro.fed.topology_runtime import plan_for_n_silos
from repro.launch import input_specs as IS
from repro.launch.mesh import make_production_mesh
from repro.launch.hlo_analysis import collective_bytes, _COLLECTIVES
from repro.models import SILO_TP, transformer as T
from repro.models.act_sharding import activation_sharding
from repro.models.params import param_pspecs
from repro.optim import adamw

ARCH = "internlm2-1.8b"
N_SILOS = 16


def run_one(gossip_kind: str, gossip_impl: str = "ppermute"):
    t0 = time.time()
    mesh = make_production_mesh()
    cfg = get_config(ARCH, n_silos=N_SILOS, flash_vjp=True)
    accum = 1  # per-silo batch 16 = one microstep of 16 seqs (1/device-col)
    batch = IS.train_input_specs(cfg, "train_4k", accum_steps=accum)
    # mode A layout: [n_silos, s, B, S] with silos over "data"
    batch_ps = {k: P("data", *([None] * (len(v.shape) - 1)))
                for k, v in batch.items()}
    params_abs = IS.abstract_model_params(cfg, jnp.bfloat16)
    params_ps = param_pspecs(T.model_specs(cfg), SILO_TP, silo_leading=True)
    opt = adamw(1e-4)
    plan = plan_for_n_silos(gossip_kind, N_SILOS)
    fed = DPASGDConfig(local_steps=1, gossip_impl=gossip_impl,
                       silo_axis="data", accum_steps=accum)
    from repro.fed import make_train_step as mts

    step_fn = mts(cfg, fed, opt, plan, mesh)
    opt_abs = jax.eval_shape(jax.vmap(opt.init), params_abs)
    opt_ps = {"mu": params_ps, "nu": params_ps}
    state_abs = {"params": params_abs, "opt_state": opt_abs,
                 "step": jax.ShapeDtypeStruct((), jnp.int32)}
    state_ps = {"params": params_ps, "opt_state": opt_ps, "step": P()}
    with jax.set_mesh(mesh), activation_sharding(None):
        compiled = jax.jit(
            step_fn,
            in_shardings=(IS.named(state_ps, mesh), IS.named(batch_ps, mesh)),
            out_shardings=(IS.named(state_ps, mesh), None),
        ).lower(state_abs, batch).compile()
    cb = collective_bytes(compiled.as_text())
    total = sum(v for k, v in cb.items() if k != "collective-count")
    ma = compiled.memory_analysis()
    peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes) / 2 ** 30
    print(f"{gossip_kind:>6s}/{gossip_impl:8s} transfers={plan.num_transfers:2d} "
          f"coll_total={total/2**30:7.3f} GiB/dev "
          f"cp={cb['collective-permute']/2**30:7.3f} "
          f"ag={cb['all-gather']/2**30:6.3f} ar={cb['all-reduce']/2**30:6.3f} "
          f"peak={peak:6.2f} GiB compile={time.time()-t0:.0f}s", flush=True)
    return {"kind": gossip_kind, "impl": gossip_impl, "coll": cb,
            "total": total, "peak_gib": peak}


def main():
    results = [run_one(k) for k in ("ring", "chain", "star")]
    results.append(run_one("ring", "einsum"))
    out = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "perf_gossip.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=2)
    ring, chain, star = results[0], results[1], results[2]
    print(f"\nring vs star gossip traffic ratio: "
          f"{star['total'] / max(ring['total'], 1):.2f}x")


if __name__ == "__main__":
    sys.exit(main())
