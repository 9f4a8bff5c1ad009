"""One run of one cell: build the system under test from the seed, check
its first rounds against the plain reference, time a window of rounds,
and reduce what was measured to the cell's metrics.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration file and traffic mix, ``limits/<cell>.json`` holds
the limits of the comparison, and each per-layer metric is the module
``metrics/<metric>.py``.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import importlib
import json
import shutil
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench import compare, flops, hlo_bytes, scopes, trace, weights
from chipbench import reference as references
from chipbench.reference import rounds as reference_rounds

CHECK_ROUNDS = 3     # rounds the reference follows
# rounds queued on the device ahead of the host: enough that a stall of
# the host of a few seconds (a one-chip machine shares its host's cores)
# does not idle the chip; the program's trainer reads its loss back only
# about ten times a run
IN_FLIGHT = 8


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: Dict[str, float]
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(root: Path, name: str) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == w["config"])
    cfg = json.loads((root / config["file"]).read_text())
    refused = reduced_refusals(cfg)
    if refused:
        raise ValueError(f"{config['file']}: " + "; ".join(refused))
    has = lambda m: name in m.get("workloads", [name])  # noqa: E731
    return Cell(
        name=name,
        chips=w["chips"],
        config=cfg,
        traffic=json.loads((root / "chipbench" / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=compare.load_limits(root, name),
        end_to_end=[m for m in bench["end_to_end"] if has(m)],
        per_layer=[m for m in bench["per_layer"] if has(m)],
    )


class CompileClock:
    """Counts JAX's backend compiles and persistent-cache hits (copied
    from the program's ``chip_smoke.CompileClock``)."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


# The keys ``reduced`` may cut: the depth, and one chip's share of a
# layer that the deployment divides over ``share`` chips: the
# vocabulary, and the routed experts under the names the published
# configs give their count.  Every other key, every width among them,
# is as published.
DEPTH_KEY = "num_hidden_layers"
SHARE_KEYS = ("vocab_size", "n_routed_experts", "num_local_experts", "num_experts")


def share_floor(key: str, published: int) -> int:
    """The least share of ``key`` a file keeps: an eighth of the
    vocabulary, 8 routed experts."""
    return -(-published // 8) if key == "vocab_size" else 8


def reduced_refusals(cfg: dict) -> List[str]:
    """Why the configuration file's ``reduced`` cuts are refused, one
    line per key; empty where all hold.  A cut changes its key from the
    published value and is the depth or a chip's share; a share states
    ``share``, the chips that share the layer, holds the published count
    over ``share`` (rounded up), and keeps the floor."""
    out = []
    for key, entry in cfg["reduced"].items():
        published, share = entry["published"], entry.get("share")
        if cfg.get(key) == published:
            out.append(f"{key}: {published} is the published value")
        elif key == DEPTH_KEY:
            continue
        elif key not in SHARE_KEYS:
            out.append(f"{key}: only the depth and a chip's share of the vocabulary or "
                       f"the routed experts are cut")
        elif not isinstance(share, int) or share < 2:
            out.append(f"{key}: a chip's share states 'share', the chips that share the layer")
        elif cfg[key] != -(-published // share):
            out.append(f"{key}: {cfg[key]} is not {published} over {share} chips")
        elif cfg[key] < share_floor(key, published):
            out.append(f"{key}: {cfg[key]} is under the floor {share_floor(key, published)}")
    return out


def share_fields(cfg: dict) -> List[Tuple[str, str]]:
    """``(key, program field)`` for each share of the routed experts the
    file's ``reduced`` lists: the field, dotted (``moe.<name>``), that
    holds the experts one chip computes, from the ``SHARE_FIELDS`` table
    (published key -> field) of the family's reference module.  The
    vocabulary's share is the program's ``vocab_size``, set with the
    other sizes."""
    keys = [k for k in cfg.get("reduced", {}) if k in SHARE_KEYS and k != "vocab_size"]
    table = getattr(references.model(cfg["reference"]), "SHARE_FIELDS", {}) if keys else {}
    missing = [k for k in keys if k not in table]
    if missing:
        raise ValueError(f"{missing}: reference/{cfg['reference']}.py names no program field "
                         f"that holds a chip's share (SHARE_FIELDS)")
    return [(k, table[k]) for k in keys]


def _with_field(obj, dotted: str, value):
    """``obj`` with the field ``dotted`` (``moe.n_shared``) set."""
    def put(o, names):
        if not (dataclasses.is_dataclass(o)
                and names[0] in {f.name for f in dataclasses.fields(o)}):
            raise ValueError(f"the program has no field {dotted!r}")
        v = put(getattr(o, names[0]), names[1:]) if names[1:] else value
        return dataclasses.replace(o, **{names[0]: v})

    return put(obj, dotted.split("."))


def program_config(cfg: dict, n_silos: int):
    """The program's ``ModelConfig`` for a configuration file: the
    registry entry of its ``arch_id`` with the file's sizes, and its
    shares of the routed experts in the fields ``share_fields`` names.
    A field that holds the published count is the router's width, and
    is refused: the share is the experts one chip computes."""
    from repro.configs import get_config

    base = get_config(cfg["arch_id"])
    pattern = list(cfg["layer_pattern"])
    n = cfg["num_hidden_layers"]
    sizes = dict(
        n_layers=n,
        block_pattern=tuple(pattern * (n // len(pattern))),
        d_model=cfg["hidden_size"],
        n_heads=cfg.get("num_attention_heads", cfg.get("num_heads")),
        n_kv_heads=cfg.get("num_key_value_heads", cfg.get("num_heads")),
        head_dim=cfg.get("head_dim", 0),
        d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"],
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        n_silos=n_silos,
    )
    if "rope_theta" in cfg:
        sizes["rope_theta"] = cfg["rope_theta"]
    if "mlstm_proj_factor" in cfg:
        sizes["ssm"] = dataclasses.replace(base.ssm, expand=cfg["mlstm_proj_factor"])
    out = dataclasses.replace(base, **sizes)
    for key, field in share_fields(cfg):
        new, published = _with_field(out, field, cfg[key]), cfg["reduced"][key]["published"]
        if functools.reduce(getattr, field.split("."), out) == published:
            raise ValueError(f"{key}: {field} holds the published {published}, the router's "
                             f"width; a share goes to the field of the experts one chip computes")
        out = new
    return out


def _annotate(on: bool):
    if on:
        return jax.profiler.TraceAnnotation
    return lambda name: contextlib.nullcontext()


class SystemUnderTest:
    """The DPASGD round as ``launch/train.py`` builds it (non-dynamic
    path): silo mesh, momentum, the overlay's gossip plan, the jitted
    donated step, and batches from the federated batcher through
    ``jnp.asarray``.  The weights are the benchmark's, drawn from the
    seed on the devices in one jitted call."""

    def __init__(self, cell: Cell, seed: int, devices, param_dtype=jnp.float32):
        import repro.fed as fed
        from repro.data import FederatedBatcher, SyntheticLMStream
        from repro.fed.topology_runtime import plan_for_n_silos
        from repro.launch.mesh import make_silo_mesh
        from repro.optim import momentum

        t = cell.traffic
        self.n = n = t["silos"]
        if len(devices) < n:
            raise RuntimeError(f"{cell.name} needs {n} devices, found {len(devices)}")
        self.cfg = program_config(cell.config, n)
        self.mesh = make_silo_mesh(n)
        opt = momentum(t["lr"], t["momentum"])
        plan = plan_for_n_silos(t["topology"], n) if n > 1 else None
        dp = fed.DPASGDConfig(local_steps=t["local_steps"],
                              gossip_impl=t["gossip_impl"] if n > 1 else "none",
                              silo_axis="data")
        step_fn = fed.make_train_step(self.cfg, dp, opt, plan, self.mesh)
        self.step = jax.jit(step_fn, donate_argnums=0)

        shapes = jax.eval_shape(lambda k: fed.init_state(self.cfg, opt, k),
                                jax.random.PRNGKey(0))
        layout = references.model(cell.config["reference"]).layout(cell.config)
        got = {k: tuple(v.shape[1:] if n > 1 else v.shape)
               for k, v in weights.flatten(shapes["params"]).items()}
        want = {k: tuple(s) for k, (s, _, _) in layout.items()}
        if got != want:
            raise RuntimeError(f"the program's parameters {sorted(set(got.items()) ^ set(want.items()))} "
                               "differ from the reference layout")
        self.layout = layout
        self.param_dtype = param_dtype
        self.shardings = jax.tree_util.tree_map(
            lambda x: NamedSharding(self.mesh, P(*(("data",) + (None,) * (x.ndim - 1)))
                                    if x.ndim else P()), shapes)

        def make_state(keys):
            gen = lambda k: weights.generate(k, layout, param_dtype)  # noqa: E731
            flat = gen(keys[0]) if n == 1 else jax.vmap(gen)(keys)
            params = weights.unflatten_like(shapes["params"], flat)
            opt_state = opt.init(params) if n == 1 else jax.vmap(opt.init)(params)
            return {"params": params, "opt_state": opt_state,
                    "step": jnp.zeros((), jnp.int32)}

        self.keys = jnp.stack([weights.silo_key(seed, i) for i in range(n)])
        self.state = jax.jit(make_state, out_shardings=self.shardings)(self.keys)
        self._start_leaf = {}
        stream = SyntheticLMStream(self.cfg.vocab_size, t["seq_len"], n_silos=n,
                                   alpha=t["dirichlet_alpha"], seed=seed)
        self.batcher = FederatedBatcher(stream, t["local_steps"], t["batch_per_silo"])

    def host_batch(self, i: int):
        return self.batcher.batch(i)

    def round(self, host_batch):
        self.state, out = self.step(self.state, device_batch(host_batch))
        return out["loss"]

    def flat(self, part: str):
        return weights.flatten(self.state[part])

    def start_leaf(self, path: str):
        """The leaf ``path`` of the starting parameters, drawn again and
        placed as the parameters are: one leaf at a time, so that the
        check adds one leaf to the device's peak, not a model."""
        shape, init, scale = self.layout[path]
        ndim = len(shape) + (self.n > 1)
        if ndim not in self._start_leaf:
            def draw(keys, fold, spec):
                one = lambda k: weights.draw(k, fold, *spec, self.param_dtype)  # noqa: E731
                return one(keys[0]) if self.n == 1 else jax.vmap(one)(keys)
            sharding = NamedSharding(self.mesh, P("data", *(None,) * (ndim - 1)))
            self._start_leaf[ndim] = jax.jit(draw, static_argnums=2, out_shardings=sharding)
        return self._start_leaf[ndim](self.keys, weights.leaf_fold(path), (shape, init, scale))


def device_batch(host_batch):
    """The batch handed to the step, as ``launch/train.py`` hands it."""
    return {k: jnp.asarray(v) for k, v in host_batch.items()}


def check_rounds(sut: SystemUnderTest):
    """Drive the first rounds through the window's own call and feed, and
    read what the comparison needs: each round's loss, the momentum's
    per-leaf norms after the first round, and the per-leaf change over
    all of them of each silo's parameters and of their average.  Returns
    (readings, host batches)."""
    stacked = sut.n > 1
    batches, losses = [], []
    for i in range(CHECK_ROUNDS):
        batches.append(sut.host_batch(i))
        losses.append(sut.round(batches[-1]))
        if i == 0:
            grad1 = compare.leaf_norms(sut.flat("opt_state"), stacked)
    now = sut.flat("params")
    change3, avg_change3 = {}, {}
    for k in now:
        start = sut.start_leaf(k)
        change3[k] = compare.change_norm(now[k], start, stacked)
        avg_change3[k] = compare.avg_change_norm(now[k], start, stacked)
        del start
    readings = jax.device_get({"losses": losses, "grad1": grad1, "change3": change3,
                               "avg_change3": avg_change3})
    readings["losses"] = [float(x) for x in readings["losses"]]
    return readings, batches


def timed_window(sut: SystemUnderTest, first: int, seconds: float, annotate: bool):
    """Rounds from ``first`` on until ``seconds`` have passed, with at
    most ``IN_FLIGHT`` rounds queued; ends when the last round's output
    is ready.  Returns (rounds, elapsed seconds, losses, the seconds
    from the window's start at which each round was dispatched, and at
    which each round read back before the final drain was found ready)."""
    span = _annotate(annotate)
    losses, queued, sent, ready = [], collections.deque(), [], []
    i = first
    t0 = time.perf_counter()
    while True:
        with span("input"):
            b = device_batch(sut.host_batch(i))
        with span("dispatch"):
            sut.state, out = sut.step(sut.state, b)
        sent.append(time.perf_counter() - t0)
        losses.append(out["loss"])
        queued.append(out["loss"])
        i += 1
        if len(queued) > IN_FLIGHT:
            with span("readback"):
                queued.popleft().block_until_ready()
            ready.append(time.perf_counter() - t0)
        if time.perf_counter() - t0 >= seconds:
            break
    with span("readback"):
        jax.block_until_ready((sut.state, out))
    elapsed = time.perf_counter() - t0
    return i - first, elapsed, losses, sent, ready


def _largest_step(stamps) -> str:
    """The longest of the intervals between consecutive ``stamps``."""
    if len(stamps) < 2:
        return "none"
    gaps = np.diff(stamps)
    k = int(np.argmax(gaps))
    return f"{gaps[k]:.3f} s (after #{k}; median {float(np.median(gaps)):.3f} s)"


def memory_peak(devices) -> int:
    """Peak bytes on the fullest device: in use plus reserved for
    program temporaries, which the in-use counter alone leaves out on
    the TPU."""
    peaks = []
    for d in devices:
        s = d.memory_stats() or {}
        peaks.append(int(s.get("peak_bytes_in_use", 0)) + int(s.get("peak_bytes_reserved", 0)))
    return max(peaks)


def _profile_options():
    """No Python call tracing (it would slow the host it measures) and
    no HLO protos: device operations and the harness's spans only."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    return options


def _metric(value: float, unit: str) -> dict:
    return {"value": value, "unit": unit}


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, devices,
             t_start: float, peak, log=print) -> dict:
    """One run; returns the result line's object (``checks`` last)."""
    clock = CompileClock()
    since = lambda: time.time() - t_start  # noqa: E731
    try:
        log(f"set-up: {since():.3f} s to the devices")
        sut = SystemUnderTest(cell, seed, devices)
        jax.block_until_ready(sut.state)
        log(f"set-up: {since():.3f} s to the state on the devices")
        mine, batches = check_rounds(sut)
        log(f"set-up: {since():.3f} s to the checked rounds")
        compiled = sut.step.lower(sut.state, device_batch(batches[0])).compile()
        mem = compiled.memory_analysis()
        log(f"compiled step: arguments {mem.argument_size_in_bytes} B, temps "
            f"{mem.temp_size_in_bytes} B, outputs {mem.output_size_in_bytes} B, "
            f"aliased {mem.alias_size_in_bytes} B")
        text = compiled.as_text()
        collectives = hlo_bytes.collective_bytes(text)
        log(f"compiled step collectives: {collectives}")
        names = scopes.op_names(text) if traced else {}
        del compiled, text
        compiles_setup, hits_setup = clock.compiles, clock.cache_hits
        log(f"set-up: {clock.compiles} compiles, {clock.seconds:.3f} s compiling, "
            f"{clock.cache_hits} persistent-cache hits")

        tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if traced else None
        setup_s = time.time() - t_start
        if traced:
            import repro.obs as obs

            # the program's spans land in the trace while repro.obs is on
            obs.enable(capture=False)
            jax.profiler.start_trace(tdir, profiler_options=_profile_options())
        try:
            with _annotate(traced)("window"):
                n_rounds, elapsed, losses, sent, ready = timed_window(
                    sut, CHECK_ROUNDS, seconds, traced)
        finally:
            if traced:
                jax.profiler.stop_trace()
                obs.disable()
        in_window = clock.compiles - compiles_setup
        log(f"window: {n_rounds} rounds in {elapsed:.6f} s, {in_window} compiles "
            f"({clock.cache_hits - hits_setup} cache hits) inside it")
        log(f"window: longest wait between dispatches {_largest_step(sent)}, "
            f"between rounds found ready {_largest_step(ready)}")
        log(f"memory_stats of {devices[0]}: {devices[0].memory_stats()}")
        peak_bytes = memory_peak(devices)
        losses = np.asarray(jax.device_get(losses))
        failed = int((~np.isfinite(losses)).sum())
        del sut  # the program's state goes before the reference runs
    finally:
        clock.close()

    ref = reference_rounds.run(references.model(cell.config["reference"]), cell.config,
                               cell.traffic, seed, batches, devices)
    found = compare.gaps(mine, ref)
    correct = compare.judge(found, cell.limits) and failed == 0 and in_window == 0

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": peak_bytes}
    if traced:
        tr = trace.load(tdir, [d.id for d in devices])
        shutil.rmtree(tdir, ignore_errors=True)
        facts = trace.Facts(trace=tr, rounds=n_rounds, chips=len(devices), peak=peak,
                            flops_per_round=tokens_per_round(cell) * flops.train_flops_per_token(
                                cell.config, cell.traffic["seq_len"]),
                            collective_bytes=collectives, op_names=names)
        _log_scopes(facts, log)
        metrics = {}
        for m in cell.per_layer:
            value = importlib.import_module(f"chipbench.metrics.{m['name']}").read(facts)
            if value is not None:
                metrics[m["name"]] = _metric(value, m["unit"])
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        breakdown = {"device_ops": tr.top_ops(10), "idle_gaps": tr.idle_gaps(10)}
    else:
        values = {
            "setup_s": setup_s,
            "tokens_per_s": n_rounds * tokens_per_round(cell) / elapsed,
            "peak_hbm_gib": peak_bytes / 2 ** 30,
        }
        metrics = {m["name"]: _metric(values[m["name"]], m["unit"]) for m in cell.end_to_end}
        breakdown = None

    checks = {k: {"value": found[k][0], "limit": cell.limits[k]} for k in compare.NUMBERS}
    checks["compiles_in_window"] = {"value": in_window, "limit": 0}
    checks["failed_rounds"] = {"value": failed, "limit": 0}
    for k in compare.NUMBERS:
        log(f"check {k}: {found[k][0]!r} (worst at {found[k][1]}), limit {cell.limits[k]!r}")
    log(f"check compiles_in_window: {in_window}, limit 0")
    log(f"check failed_rounds: {failed}, limit 0")
    log(f"correct: {correct}")
    result = {"correct": bool(correct), "attempted": n_rounds, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def _log_scopes(facts: trace.Facts, log) -> None:
    """The traced round by the program's scopes against busy time, the
    operations with most time outside the named scopes with their
    ``op_name``, and the dispatch lead."""
    tr, rounds = facts.trace, max(facts.rounds, 1)
    by_scope = scopes.device_ms(tr, facts.op_names, rounds)
    log(f"device ms per round by scope: {by_scope}; sum {sum(by_scope.values()):.6f}, "
        f"busy {tr.busy_s() / rounds * 1e3:.6f}")
    n_ops = sum(len(ops) for ops in tr.ops.values())
    other = [[n, t / rounds * 1e3, facts.op_names.get(n)] for n, t in tr.top_ops(n_ops)
             if scopes.scope_of(facts.op_names.get(n, "")) == "other"][:10]
    log(f"device ms per round outside the named scopes, top ops: {other}")
    log(f"dispatch lead: {tr.dispatch_lead_ms()} ms; step executions per device "
        f"{ {d: len(m) for d, m in tr.modules.items()} }, dispatches "
        f"{len(tr.host_spans('dispatch'))}")


def tokens_per_round(cell: Cell) -> int:
    """Tokens of all silos in one round."""
    t = cell.traffic
    return t["silos"] * t["local_steps"] * t["batch_per_silo"] * t["seq_len"]

