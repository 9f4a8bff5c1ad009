"""The plain DPASGD round: per silo, momentum SGD on the reference loss
(m = beta m + g, p = p - lr m), gradients taken one batch row at a time
and averaged; then each silo's parameters become sum_j A_ij p_j, where A
is the overlay's consensus matrix.  Every silo lives on its own device
and the exchange is an explicit copy between devices."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare, weights


def consensus_matrix(topology: str, n: int) -> np.ndarray:
    """A for the paper's overlays on ``n`` silos; silo i receives from
    the silos j with A[i, j] > 0."""
    if topology == "none" or n == 1:
        return np.eye(n)
    if topology == "ring":  # directed ring: i receives from i - 1
        return 0.5 * np.eye(n) + 0.5 * np.roll(np.eye(n), -1, axis=1)
    raise KeyError(f"no consensus matrix for topology {topology!r}")


def _mix(A, ps, devices):
    """sum_j A_ij p_j for every silo i, leaf by leaf."""
    n = len(ps)
    out = [dict() for _ in range(n)]
    for leaf in ps[0]:
        for i in range(n):
            terms = [float(A[i, j]) * jax.device_put(ps[j][leaf], devices[i])
                     for j in range(n) if A[i, j] != 0]
            out[i][leaf] = functools.reduce(jnp.add, terms)
    return out


def run(model, cfg, traffic, seed, batches, devices, rounds=3):
    """Follow the program through its first ``rounds`` rounds.

    ``batches`` are the host batches the program was fed, one per round,
    laid out as the program takes them: ``[silos, local steps, B, S]``
    (no silo axis for one silo).  Returns ``losses`` (per round, mean over
    silos and local steps), ``grad1`` (per-leaf momentum norms after the
    first round, ``[silos]``), ``change3`` (per-leaf norms of the
    parameters' change over all rounds, ``[silos]``) and ``avg_change3``
    (the same for the silos' average, ``[1]``)."""
    n = traffic["silos"]
    lr, beta = traffic["lr"], traffic["momentum"]
    layout = model.layout(cfg)
    A = consensus_matrix(traffic["topology"], n)
    gen = jax.jit(lambda key: weights.generate(key, layout))
    stack = lambda d, i: d if n == 1 else d[i]  # noqa: E731

    with jax.default_matmul_precision("highest"):
        @functools.partial(jax.jit, donate_argnums=1, static_argnums=4)
        def add_row_grad(p, m, tokens, labels, rows):
            loss, g = jax.value_and_grad(model.loss)(p, cfg, tokens[None], labels[None])
            return jax.tree_util.tree_map(lambda a, b: a + b / rows, m, g), loss

        @functools.partial(jax.jit, donate_argnums=0)
        def decay(m):
            return jax.tree_util.tree_map(lambda a: beta * a, m)

        @functools.partial(jax.jit, donate_argnums=0)
        def descend(p, m):
            return jax.tree_util.tree_map(lambda a, b: a - lr * b, p, m)

        ps = [gen(jax.device_put(weights.silo_key(seed, i), devices[i])) for i in range(n)]
        ms = [jax.tree_util.tree_map(jnp.zeros_like, p) for p in ps]
        losses, grad1 = [], None
        for r in range(rounds):
            tok, lab = batches[r]["tokens"], batches[r]["labels"]
            round_loss = []
            for step in range(stack(tok, 0).shape[0]):
                rows = stack(tok, 0).shape[1]
                ms = [decay(m) for m in ms]
                acc = [[] for _ in range(n)]
                for row in range(rows):
                    for i in range(n):
                        t = jax.device_put(stack(tok, i)[step, row], devices[i])
                        y = jax.device_put(stack(lab, i)[step, row], devices[i])
                        ms[i], loss = add_row_grad(ps[i], ms[i], t, y, rows)
                        acc[i].append(loss)
                ps = [descend(p, m) for p, m in zip(ps, ms)]
                round_loss += [np.mean(jax.device_get(a)) for a in acc]
            losses.append(float(np.mean(round_loss)))
            if r == 0:
                grad1 = [jax.device_get(compare.leaf_norms(m, False)) for m in ms]
            if n > 1:
                ps = _mix(A, ps, devices)
        del ms
        change, total = [], {}
        for i in range(n):
            p0 = gen(jax.device_put(weights.silo_key(seed, i), devices[i]))
            d = {k: ps[i][k] - p0[k] for k in p0}
            del p0
            change.append(jax.device_get({k: compare.change_norm(v, 0.0, False)
                                          for k, v in d.items()}))
            for k, v in d.items():  # sum of the silos' changes, on the first device
                v = jax.device_put(v, devices[0])
                total[k] = v if i == 0 else total[k] + v
            del d
        avg = jax.device_get({k: compare.change_norm(v / n, 0.0, False)
                              for k, v in total.items()})
    per_silo = lambda rows: {k: np.concatenate([r[k] for r in rows]) for k in rows[0]}  # noqa: E731
    return {"losses": losses, "grad1": per_silo(grad1), "change3": per_silo(change),
            "avg_change3": avg}
