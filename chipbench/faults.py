"""Faults planted under the timed path, to show that the comparison
catches them.  Each wraps the program's ``make_train_step`` (patched in
for a run, see ``planted``), so the harness drives the broken step
through its own call and feed."""

from __future__ import annotations

import contextlib
import dataclasses


def unchanged(make):
    """A step that returns its state unchanged."""
    def build(*a, **kw):
        step = make(*a, **kw)
        return lambda state, batch, *r: (state, step(state, batch, *r)[1])
    return build


def half_batch(make):
    """Half of each silo's batch left out: the step sees the first half
    of the rows and takes its mean over those."""
    def build(*a, **kw):
        step = make(*a, **kw)

        def broken(state, batch, *r):
            half = {k: v[..., : v.shape[-2] // 2, :] for k, v in batch.items()}
            return step(state, half, *r)
        return broken
    return build


def no_exchange(make):
    """The exchange between chips left out: no gossip mix."""
    def build(cfg, fed, *a, **kw):
        return make(cfg, dataclasses.replace(fed, gossip_impl="none"), *a, **kw)
    return build


def loss_altered(make):
    """The reported loss altered where it is produced (1% high)."""
    def build(*a, **kw):
        step = make(*a, **kw)

        def broken(state, batch, *r):
            state, out = step(state, batch, *r)
            return state, {**out, "loss": out["loss"] * 1.01}
        return broken
    return build


FAULTS = {f.__name__: f for f in (unchanged, half_batch, no_exchange, loss_altered)}


@contextlib.contextmanager
def planted(name: str):
    """Patch the fault ``name`` into ``repro.fed.make_train_step``."""
    import repro.fed as fed

    real = fed.make_train_step
    fed.make_train_step = FAULTS[name](real)
    try:
        yield
    finally:
        fed.make_train_step = real
