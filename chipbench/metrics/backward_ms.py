"""Model step (``fed/dpasgd.py``, ``models/``): device self time per round
and chip of the backward pass, the operations under
``transpose(jvp(forward))`` (recomputed ones included, and the fusions
of a weight gradient with its update; ``chipbench/scopes.py``), mean
over the chips, in milliseconds."""

from chipbench import scopes


def read(facts):
    return scopes.read_ms(facts, "backward")
