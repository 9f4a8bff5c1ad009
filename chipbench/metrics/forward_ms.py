"""Model step (``fed/dpasgd.py``, ``models/``): device self time per round
and chip of the operations under the program's ``forward`` scope
(``jvp(forward)`` in their HLO ``op_name``; ``chipbench/scopes.py``),
mean over the chips, in milliseconds."""

from chipbench import scopes


def read(facts):
    return scopes.read_ms(facts, "forward")
