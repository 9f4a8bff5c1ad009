"""Model step (``fed/dpasgd.py``, ``optim/``): device self time per round
and chip of the operations under the program's ``optimizer`` scope
that XLA did not fuse into a weight-gradient matmul
(``chipbench/scopes.py``), mean over the chips, in milliseconds."""

from chipbench import scopes


def read(facts):
    return scopes.read_ms(facts, "optimizer")
