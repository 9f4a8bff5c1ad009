"""Gossip (``fed/gossip.py``): device self time per round and chip of the
operations under the program's ``gossip`` scope, the mix arithmetic and
the permutes' own time (``chipbench/scopes.py``), mean over the chips,
in milliseconds."""

from chipbench import scopes


def read(facts):
    return scopes.read_ms(facts, "gossip")
