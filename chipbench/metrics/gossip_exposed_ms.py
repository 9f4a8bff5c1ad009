"""Gossip (``fed/gossip.py``): per round and per chip, the device time
in the gossip's own exchange, ``collective-permute`` operations, during
which no other operation ran on that chip, mean over the chips, in
milliseconds.  Other collectives, such as the all-reduce of the silos'
mean loss, are not the gossip and are left out."""

import re

GOSSIP = re.compile(r"collective-permute")


def read(facts):
    tr = facts.trace
    if not tr.has_collectives(GOSSIP) or facts.rounds == 0:
        return None
    exposed = sum(tr.exposed_collective_s(d, GOSSIP) for d in tr.ops) / len(tr.ops)
    return exposed / facts.rounds * 1e3
