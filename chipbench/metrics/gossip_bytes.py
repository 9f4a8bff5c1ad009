"""Gossip (``fed/gossip.py``): collective bytes per round and chip, from
the compiled step's optimized HLO (``chipbench/hlo_bytes.py``)."""


def read(facts):
    total = sum(v for k, v in facts.collective_bytes.items() if k != "collective-count")
    return float(total) if total else None
