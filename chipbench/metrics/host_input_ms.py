"""Input layer (``data/pipeline.py``): mean host time per round making
the batch (``FederatedBatcher.batch``) and handing it to the device
(``jnp.asarray``), from the harness's ``input`` spans."""


def read(facts):
    spans = facts.trace.host_spans("input")
    if not spans:
        return None
    return sum(e - s for s, e in spans) / len(spans) * 1e-6
