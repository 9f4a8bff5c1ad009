"""Input layer (``data/pipeline.py``): mean host time per round of the
program's own ``input.batch`` spans (``FederatedBatcher.batch``), which
``repro.obs`` puts in the trace while enabled; ``None`` without them."""


def read(facts):
    spans = facts.trace.host_spans("input.batch")
    if not spans:
        return None
    return sum(e - s for s, e in spans) / len(spans) * 1e-6
