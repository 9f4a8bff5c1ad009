"""Per-layer metrics, one module each, named as in ``BENCHMARK.json``.
Each has ``read(facts) -> float | None`` (``chipbench.trace.Facts``);
``None`` means the trace holds nothing for it, and the metric is left
out of the result line."""
