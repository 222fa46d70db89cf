"""Per-layer metric readers, one module per metric, found by the name
``BENCHMARK.json`` gives it. Each has ``read(ctx) -> float | None``;
``ctx`` is `chipbench.run.TraceContext`. A reader that finds nothing to
read returns None, and the metric is left out of the result line."""
