"""Per-layer metric readers, one module per metric of ``BENCHMARK.json``,
found by the metric's name.  Each has ``read(run) -> float | None``: None
where the run holds nothing to read (a site or annotation that is gone),
and the harness then leaves the metric out of the result line."""
