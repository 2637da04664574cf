"""Per-layer metric readers, one module per metric, found by the metric's
name in BENCHMARK.json.  Each has ``read(run) -> float | None``, where
``run`` is the harness's :class:`bench.run_cell.Run`; ``None`` means the
run holds nothing to read, and the metric is left out of the line."""
