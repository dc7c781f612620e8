"""One reducer per metric, found by the metric's name in BENCHMARK.json:
``<name>.py`` defines ``reduce(run) -> float | None`` over a
``bench.harness.Run``.  A reducer that finds nothing to read returns
None, and the metric is left out of the result line."""
