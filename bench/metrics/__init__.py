"""Per-layer metric readers: ``bench/metrics/<metric>.py`` for each per_layer
entry of BENCHMARK.json, found by the metric's name.

A reader is a module with ``read(ctx) -> float | None``.  ``ctx`` is the
traced run's `bench.harness.Context`: the trace reduction, the set-up's
compile seconds, the model, the traffic, the traced calls and their wall
time, the device kind and chip count.  A reader that finds nothing to read
returns None, and the harness leaves the metric out of the result line.
"""
