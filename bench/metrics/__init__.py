"""One reader per metric, found by the metric's name in BENCHMARK.json.

Each module has ``read(ctx) -> float | None``.  ``ctx`` carries the
window (``window_s``, ``rounds``, ``setup_s``), the window call's
``telemetry`` and analytic ``wire_hint``, ``memory_peak_bytes``, the
configuration (``model``), the solver and mix numbers (``train``), the
device's ``peaks`` row and, in a traced run, the trace ``reduction``
(``bench/trace_reduce.py``).
A reader that finds nothing to read returns None and the metric is
left out of the result.
"""
