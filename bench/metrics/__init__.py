"""One reader per metric, found by the metric's name in BENCHMARK.json.

Each module has ``read(ctx) -> float | None``.  ``ctx`` carries the
window (``window_s``, ``rounds``, ``setup_s``), the window call's
``telemetry``, analytic ``wire_hint`` and ``round_scopes`` (op -> round
phase, from ``train.main``'s summary), ``memory_peak_bytes`` (the
fullest of the cell's chips), the configuration (``model``), the solver
and mix numbers (``train``), the device's ``peaks`` row and, in a
traced run, the ``capture``'s path and its reduction (``reduction``,
``bench/trace_reduce.py``).

Every traced reading is one chip's, and every reader counts chips the
same way: the chips that ran anything in the traced window
(``reduction.chips``; ``bench/spans.py`` counts alike and averages over
them itself).  A device-time reading is the seconds summed over those
chips over their number, ``device_idle_pct`` averages their busy time,
and ``step_mfu_pct`` divides by their number times the bf16 peak.
A reader that finds nothing to read returns None and the metric is
left out of the result.
"""
