"""Observability: in-trace telemetry counters + wall-clock span tracing.

Two layers, importable without touching the hot path:

* ``obs.telemetry`` — a typed counter pytree that rides the scanned
  round's carry (no host syncs, no trajectory changes); solvers opt in
  via ``with_telemetry(solver)``.
* ``obs.trace`` — named spans, always ``jax.profiler`` annotations on
  the device trace's clock, with ``Tracer`` also writing them as
  Chrome-trace/Perfetto JSONL; plus the shared ``timeit``
  microbenchmark helper.  ``python -m repro.obs.summary out.json``
  prints a per-phase report.
"""
from repro.obs.telemetry import (  # noqa: F401
    Telemetry,
    TelemetryState,
    counters,
    with_telemetry,
)
from repro.obs.trace import Tracer, timeit  # noqa: F401
