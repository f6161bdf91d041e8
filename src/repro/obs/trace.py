"""Span layer: named spans on the profiler's clock, and a JSONL writer.

Every span and instant enters a ``jax.profiler.TraceAnnotation`` of the
same name, its arguments (e.g. ``first_round``) as annotation metadata,
so the spans land in any ``jax.profiler`` capture on the device trace's
clock: ``--trace-profile``, or a capture an outside caller (the
benchmark's traced window) takes around the run.  With no capture
active a span costs one TraceMe check.

``ANNOTATIONS`` is the default tracer: annotations only, no file.
``Tracer`` (``--trace PATH``) also appends one JSON event per line
(after a leading ``[``), which is simultaneously a valid unterminated
Chrome trace — load it directly in ``chrome://tracing`` or Perfetto —
and line-parseable by ``python -m repro.obs.summary out.json``;
``profile_dir`` optionally attaches the ``jax.profiler`` device trace
over the same run.

Also home of the shared ``timeit`` microbenchmark helper (compile once,
average ``iters`` timed calls) used by ``benchmarks/common.py`` and
``benchmarks/kernels_bench.py``.
"""
from __future__ import annotations

import contextlib
import json
import os
import time

import jax


def timeit(fn, *args, iters=5):
    """us per call of ``fn(*args)``: one untimed compile/warm-up call,
    then the mean wall time of ``iters`` back-to-back calls with one
    trailing ``block_until_ready``."""
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


class AnnotationTracer:
    """Spans as ``jax.profiler.TraceAnnotation``s only: writes no file.
    The default when no ``--trace`` is given, so call sites never
    branch."""

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """Annotation named ``name`` around the with-block."""
        with jax.profiler.TraceAnnotation(name, **args):
            yield self

    def instant(self, name: str, **args) -> None:
        with jax.profiler.TraceAnnotation(name, **args):
            pass

    def close(self) -> None:
        pass


ANNOTATIONS = AnnotationTracer()


class Tracer(AnnotationTracer):
    """Annotations plus a Chrome-trace JSONL file (one event per line,
    flushed eagerly so a crashed run still leaves a loadable trace)."""

    def __init__(self, path: str, profile_dir: str | None = None):
        self.path = path
        self._t0 = time.perf_counter()
        self._f = open(path, "w")
        self._f.write("[\n")
        self._f.flush()
        self._profiling = False
        if profile_dir:
            os.makedirs(profile_dir, exist_ok=True)
            jax.profiler.start_trace(profile_dir)
            self._profiling = True

    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def _event(self, ev: dict) -> None:
        if self._f.closed:
            return
        self._f.write(json.dumps(ev) + ",\n")
        self._f.flush()

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """Annotation plus a complete-event ("ph": "X") line around the
        with-block."""
        ts = self._now_us()
        try:
            with super().span(name, **args):
                yield self
        finally:
            self._event({
                "name": name, "ph": "X", "pid": 0, "tid": 0,
                "ts": round(ts, 1),
                "dur": round(self._now_us() - ts, 1),
                "args": args,
            })

    def instant(self, name: str, **args) -> None:
        super().instant(name, **args)
        self._event({
            "name": name, "ph": "i", "s": "g", "pid": 0, "tid": 0,
            "ts": round(self._now_us(), 1), "args": args,
        })

    def close(self) -> None:
        if self._profiling:
            jax.profiler.stop_trace()
            self._profiling = False
        if not self._f.closed:
            self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def load_events(path: str) -> list[dict]:
    """Parse a Tracer JSONL file back into a list of event dicts
    (tolerates the leading ``[``, trailing commas, and truncation)."""
    events = []
    with open(path) as f:
        for line in f:
            line = line.strip().rstrip(",")
            if not line or line in "[]":
                continue
            try:
                events.append(json.loads(line))
            except json.JSONDecodeError:
                continue  # torn tail line of a crashed run
    return events
