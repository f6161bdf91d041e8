"""Reduce a ``jax.profiler`` capture to the program's own spans and round
phases.

The program marks its work in two ways that reach the capture:

* host spans: ``jax.profiler.TraceAnnotation``s from
  ``src/repro/obs/trace.py``, named ``train.chunk``, ``train.eval``,
  ``train.watchdog``, ``train.log`` and ``train.checkpoint`` by
  ``launch/train.py``'s loop.  They sit on the host plane, on the
  device trace's clock;
* round phases: the ``jax.named_scope``s ``ltadmm.local``,
  ``ltadmm.compress``, ``ltadmm.exchange`` and ``ltadmm.update`` of
  ``core/admm.py``.  XLA keeps them in each op's ``op_name`` metadata,
  which the capture stores as the op's ``tf_op`` stat (the same map
  ``train.main`` returns as ``round_scopes``, parsed from the compiled
  text).

``reduce(path, window_s)`` reads each chip that ran anything
(``chips``, the count ``trace_reduce`` gives as ``Reduction.chips``)
and gives one chip's numbers: every time below is summed over those
chips and divided by their number.

* ``module_s``: device seconds of the round program (``jit_run_chunk``),
  and ``module_idle_s`` the stretches inside it in which no op ran;
* ``phase_s``: device seconds of the round program's ops per phase,
  and ``unscoped_s`` of those under no phase; control-flow containers
  (a scan's ``while``) are left out, as their bodies' ops count, and
  ``control_s`` is the time in which only a container ran (the loops'
  own bookkeeping).  The phases, ``unscoped_s``, ``control_s`` and
  ``module_idle_s`` add up to ``module_s`` when no two ops of a chip
  overlap;
* ``idle_s``: the window less the union of the op intervals;
* ``span_idle_s``: the idle time inside the spans of each name, and
  ``unattributed_idle_s`` the idle time inside no ``train.*`` span.
  Idle is time the chip ran nothing, not a span's length;
* ``span_rows`` (each span's name, ``first_round``, wall time and idle
  time) and ``module_runs_s`` (each run of the round program, chip by
  chip, as measured), for a split round by round.

A capture of a program without the spans or scopes reads empty
``span_idle_s`` or ``phase_s``, and the readers return None.  The
readers find the capture at ``ctx.capture``, where ``bench/run.py``
profiled the window.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import re

from bench import trace_reduce

MODULE = "jit_run_chunk"
SPAN_PREFIX = "train."
# a phase is the first ``ltadmm.<name>`` component of an op's op_name
SCOPE_RE = re.compile(r"(?<![\w.])ltadmm\.\w+")


@dataclasses.dataclass
class Spans:
    window_s: float
    module_s: float
    module_idle_s: float
    phase_s: dict
    unscoped_s: float
    control_s: float
    idle_s: float
    span_idle_s: dict
    unattributed_idle_s: float
    span_rows: list  # [name, first_round, wall s, idle s] per span
    module_runs_s: list  # device seconds of each round-program run
    chips: int


# ---- op metadata: the capture's protobuf, read directly --------------------
# ProfileData exposes event stats, not the per-op metadata stats that
# hold ``tf_op``; these are the few fields of tsl's xplane.proto needed.
XSPACE_PLANES = 1
XPLANE_NAME, XPLANE_EVENT_METADATA, XPLANE_STAT_METADATA = 2, 4, 5
MAP_VALUE = 2
XEVENT_METADATA_NAME, XEVENT_METADATA_STATS = 2, 5
XSTAT_METADATA_ID, XSTAT_METADATA_NAME = 1, 2
XSTAT_METADATA, XSTAT_STR, XSTAT_REF = 1, 5, 7


def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of a protobuf message: an int for a
    varint or fixed field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield field, value


def _first(buf, field):
    return next((v for f, v in _fields(buf) if f == field), None)


def op_scopes(path) -> dict:
    """Op name (``fusion.12``) -> its ``ltadmm.*`` scope, from the
    ``tf_op`` metadata of the capture's device planes."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        name = _first(plane, XPLANE_NAME) if field == XSPACE_PLANES else None
        if name is None or not bytes(name).decode().startswith(
                trace_reduce.DEVICE_PREFIX):
            continue
        stat_names, events = {}, []
        for f, entry in _fields(plane):
            if f == XPLANE_STAT_METADATA:
                meta = _first(entry, MAP_VALUE)
                stat_names[_first(meta, XSTAT_METADATA_ID) or 0] = bytes(
                    _first(meta, XSTAT_METADATA_NAME) or b"").decode()
            elif f == XPLANE_EVENT_METADATA:
                events.append(_first(entry, MAP_VALUE))
        for meta in events:
            ev_name = bytes(_first(meta, XEVENT_METADATA_NAME) or b"")
            for f, stat in _fields(meta):
                if f != XEVENT_METADATA_STATS:
                    continue
                values = dict(_fields(stat))
                if stat_names.get(values.get(XSTAT_METADATA, 0)) != "tf_op":
                    continue
                text = (bytes(values[XSTAT_STR]).decode()
                        if XSTAT_STR in values
                        else stat_names.get(values.get(XSTAT_REF), ""))
                scope = SCOPE_RE.search(text)
                if scope:
                    out[trace_reduce.op_name(ev_name.decode())] = scope[0]
    return out


# ---- the reduction ----------------------------------------------------------


class Idle:
    """The stretches of ``[0, end_ns]`` in which no op ran, and the idle
    time inside any interval."""

    def __init__(self, busy, end_ns):
        self.gaps, edge = [], 0.0
        for s, e in trace_reduce._merged(busy) + [[end_ns, end_ns]]:
            if s > edge:
                self.gaps.append((edge, min(s, end_ns)))
            edge = max(edge, e)
            if edge >= end_ns:
                break
        self.starts = [s for s, _ in self.gaps]
        self.cum = [0.0]
        for s, e in self.gaps:
            self.cum.append(self.cum[-1] + e - s)

    @property
    def total_ns(self) -> float:
        return self.cum[-1]

    def _upto(self, t):
        k = bisect.bisect_right(self.starts, t)
        if k == 0:
            return 0.0
        s, e = self.gaps[k - 1]
        return self.cum[k - 1] + min(t, e) - s

    def between(self, a, b) -> float:
        """Idle nanoseconds inside ``[a, b]``."""
        return max(0.0, self._upto(b) - self._upto(a))


def _chip(ops, modules, scopes, spans, window_s):
    """One chip's numbers: ``Spans``'s fields but ``window_s`` and
    ``chips``, with ``idle_rows`` the idle seconds inside
    each of ``spans`` (sorted ``(start, end, ...)``)."""
    idle = Idle([(e.start_ns, e.end_ns) for e in ops], window_s * 1e9)
    rounds = sorted((e.start_ns, e.end_ns) for e in modules
                    if e.name.split("(")[0] == MODULE)
    starts = [s for s, _ in rounds]
    phase_s, unscoped, spanned, leaves = {}, 0.0, [], []
    for e in ops:
        k = bisect.bisect_right(starts, e.start_ns) - 1
        if k < 0 or e.start_ns >= rounds[k][1]:
            continue  # not an op of the round program
        spanned.append((e.start_ns, e.end_ns))
        op = trace_reduce.op_name(e.name)
        if trace_reduce._base(op) in trace_reduce.CONTAINERS:
            continue
        leaves.append((e.start_ns, e.end_ns))
        scope = scopes.get(op)
        if scope is None:
            unscoped += e.duration_ns / 1e9
        else:
            phase_s[scope] = phase_s.get(scope, 0.0) + e.duration_ns / 1e9
    covered = sum(idle.between(s, e) for s, e in
                  trace_reduce._merged([sp[:2] for sp in spans]))
    return {
        "module_s": sum(e - s for s, e in rounds) / 1e9,
        "module_idle_s": sum(idle.between(s, e) for s, e in rounds) / 1e9,
        "phase_s": phase_s,
        "unscoped_s": unscoped,
        "control_s": (trace_reduce._union_s(spanned)
                      - trace_reduce._union_s(leaves)),
        "idle_s": idle.total_ns / 1e9,
        "unattributed_idle_s": (idle.total_ns - covered) / 1e9,
        "idle_rows": [idle.between(s, e) / 1e9 for s, e, *_ in spans],
        "module_runs_s": [(e - s) / 1e9 for s, e in rounds],
    }


def reduce(path, window_s: float) -> Spans:
    from jax.profiler import ProfileData

    scopes = op_scopes(path)
    data = ProfileData.from_file(str(path))
    spans, chips = [], []
    for plane in data.planes:
        if plane.name == trace_reduce.HOST_PLANE:
            spans += [(e.start_ns, e.end_ns, e.name,
                       dict(e.stats).get("first_round"))
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
            continue
        if not plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        ops = lines.get(trace_reduce.OPS_LINE, [])
        if ops:
            chips.append((ops, lines.get(trace_reduce.MODULES_LINE, [])))
    spans.sort()
    per_chip = [_chip(ops, modules, scopes, spans, window_s)
                for ops, modules in chips]
    n = max(len(per_chip), 1)

    def mean(key):
        return sum(c[key] for c in per_chip) / n

    phase_s = {}
    for c in per_chip:
        for scope, sec in c["phase_s"].items():
            phase_s[scope] = phase_s.get(scope, 0.0) + sec / n
    idle_rows = [sum(c["idle_rows"][k] for c in per_chip) / n
                 for k in range(len(spans))]
    span_idle = {}
    for (_, _, name, _), idle in zip(spans, idle_rows):
        span_idle[name] = span_idle.get(name, 0.0) + idle
    return Spans(
        window_s=window_s,
        module_s=mean("module_s"),
        module_idle_s=mean("module_idle_s"),
        phase_s=phase_s,
        unscoped_s=mean("unscoped_s"),
        control_s=mean("control_s"),
        idle_s=mean("idle_s"),
        span_idle_s=span_idle,
        unattributed_idle_s=mean("unattributed_idle_s"),
        span_rows=[[name, first_round, (e - s) / 1e9, idle]
                   for (s, e, name, first_round), idle
                   in zip(spans, idle_rows)],
        module_runs_s=[r for c in per_chip for r in c["module_runs_s"]],
        chips=len(per_chip),
    )


_reduce_once = functools.lru_cache(maxsize=1)(reduce)


def for_ctx(ctx):
    """The reduction of the traced window ``ctx`` describes, or None in
    an untraced run."""
    if ctx.capture is None:
        return None
    return _reduce_once(str(ctx.capture), ctx.window_s)


def phase_ms(ctx, *scopes):
    """Per-round, per-chip device ms of the round program's ops under
    ``scopes``, or None where the capture holds no phase."""
    spans = for_ctx(ctx)
    if spans is None or not spans.phase_s:
        return None
    return sum(spans.phase_s.get(s, 0.0) for s in scopes) / ctx.rounds * 1e3


def idle_ms(ctx, span=None):
    """Per-round, per-chip idle ms inside the ``span`` spans (inside no
    ``train.*`` span for None), or None where the capture holds no
    span."""
    spans = for_ctx(ctx)
    if spans is None or not spans.span_idle_s:
        return None
    sec = (spans.unattributed_idle_s if span is None
           else spans.span_idle_s.get(span, 0.0))
    return sec / ctx.rounds * 1e3
