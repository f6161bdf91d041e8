"""Reduce a ``jax.profiler`` capture (``.xplane.pb``) to the numbers the
per-layer metrics read.

Device planes are the ``/device:TPU:<n>`` planes.  On each, the ``XLA
Ops`` line holds one event per operation run on the chip and the ``XLA
Modules`` line one event per execution of a compiled program.  The
host's ``python`` line (when the Python tracer ran) and main-thread
line hold what the host was doing while the chip sat idle.

``reduce(path, window_s)``:

* ``busy_s``: the union of the op intervals of each chip, averaged over
  the chips that ran anything;
* ``module_s``: device seconds per program, keyed by its name with the
  ``(id)`` suffix cut (``jit_run_chunk``);
* ``op_s``: device seconds per op (the HLO instruction's name, e.g.
  ``fusion.12``; an event's name is the instruction's whole text),
  summed over chips;
* ``kernel_s(name)``: device seconds of the ops named ``name`` with any
  ``.<n>`` suffix (a Pallas call shows as a custom call named after
  the jitted function that launches it, e.g. ``quantize_rows.28``);
* ``gaps``: idle stretches of the first busy chip inside the window,
  each named by the innermost host event (Python call or main-thread
  runtime annotation) that covers most of it.

Times in a plane are nanoseconds from the start of the capture, so the
window is ``[0, window_s]``.
"""
from __future__ import annotations

import dataclasses
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
# host lines whose events name what the host was doing: the Python
# tracer's line and the main thread's runtime annotations
HOST_LINES = ("python", "main")
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    module_s: dict
    op_s: dict
    gaps: list  # (label, seconds), longest first
    chips: int

    def kernel_s(self, name: str) -> float:
        return sum(sec for op, sec in self.op_s.items()
                   if _base(op) == name)


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    head = event_name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def _base(op: str) -> str:
    return re.sub(r"\.\d+$", "", op)


def _union_s(intervals) -> float:
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total / 1e9


def _merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _labels(host_events, gaps) -> list:
    """The label of each of ``gaps`` (``(g0, g1)``, sorted and disjoint):
    the innermost host event covering at least half of the gap, else the
    one that overlaps it most; of equals, the first in ``host_events``.
    One sweep over the events sorted by start."""
    order = sorted(range(len(host_events)), key=lambda k: host_events[k][1])
    nxt, live, out = 0, [], []
    for g0, g1 in gaps:
        while nxt < len(order) and host_events[order[nxt]][1] < g1:
            live.append(order[nxt])
            nxt += 1
        # an event that ends before this gap ends before every later one
        live = [k for k in live if host_events[k][2] > g0]
        best, best_key = "host", None
        span = max(g1 - g0, 1)
        for k in live:
            name, s, e = host_events[k]
            ov = min(e, g1) - max(s, g0)
            if ov <= 0:
                continue
            half = ov >= span / 2
            key = (half, -(e - s) if half else ov, -k)
            if best_key is None or key > best_key:
                best, best_key = name, key
        out.append(best)
    return out


def reduce(path, window_s: float, top: int = 10) -> Reduction:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    busy, module_s, op_s = [], {}, {}
    first_busy = None
    host_events = []
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                if line.name.startswith(HOST_LINES):
                    host_events += [(e.name, e.start_ns, e.end_ns)
                                    for e in line.events]
            continue
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        intervals = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for e in line.events:
                    intervals.append((e.start_ns, e.end_ns))
                    op = op_name(e.name)
                    op_s[op] = op_s.get(op, 0.0) + e.duration_ns / 1e9
            elif line.name == MODULES_LINE:
                for e in line.events:
                    base = re.sub(r"\(\d+\)$", "", e.name)
                    module_s[base] = module_s.get(base, 0.0) + e.duration_ns / 1e9
        if intervals:
            busy.append(_union_s(intervals))
            if first_busy is None:
                first_busy = intervals
    gaps = []
    if first_busy:
        edge = 0
        end_ns = window_s * 1e9
        for s, e in _merged(first_busy) + [[end_ns, end_ns]]:
            if s > edge:
                gaps.append((edge, s))
            edge = max(edge, e)
        gaps = [(label, (g1 - g0) / 1e9) for label, (g0, g1)
                in zip(_labels(host_events, gaps), gaps)]
        gaps.sort(key=lambda g: -g[1])
    return Reduction(
        window_s=window_s,
        busy_s=sum(busy) / len(busy) if busy else 0.0,
        module_s=module_s,
        op_s=op_s,
        gaps=gaps[:top],
        chips=len(busy),
    )


def top_ops(red: Reduction, top: int = 10):
    """``[[name, seconds], ...]`` of the ops that took most device time,
    control-flow containers (a scan's ``while``) left out."""
    ops = [(k, v) for k, v in red.op_s.items() if _base(k) not in CONTAINERS]
    return [[k, v] for k, v in sorted(ops, key=lambda kv: -kv[1])[:top]]
