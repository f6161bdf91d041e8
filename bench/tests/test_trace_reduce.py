"""The trace reduction on a small recorded trace and on hand-made
intervals."""
from pathlib import Path

import pytest

from bench import trace_reduce

TRACE = Path(__file__).resolve().parent / "data" / "small.xplane.pb"
# the recorded capture: qwen3 smoke size on one v5e, two chunks of two
# rounds of train.main, profiled as bench/run.py profiles its window
# (bench/tools/record_trace.py; window length as it printed).  The source
# paths in its HLO metadata were rewritten to a neutral prefix of the
# same length, which leaves the protobuf intact.
TRACE_WINDOW_S = 0.420262


def test_union_and_merge_of_intervals():
    iv = [(0, 10), (5, 20), (30, 40), (35, 38), (50, 50)]
    assert trace_reduce._union_s(iv) == 30 / 1e9
    assert trace_reduce._merged(iv) == [[0, 20], [30, 40], [50, 50]]


def test_gap_label_prefers_innermost_covering_call():
    host = [("main", 0, 100), ("observe", 40, 60), ("print", 45, 46)]
    assert trace_reduce._labels(host, [(42, 58), (70, 90)]) == [
        "observe", "main"]
    assert trace_reduce._labels([], [(0, 5)]) == ["host"]
    # of two equal candidates the first listed wins; an event of no
    # length inside a gap overlaps nothing
    twins = [("b", 10, 20), ("a", 10, 20), ("dot", 30, 30)]
    assert trace_reduce._labels(twins, [(12, 18), (25, 35)]) == [
        "b", "host"]


def _label_by_scan(host_events, g0, g1):
    """The labelling as one scan of every host event per gap: the
    reference the sweep is held to."""
    best, best_key = "host", None
    span = max(g1 - g0, 1)
    for name, s, e in host_events:
        ov = min(e, g1) - max(s, g0)
        if ov <= 0:
            continue
        key = (ov >= span / 2, -(e - s) if ov >= span / 2 else ov)
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best


@pytest.mark.parametrize("capture", sorted(TRACE.parent.glob("*.xplane.pb")),
                         ids=lambda p: p.name)
def test_gap_labels_sweep_equals_scan_on_recorded_captures(capture):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(capture))
    host = [(e.name, e.start_ns, e.end_ns) for p in data.planes
            if p.name == trace_reduce.HOST_PLANE for line in p.lines
            if line.name.startswith(trace_reduce.HOST_LINES)
            for e in line.events]
    device = [p for p in data.planes
              if p.name.startswith(trace_reduce.DEVICE_PREFIX)]
    ops = [(e.start_ns, e.end_ns) for line in device[0].lines
           if line.name == trace_reduce.OPS_LINE for e in line.events]
    merged = trace_reduce._merged(ops)
    # the idle stretches between ops, and a tiling of the whole capture
    # into stretches of every length
    gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:]) if b[0] > a[1]]
    step = max(e for _, _, e in host) / 997
    tiles = [(k * step, (k + 1) * step) for k in range(997)]
    for stretch in (gaps, tiles):
        assert len(stretch) > 50
        assert trace_reduce._labels(host, stretch) == [
            _label_by_scan(host, g0, g1) for g0, g1 in stretch]


@pytest.fixture(scope="module")
def red():
    if TRACE_WINDOW_S is None:
        pytest.skip("no recorded trace")
    return trace_reduce.reduce(TRACE, TRACE_WINDOW_S)


def test_recorded_trace_reduces(red):
    assert red.chips == 1
    assert 0 < red.busy_s <= red.window_s
    assert red.module_s.get("jit_run_chunk", 0) > 0
    assert red.module_s["jit_run_chunk"] <= red.busy_s * 1.001
    assert red.kernel_s("quantize_rows") > 0
    assert red.gaps and all(sec > 0 for _, sec in red.gaps)
    ops = trace_reduce.top_ops(red)
    assert len(ops) == 10 and ops[0][1] >= ops[-1][1]
    assert not any(name.startswith("while") for name, _ in ops)


def test_recorded_trace_numbers(red):
    # pinned from this capture: a later change to the reduction that
    # moves them has to say why
    assert red.busy_s == pytest.approx(0.004238263, rel=1e-9)
    assert red.module_s["jit_run_chunk"] == pytest.approx(0.003553546, rel=1e-9)
    assert red.kernel_s("quantize_rows") == pytest.approx(0.000217402, rel=1e-9)
    # the chip idles while train.main lowers its evaluation again at
    # every log point
    assert red.gaps[0][0] == "lower_sharding_computation"
