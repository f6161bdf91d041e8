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
    assert trace_reduce._label(host, 42, 58) == "observe"
    assert trace_reduce._label(host, 70, 90) == "main"
    assert trace_reduce._label([], 0, 5) == "host"


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
