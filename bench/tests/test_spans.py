"""The span reduction on a small recorded window, its identities, and the
readers on a capture with and without the program's spans."""
import importlib
import json
import types
from pathlib import Path

import pytest

from bench import spans, trace_reduce

DATA = Path(__file__).resolve().parent / "data"
# qwen3 smoke size on one v5e, two chunks of two rounds of train.main with
# the watchdog on, profiled as bench/run.py profiles its window
# (bench/tools/record_spans.py, which also wrote the run's round_scopes)
CAPTURE = DATA / "spans.xplane.pb"
META = json.loads((DATA / "spans.json").read_text())
PHASES = ("ltadmm.local", "ltadmm.compress", "ltadmm.exchange",
          "ltadmm.update")
# test_trace_reduce.py's capture: a program with neither spans nor phases
PARENT_CAPTURE, PARENT_WINDOW_S = DATA / "small.xplane.pb", 0.420262


@pytest.fixture(scope="module")
def red():
    return spans.reduce(CAPTURE, META["window_s"])


def test_idle_between_hand_made_intervals():
    idle = spans.Idle([(10, 20), (15, 30), (50, 60)], 100)
    assert idle.gaps == [(0.0, 10), (30, 50), (60, 100)]
    assert idle.total_ns == 70
    assert idle.between(0, 100) == 70
    assert idle.between(5, 55) == 25
    assert idle.between(20, 30) == 0
    assert idle.between(95, 200) == 5


def test_phase_sums_and_unscoped_time_are_the_round_program(red):
    assert set(red.phase_s) == set(PHASES)
    assert all(red.phase_s[p] > 0 for p in PHASES)
    parts = (sum(red.phase_s.values()) + red.unscoped_s + red.control_s
             + red.module_idle_s)
    assert parts == pytest.approx(red.module_s, rel=1e-9)
    assert sum(red.module_runs_s) == pytest.approx(red.module_s, rel=1e-9)
    # the module is the one trace_reduce finds
    tr = trace_reduce.reduce(CAPTURE, META["window_s"])
    assert red.module_s == pytest.approx(tr.module_s["jit_run_chunk"],
                                         rel=1e-9)
    assert red.chips == tr.chips == 1


def test_span_idles_and_unattributed_idle_are_the_idle_time(red):
    assert set(red.span_idle_s) == {"train.chunk", "train.eval",
                                    "train.watchdog", "train.log"}
    total = sum(red.span_idle_s.values()) + red.unattributed_idle_s
    assert total == pytest.approx(red.idle_s, rel=1e-9)
    # device_idle_pct's idle: ops the capture holds past the host's
    # window end count as busy there, so the two differ by a hair
    busy_s = trace_reduce.reduce(CAPTURE, META["window_s"]).busy_s
    assert red.idle_s == pytest.approx(red.window_s - busy_s, rel=1e-3)
    rows = sum(idle for _, _, _, idle in red.span_rows)
    assert rows == pytest.approx(sum(red.span_idle_s.values()), rel=1e-9)
    assert all(0 <= idle <= wall for _, _, wall, idle in red.span_rows)


def test_recorded_numbers(red):
    # pinned from this capture: a later change to the reduction that
    # moves them has to say why
    assert red.module_s == pytest.approx(0.003557922, rel=1e-9)
    assert red.phase_s["ltadmm.local"] == pytest.approx(0.002481969, rel=1e-9)
    assert red.phase_s["ltadmm.compress"] == pytest.approx(0.000437565,
                                                           rel=1e-9)
    assert red.phase_s["ltadmm.exchange"] == pytest.approx(0.000055596,
                                                           rel=1e-9)
    assert red.phase_s["ltadmm.update"] == pytest.approx(0.000117769,
                                                         rel=1e-9)
    assert red.unscoped_s == pytest.approx(0.000381917, rel=1e-9)
    assert red.control_s == pytest.approx(0.000078926, rel=1e-9)
    assert red.span_idle_s["train.eval"] == pytest.approx(1.597214462,
                                                          rel=1e-9)
    assert red.span_idle_s["train.watchdog"] == pytest.approx(0.054897031,
                                                              rel=1e-9)
    assert red.unattributed_idle_s == pytest.approx(0.043383858, rel=1e-9)
    # both chunks' spans carry their first round
    assert {(name, r) for name, r, _, _ in red.span_rows} >= {
        ("train.eval", 2), ("train.eval", 4), ("train.watchdog", 2),
        ("train.watchdog", 4), ("train.chunk", 2), ("train.chunk", 4)}


def test_trace_scopes_agree_with_round_scopes():
    """The capture's op metadata and the scopes ``train.main`` parsed from
    the compiled text name the same phase for every op both know; the
    capture also knows the copies the TPU backend adds after the text."""
    from jax.profiler import ProfileData

    traced = spans.op_scopes(CAPTURE)
    ran = {trace_reduce.op_name(e.name)
           for p in ProfileData.from_file(str(CAPTURE)).planes
           if p.name.startswith(trace_reduce.DEVICE_PREFIX)
           for line in p.lines if line.name == trace_reduce.OPS_LINE
           for e in line.events}
    both = [op for op, scope in META["round_scopes"].items()
            if op in ran and trace_reduce._base(op) not in
            trace_reduce.CONTAINERS]
    assert len(both) > 500
    assert all(traced.get(op) == META["round_scopes"][op] for op in both)
    assert set(traced.values()) == set(PHASES)


def _ctx(capture, window_s, rounds):
    """The ``ctx`` bench/run.py hands a reader in a traced run."""
    spans._reduce_once.cache_clear()
    red = trace_reduce.reduce(capture, window_s)
    return types.SimpleNamespace(reduction=red, capture=capture,
                                 window_s=window_s, rounds=rounds)


def _read(name, ctx):
    return importlib.import_module(f"bench.metrics.{name}").read(ctx)


READERS = ("local_device_ms", "plane_device_ms", "exchange_device_ms",
           "eval_idle_ms", "watchdog_idle_ms", "unattributed_idle_ms")


def test_readers_on_the_recorded_window(red):
    ctx = _ctx(CAPTURE, META["window_s"], META["rounds"])
    per_round = {name: _read(name, ctx) for name in READERS}
    ms = 1e3 / META["rounds"]
    assert per_round["local_device_ms"] == pytest.approx(
        red.phase_s["ltadmm.local"] * ms)
    assert per_round["plane_device_ms"] == pytest.approx(
        (red.phase_s["ltadmm.compress"] + red.phase_s["ltadmm.update"]) * ms)
    assert per_round["exchange_device_ms"] == pytest.approx(
        red.phase_s["ltadmm.exchange"] * ms)
    assert per_round["eval_idle_ms"] == pytest.approx(
        red.span_idle_s["train.eval"] * ms)
    assert per_round["watchdog_idle_ms"] == pytest.approx(
        red.span_idle_s["train.watchdog"] * ms)
    assert per_round["unattributed_idle_ms"] == pytest.approx(
        red.unattributed_idle_s * ms)


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_in_a_program_without_spans(name):
    ctx = _ctx(PARENT_CAPTURE, PARENT_WINDOW_S, 4)
    assert _read(name, ctx) is None


def test_readers_find_nothing_in_an_untraced_run():
    ctx = _ctx(CAPTURE, META["window_s"], META["rounds"])
    ctx.reduction = ctx.capture = None
    assert all(_read(name, ctx) is None for name in READERS)


# ---- several chips ----------------------------------------------------------


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, body):
    return _varint(number << 3 | 2) + _varint(len(body)) + body


def four_chips(src, dst):
    """``src`` with its one device plane given three copies, named
    ``/device:TPU:1`` to ``3``: a capture of four chips that ran the
    same ops at the same times."""
    raw = memoryview(Path(src).read_bytes())
    out, i = bytearray(raw), 0
    while i < len(raw):
        key, i = spans._varint(raw, i)
        size, i = spans._varint(raw, i)
        plane, i = raw[i:i + size], i + size
        name = bytes(spans._first(plane, spans.XPLANE_NAME) or b"").decode()
        if key >> 3 != spans.XSPACE_PLANES or name != "/device:TPU:0":
            continue
        # the plane's id and name, then the rest as it is
        _, j = spans._varint(plane, 1)
        name_size, j = spans._varint(plane, j + 1)
        rest = bytes(plane[j + name_size:])
        for chip in (1, 2, 3):
            body = (_varint(1 << 3) + _varint(100 + chip)
                    + _field(spans.XPLANE_NAME, f"/device:TPU:{chip}".encode())
                    + rest)
            out += _field(spans.XSPACE_PLANES, body)
    Path(dst).write_bytes(bytes(out))
    return dst


PER_CHIP = READERS + ("round_device_ms", "loop_ms", "device_idle_pct",
                      "step_mfu_pct")


def test_four_chips_read_per_chip(tmp_path, red):
    one = _ctx(CAPTURE, META["window_s"], META["rounds"])
    four = _ctx(four_chips(CAPTURE, tmp_path / "four.xplane.pb"),
                META["window_s"], META["rounds"])
    # trace_reduce sums device seconds over the chips and averages busy
    # time; the span reduction gives one chip's numbers
    assert four.reduction.chips == 4
    assert four.reduction.busy_s == one.reduction.busy_s
    assert four.reduction.module_s["jit_run_chunk"] == pytest.approx(
        4 * one.reduction.module_s["jit_run_chunk"], rel=1e-12)
    red4 = spans.for_ctx(four)
    assert red4.chips == 4
    for key in ("module_s", "module_idle_s", "unscoped_s", "control_s",
                "idle_s", "unattributed_idle_s"):
        assert getattr(red4, key) == pytest.approx(getattr(red, key),
                                                   rel=1e-12), key
    assert red4.phase_s == pytest.approx(red.phase_s, rel=1e-12)
    assert red4.span_idle_s == pytest.approx(red.span_idle_s, rel=1e-12)
    assert [r[:3] for r in red4.span_rows] == [r[:3] for r in red.span_rows]
    assert [r[3] for r in red4.span_rows] == pytest.approx(
        [r[3] for r in red.span_rows], rel=1e-12)
    assert all(0 <= idle <= wall for _, _, wall, idle in red4.span_rows)
    assert len(red4.module_runs_s) == 4 * len(red.module_runs_s)
    # the whole step's FLOPs over four chips' peak: 4x the work of one
    # chip's agents reads as one chip's share
    for ctx, agents in ((one, 2), (four, 8)):
        ctx.model = json.loads((DATA / "tiny.json").read_text())
        ctx.train = {"agents": agents, "m_local": 4, "tau": 2,
                     "batch_size": 1, "seq_len": 32}
        ctx.peaks = {"bf16_flops_per_s": 197e12}
    for name in PER_CHIP:
        assert _read(name, four) == pytest.approx(_read(name, one),
                                                  rel=1e-12), name


def test_memory_peak_is_the_fullest_chip():
    from bench import run

    def chip(stats):
        return types.SimpleNamespace(memory_stats=lambda: stats)

    chips = [chip({"peak_bytes_in_use": 5}), chip({"peak_bytes_in_use": 9}),
             chip({}), chip(None)]
    assert run.max_peak(chips) == 9
    assert run.max_peak(chips[:1]) == 5
    assert run.max_peak(chips[2:]) is None
