"""The span reduction on a small recorded window, its identities, and the
readers on a capture with and without the program's spans."""
import importlib
import json
import shutil
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
    assert red.busy_s == tr.busy_s


def test_span_idles_and_unattributed_idle_are_the_idle_time(red):
    assert set(red.span_idle_s) == {"train.chunk", "train.eval",
                                    "train.watchdog", "train.log"}
    total = sum(red.span_idle_s.values()) + red.unattributed_idle_s
    assert total == pytest.approx(red.idle_s, rel=1e-9)
    # device_idle_pct's idle: ops the capture holds past the host's
    # window end count as busy there, so the two differ by a hair
    assert red.idle_s == pytest.approx(red.window_s - red.busy_s, rel=1e-3)
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


def _ctx(tmp_path, monkeypatch, capture, window_s, rounds):
    """The ``ctx`` bench/run.py hands a reader in a traced run, the
    capture where its window profiles to."""
    where = tmp_path / "bench.x" / "profile" / "plugins" / "profile" / "t"
    where.mkdir(parents=True)
    shutil.copy(capture, where / "host.xplane.pb")
    monkeypatch.setattr(spans.tempfile, "tempdir", str(tmp_path))
    spans._reduce_once.cache_clear()
    return types.SimpleNamespace(
        reduction=trace_reduce.reduce(capture, window_s),
        window_s=window_s, rounds=rounds)


def _read(name, ctx):
    return importlib.import_module(f"bench.metrics.{name}").read(ctx)


READERS = ("local_device_ms", "plane_device_ms", "exchange_device_ms",
           "eval_idle_ms", "watchdog_idle_ms", "unattributed_idle_ms")


def test_readers_on_the_recorded_window(tmp_path, monkeypatch, red):
    ctx = _ctx(tmp_path, monkeypatch, CAPTURE, META["window_s"],
               META["rounds"])
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
def test_readers_find_nothing_in_a_program_without_spans(
        tmp_path, monkeypatch, name):
    ctx = _ctx(tmp_path, monkeypatch, PARENT_CAPTURE, PARENT_WINDOW_S, 4)
    assert _read(name, ctx) is None


def test_readers_refuse_a_capture_of_another_window(tmp_path, monkeypatch):
    ctx = _ctx(tmp_path, monkeypatch, CAPTURE, META["window_s"],
               META["rounds"])
    ctx.reduction = trace_reduce.reduce(PARENT_CAPTURE, PARENT_WINDOW_S)
    assert spans.for_ctx(ctx) is None
    ctx.reduction = None  # an untraced run
    assert _read("local_device_ms", ctx) is None
