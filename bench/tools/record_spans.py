"""Record a small traced window of ``train.main`` with the program's
spans and round phases, for ``bench/tests/test_spans.py``.

    python bench/tools/record_spans.py OUT_DIR

Runs the system's qwen3 smoke configuration (two agents, qbit 8-bit,
``impl=auto``, the divergence watchdog on) for three chunks of two
rounds and profiles the last two chunks, as the benchmark profiles its
window (no Python tracer).  Writes ``OUT_DIR/spans.xplane.pb`` and
``OUT_DIR/spans.json`` (the window's length, its rounds and the run's
``round_scopes``), and prints the reduction.  Needs the chip.

The capture is kept as the profiler wrote it but for two edits that
leave the protobuf intact: the checkout's path in the HLO metadata is
rewritten to a neutral prefix of the same length, and only the planes
are kept, less ``/host:metadata`` (the HLO of the programs compiled in
the window: two thirds of the bytes, read by no reduction).
"""
from __future__ import annotations

import dataclasses
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
NEUTRAL = "/work/tree"
ROUNDS_PER_CHUNK = 2
DROPPED_PLANE = "/host:metadata"


def planes_only(raw: bytes) -> bytes:
    """The XSpace ``raw`` with its planes alone, less DROPPED_PLANE."""
    from bench import spans

    buf, out, i = memoryview(raw), bytearray(), 0
    while i < len(buf):
        start = i
        key, i = spans._varint(buf, i)
        size, i = spans._varint(buf, i)  # every XSpace field is a length
        body, i = buf[start:i + size], i + size
        plane = body[len(body) - size:]
        if (key >> 3 == spans.XSPACE_PLANES and bytes(spans._first(
                plane, spans.XPLANE_NAME) or b"").decode() != DROPPED_PLANE):
            out += body
    return bytes(out)


def main(out):
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    from bench import run, spans
    from repro.launch import compile_cache

    compile_cache.enable()
    out = Path(out)
    out.mkdir(parents=True, exist_ok=True)
    prof = out / "profile"
    window = run.Window(2, str(prof))
    argv = ["--arch", "qwen3-0.6b", "--smoke", "--agents", "2",
            "--topology", "complete", "--seq-len", "256", "--m-local", "4",
            "--batch-size", "1", "--tau", "2",
            "--log-every", str(ROUNDS_PER_CHUNK), "--rounds", "6",
            "--telemetry", "--watchdog-blowup", "1e4"]
    summary, _ = run.drive(argv, window.on_line)
    window_s = window.trace_t1 - window.trace_t0
    raw = run._xplane(prof).read_bytes()
    root = str(ROOT).encode()
    neutral = (NEUTRAL + "x" * len(root))[:len(root)].encode()
    path = out / "spans.xplane.pb"
    path.write_bytes(planes_only(raw.replace(root, neutral)))
    shutil.rmtree(prof)
    (out / "spans.json").write_text(json.dumps({
        "window_s": round(window_s, 6),
        "rounds": 2 * ROUNDS_PER_CHUNK,
        "round_scopes": summary["round_scopes"],
    }, indent=1, sort_keys=True) + "\n")
    red = spans.reduce(path, round(window_s, 6))
    print(json.dumps(dataclasses.asdict(red), indent=1))
    print(f"{path.stat().st_size} bytes; {raw.count(root)} paths rewritten")


if __name__ == "__main__":
    t0 = time.perf_counter()
    main(sys.argv[1])
    print(f"record_spans took {time.perf_counter() - t0:.1f} s")
