"""Record a small device trace of ``train.main`` for the reduction's test,
and print the trace's planes, lines and most frequent event names.

    python bench/tools/record_trace.py OUT_DIR

Runs the system's qwen3 smoke configuration (two agents, qbit 8-bit,
``impl=auto``) for three chunks of two rounds and profiles the last two
chunks, as the benchmark profiles its window (no Python tracer).
Needs the chip.
"""
from __future__ import annotations

import collections
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(out):
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    from bench import run
    from jax.profiler import ProfileData
    from repro.launch import compile_cache

    compile_cache.enable()

    out = Path(out)
    out.mkdir(parents=True, exist_ok=True)
    prof = out / "profile"
    window = run.Window(2, str(prof))
    argv = ["--arch", "qwen3-0.6b", "--smoke", "--agents", "2",
            "--topology", "complete", "--seq-len", "256", "--m-local", "4",
            "--batch-size", "1", "--tau", "2", "--log-every", "2",
            "--rounds", "6", "--telemetry", "--watchdog-blowup", "0"]
    run.drive(argv, window.on_line)
    print(f"traced window {window.trace_t1 - window.trace_t0:.6f} s")
    path = run._xplane(prof)
    shutil.copy(path, out / "small.xplane.pb")
    data = ProfileData.from_file(str(path))
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            print(f"  LINE {line.name!r} events={len(evs)} "
                  f"first={evs[0].start_ns if evs else None} "
                  f"last_end={max((e.end_ns for e in evs), default=None)}")
            for name, count in names.most_common(12):
                ev = next(e for e in evs if e.name == name)
                print(f"    {count:5d} {name[:90]!r} stats={list(ev.stats)[:6]}")


if __name__ == "__main__":
    t0 = time.perf_counter()
    main(sys.argv[1])
    print(f"record_trace took {time.perf_counter() - t0:.1f} s")
