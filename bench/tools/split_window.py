"""Run one traced cell of the benchmark and keep the whole span split of
its window, for PERF.md.

    python bench/tools/split_window.py OUT.json --workload <cell> \
        --seed <n> --seconds <s>

Runs ``bench/run.py``'s ``main`` with ``--trace 1`` in this process and
prints its result line as it does.  The benchmark drops its capture at
the end of the run, so this keeps the reduction ``bench/spans.py`` made
of it while it existed (phases, idle per span, each span's row and each
run of the round program) and writes it, with the cell's round count,
to OUT.json.  Needs the chip.
"""
from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(out, argv):
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    from bench import run, spans

    kept = []
    for_ctx = spans.for_ctx

    def keep(ctx):
        red = for_ctx(ctx)
        if red is not None:
            kept.append({"rounds": ctx.rounds, **dataclasses.asdict(red)})
        return red

    spans.for_ctx = keep
    rc = run.main([*argv, "--trace", "1"])
    if kept:
        Path(out).write_text(json.dumps(kept[-1], indent=1) + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2:]))
