"""Readings the limits of ``bench/limits/<workload>.json`` are set from.

    python bench/tools/readings.py WORKLOAD --seeds 101-112 --faults 3 \
        [--out readings.jsonl]

One process, on the chip.  For every seed: the cell's checked call of
``train.main`` (the rounds and log points ``bench/run.py`` checks) and
the float32 reference, compared by ``bench/check.py``: the lower
readings.  For the first ``--faults`` seeds also the control (the
reference with its solver state and products in bfloat16, put in the
program's place) and the reference with each planted fault
(``half_batch``, ``no_exchange``), each against the float32 reference:
the upper readings.  A state left unchanged reads 1 on ``change_gap``
by construction and is not run.  One JSON line per reading.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
PLANTED = ("half_batch", "no_exchange")


def _seeds(text):
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def _numbers(prog, out):
    from bench import check

    limits = {k: math.inf for k in ("loss_gap", "consensus_gap", "change_gap",
                                     "replay_gap", "wire_gap",
                                     "window_compiles")}
    checks = check.compare(prog, dict(out, wire_per_round=0), limits)
    _, leaf, left_out = check.change_gap(prog["xbar"], out["weights0"],
                                         out["xbar"], out["grad0"])
    return {**{k: checks[k][0] for k in ("loss_gap", "consensus_gap",
                                          "change_gap")},
            "worst_leaf": leaf, "leaves_left_out": left_out}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("workload")
    ap.add_argument("--seeds", required=True, help="first-last")
    ap.add_argument("--faults", type=int, default=3,
                    help="seeds (from the first) that also read the control "
                         "and the planted faults")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import run
    from bench.check import reference_as_program
    from bench.ref import ltadmm
    from repro.launch import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    spec = run.load_cell(args.workload)
    every = int(spec["mix"]["program"]["log-every"])
    checked = every * math.ceil(run.CHECK_ROUNDS / every)
    train = run.reference_train(spec)
    net = importlib.import_module(
        "bench.ref." + Path(spec["config"]["reference"]).stem)
    sink = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()

    def reference(seed, **kw):
        ref = ltadmm.Reference(spec["config"], train, net=net, **kw)
        t0 = time.perf_counter()
        prec = "highest" if "plane_dtype" not in kw else "default"
        with jax.default_matmul_precision(prec):
            out = ref.run(seed, checked, every)
        return out, time.perf_counter() - t0

    try:
        for i, seed in enumerate(_seeds(args.seeds)):
            with tempfile.TemporaryDirectory(prefix="readings.") as tmp:
                ckpt = str(Path(tmp) / "checked")
                t0 = time.perf_counter()
                summ, lines = run.drive(run.program_argv(
                    spec, seed, checked, ["--checkpoint", ckpt]))
                t_prog = time.perf_counter() - t0
                with np.load(Path(ckpt) / "arrays.npz") as z:
                    xbar = {k: z[k] for k in z.files}
            chunks = run.chunk_lines(lines)
            prog = {"losses": [v for _, v in summ["losses"]],
                    "consensus": [r["consensus_err"] for _, r in chunks],
                    "xbar": xbar, "replay": [(0.0, 0.0), (0.0, 0.0)],
                    "tx_bytes": 0, "rounds": 0, "compiles": 0}
            out, t_ref = reference(seed)
            emit({"workload": args.workload, "seed": seed, "kind": "program",
                  "program_s": t_prog, "reference_s": t_ref,
                  **_numbers(prog, out)})
            if i >= args.faults:
                continue
            ctl, t_ctl = reference(seed, plane_dtype=jnp.bfloat16,
                                   compute_dtype=jnp.bfloat16)
            emit({"workload": args.workload, "seed": seed, "kind": "control",
                  "seconds": t_ctl, **_numbers(reference_as_program(ctl), out)})
            for fault in PLANTED:
                bad, t_bad = reference(seed, fault=fault)
                emit({"workload": args.workload, "seed": seed, "kind": fault,
                      "seconds": t_bad, **_numbers(reference_as_program(bad), out)})
    finally:
        if sink:
            sink.close()


if __name__ == "__main__":
    main()
