"""The reference with its agents over four chips, against the system's
host-simulated ring and against itself on one chip.

    python bench/tools/ring_check.py --seeds 101-103 [--out ring.jsonl]

One process on a host of four TPU chips; on any other it exits 3 with
no result.  The ``qwen3-0.6b.train`` cell cut to one layer, with four
agents on a ring, one reference agent per chip (``ring_spec``).  For
every seed:

1. the reference, agent i on chip i; after the first seed's, each
   chip's peak memory, which is then the reference's alone;
2. the reference with every agent on the first chip, and the largest
   difference between the two;
3. ``bench/run.py``'s run of the cell (``run_cell``, with a short
   window): the system's checked call, every agent host-simulated on
   the first chip, against the reference over the four chips under the
   cell's limits.

One JSON line per seed, after the run's own lines.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
WORKLOAD = "qwen3-0.6b.train"
CHIPS = AGENTS = 4
LAYERS = 1
SECONDS = 5  # the run's window: the check is its checked call


def _seeds(text):
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def ring_spec(run):
    """The cell's entry, cut to ``LAYERS`` layers, with ``AGENTS``
    agents on a ring over ``CHIPS`` chips."""
    spec = run.load_cell(WORKLOAD)
    spec["cell"] = dict(spec["cell"], chips=CHIPS)
    cfg = spec["config"]
    cfg["num_hidden_layers"] = cfg["program"]["layers"] = LAYERS
    cfg["solver"].update(agents=AGENTS, topology="ring")
    return spec


def spread(a, b):
    """Largest relative difference of the losses and consensus errors,
    and largest absolute difference of the consensus mean and the first
    gradient, of two reference results."""
    import numpy as np

    def rel(key):
        return max(abs(x - y) / abs(y) for x, y in zip(a[key], b[key]))

    def absolute(key):
        return max(float(np.max(np.abs(np.asarray(a[key][k], np.float64)
                                       - np.asarray(b[key][k], np.float64))))
                   for k in b[key])

    return {"losses_rel": rel("losses"), "consensus_rel": rel("consensus"),
            "xbar_maxabs": absolute("xbar"), "grad0_maxabs": absolute("grad0")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", required=True, help="first-last")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import run

    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    try:
        devices = run.find_device(CHIPS, peaks)[:CHIPS]
    except run.NoChip as e:
        print(f"ring_check: {e}", file=sys.stderr)
        return 3
    import jax

    from repro.launch import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    spec = ring_spec(run)
    sink = open(args.out, "a") if args.out else None
    try:
        for i, seed in enumerate(_seeds(args.seeds)):
            rec = {"workload": WORKLOAD, "layers": LAYERS, "agents": AGENTS,
                   "topology": "ring", "seed": seed,
                   "platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "devices": [d.id for d in devices]}
            over = run.reference_out(spec, seed, devices)
            if i == 0:
                rec["reference_peak_bytes"] = [run.max_peak([d])
                                               for d in devices]
            one = run.reference_out(spec, seed, devices[:1])
            rec["four_vs_one"] = spread(over, one)
            del over, one
            gc.collect()
            result, checks = run.run_cell(spec, seed, SECONDS, 0, devices,
                                          peaks[devices[0].device_kind])
            rec["correct"] = result["correct"]
            rec["checks"] = {k: [v, lim] for k, (v, lim) in checks.items()}
            line = json.dumps(rec)
            print(line, flush=True)
            if sink:
                sink.write(line + "\n")
                sink.flush()
    finally:
        if sink:
            sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
