"""Benchmark of LT-ADMM training rounds through ``launch/train.py``.

    python bench/run.py --workload qwen3-0.6b.train --seed 7 --seconds 20 --trace 0

One cell (``workloads`` in ``BENCHMARK.json``) per process, on the chip
the process finds; no accelerator, or fewer chips than the cell asks
for, exits non-zero with no result.  A cell names a configuration
(``bench/configs/<name>.json``) and a traffic mix
(``bench/mixes/<name>.json``); both hold the ``launch/train.py`` flags
they stand for, and the configuration also the sizes its plain
reference (``bench/ref``) is built from.

The run calls ``repro.launch.train.main`` in-process twice, with the
cell's flags and ``--seed``:

1. the checked call, ``--rounds`` = the rounds of the first log points
   that cover three rounds, with ``--checkpoint``: it compiles the
   round (or loads it from the compilation cache) and writes the
   consensus mean the reference is compared with;
2. the window call, ``--rounds`` = ``log_every`` x (1 + enough log
   points to fill ``--seconds``, timed from the first call).  Each
   per-chunk line is timestamped as it is printed; the window runs from
   the first chunk's line (that chunk loads the program) to the last.

Set-up is everything up to the window's first line.  With ``--trace
1`` the ``jax.profiler`` capture covers the window alone and the
per-layer metrics are read from it.  After the window the peak memory
of the fullest of the cell's chips is read, the program's state is
dropped, and the reference follows the checked rounds with its agents
over the cell's chips (``bench/check.py`` says what is compared).

The last line of standard output is one JSON object; the last lines of
standard error are the compared numbers beside their limits.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CHECK_ROUNDS = 3  # the reference follows the log points that cover these
# JAX reports every compile request, and on a persistent-cache hit also
# a retrieval: requests less retrievals are the programs XLA compiled
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def log(msg):
    print(f"# bench: {msg}", file=sys.stderr, flush=True)


def _load(path):
    with open(path) as f:
        return json.load(f)


def load_cell(workload, root=ROOT):
    """The cell's entry, configuration, mix, limits and metric lists."""
    bench = _load(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if workload in m.get("workloads", [workload] if m["moves"] in
                                  names else [])]
    return {
        "cell": cell,
        "config": _load(root / configs[cell["config"]]["file"]),
        "mix": _load(root / "bench" / "mixes" / f"{cell['traffic']}.json"),
        "limits": _load(root / "bench" / "limits" / f"{workload}.json"),
        "end_to_end": e2e,
        "per_layer": layer,
    }


def flags(args: dict) -> list:
    out = []
    for k, v in args.items():
        if v is True:
            out.append(f"--{k}")
        elif v is not False:
            out += [f"--{k}", str(v)]
    return out


def program_argv(spec, seed, rounds, extra=()):
    cfg, mix = spec["config"], spec["mix"]
    return (flags(cfg["program"]) + flags(cfg["solver"])
            + flags(mix["program"])
            + ["--seed", str(seed), "--rounds", str(rounds), *extra])


def checked_rounds(spec):
    """``(log_every, rounds)``: the rounds of the first log points that
    cover ``CHECK_ROUNDS`` rounds, which the reference follows."""
    every = int(spec["mix"]["program"]["log-every"])
    return every, every * math.ceil(CHECK_ROUNDS / every)


def reference_train(spec) -> dict:
    """Solver, graph and mix numbers under the reference's names."""
    cfg, mix = spec["config"], spec["mix"]
    merged = {**cfg["solver"], **cfg["solver_defaults"], **mix["program"]}
    return {k.replace("-", "_"): v for k, v in merged.items()}


class LineClock(io.TextIOBase):
    """``sys.stdout`` stand-in: passes text on and timestamps each line
    as it ends, calling ``on_line(t, line)``."""

    def __init__(self, sink, on_line=None):
        self.sink, self.on_line = sink, on_line
        self.buf, self.lines = "", []

    def writable(self):
        return True

    def write(self, s):
        self.sink.write(s)
        self.buf += s
        while "\n" in self.buf:
            line, self.buf = self.buf.split("\n", 1)
            t = time.perf_counter()
            self.lines.append((t, line))
            if self.on_line is not None:
                self.on_line(t, line)
        return len(s)

    def flush(self):
        self.sink.flush()


def chunk_lines(lines):
    """``[(t, record)]`` of the per-chunk JSON lines (rollbacks too)."""
    out = []
    for t, line in lines:
        if line.startswith("{"):
            rec = json.loads(line)
            if "round" in rec:
                out.append((t, rec))
    return out


def drive(argv, on_line=None):
    """``train.main(argv)`` with its standard output timestamped."""
    from repro.launch import train

    clock = LineClock(sys.stdout, on_line)
    with contextlib.redirect_stdout(clock):
        summary = train.main(argv)
    return summary, clock.lines


class Window:
    """Marks the window on the window call's chunk lines, counts the
    compilations inside it and, when tracing, runs the profiler over
    exactly that stretch."""

    def __init__(self, last_chunk, profile_dir=None):
        self.last, self.profile_dir = last_chunk, profile_dir
        self.seen, self.t0, self.t1 = 0, None, None
        self.open, self.requests, self.cache_loads = False, 0, 0
        self.trace_t0 = self.trace_t1 = None

    def on_compile(self, event, duration, **_):
        if self.open and event == COMPILE_EVENT:
            self.requests += 1
        elif self.open and event == CACHE_HIT_EVENT:
            self.cache_loads += 1

    @property
    def compiles(self):
        return self.requests - self.cache_loads

    def on_line(self, t, line):
        if not line.startswith("{") or '"round"' not in line:
            return
        import jax

        if self.seen == 0:
            self.t0, self.open = t, True
            if self.profile_dir:
                # no Python tracer: it would slow the host work it times
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.enable_hlo_proto = False
                jax.profiler.start_trace(self.profile_dir,
                                         profiler_options=opts)
                self.trace_t0 = time.perf_counter()
        if self.seen == self.last:
            self.t1, self.open = t, False
            if self.profile_dir:
                self.trace_t1 = time.perf_counter()
                jax.profiler.stop_trace()
        self.seen += 1


def find_device(chips, peaks):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"found {len(devs)} {devs[0].platform} device(s), the "
                     f"cell needs {chips} TPU chip(s)")
    kind = devs[0].device_kind
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} has no row in bench/peaks.json")
    return devs


def max_peak(devices):
    """Peak bytes in use on the fullest of ``devices``, or None where the
    backend reports none."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    return max((p for p in peaks if p is not None), default=None)


def _metric(name):
    return importlib.import_module(f"bench.metrics.{name}")


def _xplane(directory):
    found = sorted(Path(directory).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under {directory}")
    return found[-1]


def reference_out(spec, seed, devices):
    """The float32 reference over the checked rounds from ``seed``, its
    agent i on ``devices[i % len(devices)]``, with ``wire_per_round``
    (the busiest agent's quantized message bytes in one round)."""
    import jax
    import numpy as np

    from bench import ref
    from bench.ref import ltadmm

    every, checked = checked_rounds(spec)
    train = reference_train(spec)
    reference = ltadmm.Reference(spec["config"], train,
                                 net=ref.module(spec["config"]),
                                 devices=devices)
    t_ref = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        out = reference.run(seed, checked, every)
    nbrs = ltadmm.neighbours(train["topology"], train["agents"])
    n = sum(int(np.prod(v.shape)) for v in out["weights0"].values())
    out["wire_per_round"] = (max(len(x) for x in nbrs) * 2
                             * ltadmm.message_bytes(n, train["bits"]))
    log(f"reference took {time.perf_counter() - t_ref:.1f} s; peak of "
        f"each chip since the start {[max_peak([d]) for d in devices]} B")
    return out


def run_cell(spec, seed, seconds, trace, devices, peaks=None):
    """One run of the cell on ``devices[:chips]``; -> (result dict,
    checks)."""
    import jax
    import numpy as np

    from bench import check

    chips = devices[:int(spec["cell"]["chips"])]

    every, checked = checked_rounds(spec)
    with tempfile.TemporaryDirectory(prefix="bench.") as tmp:
        ckpt = str(Path(tmp) / "checked")
        log(f"checked call: {checked} rounds")
        sum_a, lines_a = drive(program_argv(spec, seed, checked,
                                            ["--checkpoint", ckpt]))
        chunks_a = chunk_lines(lines_a)
        banner = next(t for t, line in lines_a if line.startswith("# arch="))
        if len(chunks_a) > 1:
            per_chunk = (chunks_a[-1][0] - chunks_a[0][0]) / (len(chunks_a) - 1)
        else:
            per_chunk = chunks_a[0][0] - banner - sum_a["compile_s"]
        n_win = max(1, math.ceil(seconds / max(per_chunk, 1e-3)))
        log(f"chunk of {every} rounds ~{per_chunk:.3f} s; window call: "
            f"1 + {n_win} chunks")
        with np.load(Path(ckpt) / "arrays.npz") as z:
            xbar = {k: z[k] for k in z.files}

        window = Window(n_win, str(Path(tmp) / "profile") if trace else None)
        jax.monitoring.register_event_duration_secs_listener(window.on_compile)
        sum_b, lines_b = drive(program_argv(spec, seed, every * (1 + n_win)),
                               window.on_line)
        chunks_b = chunk_lines(lines_b)
        peak = max_peak(chips)
        rounds = n_win * every
        ctx = types.SimpleNamespace(
            setup_s=window.t0 - T_START,
            window_s=window.t1 - window.t0,
            rounds=rounds,
            telemetry=sum_b["telemetry"],
            wire_hint=sum_b["wire_bytes"],
            memory_peak_bytes=peak,
            model=spec["config"],
            train=reference_train(spec),
            peaks=peaks,
            round_scopes=sum_b.get("round_scopes"),
            capture=None,
            reduction=None,
        )
        breakdown = None
        if trace:
            from bench import trace_reduce

            ctx.window_s = window.trace_t1 - window.trace_t0
            ctx.capture = _xplane(Path(tmp) / "profile")
            ctx.reduction = trace_reduce.reduce(ctx.capture, ctx.window_s)
            breakdown = {
                "device_ops": trace_reduce.top_ops(ctx.reduction),
                "idle_gaps": [list(g) for g in ctx.reduction.gaps],
            }
        metrics = {}
        for m in spec["per_layer"] if trace else spec["end_to_end"]:
            value = _metric(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        log(f"window: {rounds} rounds in {ctx.window_s:.3f} s, "
            f"{window.compiles} compilations and {window.cache_loads} "
            f"compilation-cache loads inside, peak {peak} B")

    # the program's executables and any buffer they keep go before the
    # reference loads its own programs
    jax.clear_caches()
    gc.collect()
    live = sum(a.nbytes for a in jax.live_arrays())
    log(f"reference: {checked} rounds; {live} B live on the device before it")
    out = reference_out(spec, seed, chips)
    tel = sum_b["telemetry"] or {"tx_bytes": [0], "rounds": 0}
    prog = {
        "losses": [loss for _, loss in sum_a["losses"]],
        "consensus": [r["consensus_err"] for _, r in chunks_a
                      if "consensus_err" in r],
        "xbar": xbar,
        "replay": [(sum_b["losses"][0][1], chunks_b[0][1]["consensus_err"]),
                   (sum_a["losses"][0][1], chunks_a[0][1]["consensus_err"])],
        "tx_bytes": int(max(tel["tx_bytes"])),
        "rounds": int(tel["rounds"]),
        "compiles": window.compiles,
    }
    checks = check.compare(prog, out, spec["limits"])
    failed = sum(every for _, r in chunks_b[1:] if "watchdog" in r)
    result = {
        "correct": check.passed(checks),
        "attempted": rounds,
        "failed": failed,
        "metrics": metrics,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": peak,
        },
    }
    if trace:
        result["device"]["busy_s"] = ctx.reduction.busy_s
        result["device"]["window_s"] = ctx.window_s
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    src = ROOT / "src"
    if not (src / "repro" / "launch" / "train.py").is_file():
        print(f"bench: the system under test is not in {src}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(src), str(ROOT)]
    spec = load_cell(args.workload)
    peaks = _load(ROOT / "bench" / "peaks.json")
    try:
        devices = find_device(int(spec["cell"]["chips"]), peaks)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    import jax

    from repro.launch import compile_cache

    log(f"compilation cache: {compile_cache.enable()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result, checks = run_cell(spec, args.seed, args.seconds, args.trace,
                              devices, peaks[devices[0].device_kind])
    for name, (value, limit) in checks.items():
        print(f"check {name} {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
