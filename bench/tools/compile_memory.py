"""Compile a cell's round program for a described TPU v5e, without the
chip, and print XLA's memory analysis.

    JAX_PLATFORMS=cpu python bench/tools/compile_memory.py olmo-1b.train

Builds the solver exactly as ``launch/train.py``'s ``build`` does from
the cell's flags (with telemetry), then lowers one chunk of
``log_every`` scanned rounds for one chip of a described ``v5e:2x2``
from abstract shapes and compiles it with the Pallas kernels in
compiled mode.  Prints the arguments, outputs, aliased and temporary
bytes, and adds the initial weights and their per-agent copy that
``train.main`` keeps alive beside the state.
"""
from __future__ import annotations

import argparse
import math
import os
import sys
import time
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("workload")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import repro.kernels as kernels
    from bench import run
    from repro.launch import steps, train
    from repro.models.common import abstract_params
    from repro.obs import telemetry

    jax.config.update("jax_enable_compilation_cache", False)
    # compile the kernels, not their interpreter: there is no chip here
    kernels.resolve_interpret = lambda interpret: bool(interpret)
    spec = run.load_cell(args.workload)
    cfg, mix = spec["config"], spec["mix"]["program"]
    flags = {**cfg["program"], **cfg["solver"], **mix}
    ns = types.SimpleNamespace(
        arch=flags["arch"], smoke=False, layers=flags.get("layers"),
        vocab_rows=flags.get("vocab-rows"), agents=flags["agents"],
        solver=flags["solver"], topology=flags["topology"],
        topology_schedule=None, compressor=flags["compressor"],
        bits=flags["bits"], fraction=0.25, tau=flags["tau"],
        gamma=flags["gamma"], beta=flags["beta"],
        batch_size=flags["batch-size"], faults=None)
    arch, mcfg, solver, _, _ = train.build(ns)
    solver = telemetry.with_telemetry(solver)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    a = ns.agents
    params = abstract_params(steps.model_specs(arch, mcfg))
    x_sds = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((a,) + s.shape, s.dtype), params)
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        solver.abstract_state(x_sds))
    data = jax.ShapeDtypeStruct(
        (a, mix["m-local"], mix["seq-len"] + 1), jnp.int32, sharding=chip)
    first = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    n = int(mix["log-every"])

    def run_chunk(state, tokens, first_round):
        def body(st, r):
            return solver.step(st, {"tokens": tokens},
                               jax.random.key(1000 + r)), None

        state, _ = jax.lax.scan(body, state, first_round + jnp.arange(n))
        return state

    t0 = time.perf_counter()
    compiled = jax.jit(run_chunk, donate_argnums=0).lower(
        state, data, first).compile()
    mem = compiled.memory_analysis()
    n_params = sum(math.prod(s.shape) for s in jax.tree.leaves(params))
    kept = 4 * n_params * (1 + a)  # params0 and the broadcast x0, float32
    gb = 1e9
    print(f"{args.workload}: compiled for one v5e chip in "
          f"{time.perf_counter() - t0:.1f} s; params {n_params:,}")
    print(f"  arguments {mem.argument_size_in_bytes / gb:.3f} GB, outputs "
          f"{mem.output_size_in_bytes / gb:.3f} GB, aliased "
          f"{mem.alias_size_in_bytes / gb:.3f} GB, temporaries "
          f"{mem.temp_size_in_bytes / gb:.3f} GB")
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes + kept)
    print(f"  + params0 and x0 kept by train.main {kept / gb:.3f} GB = "
          f"{total / gb:.3f} GB analysed")
    print(f"  pallas kernel in round: {'tpu_custom_call' in compiled.as_text()}")


if __name__ == "__main__":
    main()
