# Multi-pod dry-run: the XLA_FLAGS line MUST precede every other import —
# jax locks the device count on first initialization.
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCHS, SHAPES, input_specs  # noqa: E402
from repro.launch import hlo_analysis as ha  # noqa: E402
from repro.launch import sharding as shd  # noqa: E402
from repro.launch import steps  # noqa: E402
from repro.launch.mesh import agent_axis_for, make_production_mesh  # noqa: E402
from repro.models.common import abstract_params, param_count  # noqa: E402
from repro.models.moe import MoEConfig  # noqa: E402

"""Dry-run: lower + compile every (architecture x input-shape x mesh)
combination against the production mesh with ShapeDtypeStruct inputs —
no allocation, but the compiled artifact is real: memory analysis, cost
analysis and the collective schedule all come from it (EXPERIMENTS.md
§Dry-run / §Roofline read these records).

Usage:
    python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
    python -m repro.launch.dryrun --all --multi-pod both \
        --out results/dryrun.jsonl
"""


def _sharding_tree(mesh, pspec_tree):
    return jax.tree.map(
        lambda sp: NamedSharding(mesh, sp),
        pspec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def active_param_count(arch, cfg) -> float:
    """Parameters touched per token (MoE: routed experts scaled by top_k/E)."""
    specs = steps.model_specs(arch, cfg)
    total = param_count(specs)
    moe: MoEConfig = getattr(cfg, "moe", None)
    if moe is None:
        return float(total)
    # routed expert params per MoE layer
    per_expert = 3 * moe.d_model * moe.d_ff_expert
    n_moe_layers = cfg.n_units * sum(
        1 for k in cfg.pattern if k in ("moe", "mla")
    )
    routed = n_moe_layers * moe.n_experts * per_expert
    active_routed = routed * moe.top_k / moe.n_experts
    return float(total - routed + active_routed)


def model_flops(arch, cfg, shape, mode, n_agents, recipe) -> float:
    """Analytic 6·N_active·D (dense fwd+bwd) / 2·N·D (fwd-only)."""
    n_act = active_param_count(arch, cfg)
    b, t = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        # LT-ADMM-CC outer round: SVRG anchor (m_local seqs) + tau inner
        # steps x 2 batch-grads each, per agent.
        m_local = b // n_agents
        tokens = n_agents * (m_local + 2 * recipe.tau * recipe.batch_size) * t
        return 6.0 * n_act * tokens
    if shape.kind == "prefill":
        return 2.0 * n_act * b * t
    return 2.0 * n_act * b  # decode: one token per request


def dryrun_one(arch_id, shape_name, multi_pod, recipe=None, verbose=True,
               variant=None):
    """variant: dict of perf-iteration overrides —
       xent_chunks: int   (streamed fused unembed+xent)
       serve_mode: "serve" | "serve_replicated"
       remat: bool
    """
    variant = variant or {}
    recipe = recipe or steps.TrainRecipe()
    import dataclasses as _dc0
    rec_over = {k[7:]: v for k, v in variant.items()
                if k.startswith("recipe_")}
    if rec_over:
        recipe = _dc0.replace(recipe, **rec_over)
    arch = ARCHS[arch_id]
    shape = SHAPES[shape_name]
    cfg = arch.make(shape_name)
    import dataclasses as _dc
    for field in ("xent_chunks", "remat", "remat_policy"):
        if field in variant and hasattr(cfg, field):
            cfg = _dc.replace(cfg, **{field: variant[field]})
    if "attn_seq_shard" in variant and getattr(cfg, "attn", None):
        cfg = _dc.replace(
            cfg,
            attn=_dc.replace(cfg.attn,
                             seq_shard_axis=variant["attn_seq_shard"]),
        )
    serve_mode = variant.get("serve_mode", "serve")
    mesh = make_production_mesh(multi_pod=multi_pod)
    aaxis = agent_axis_for(mesh)
    t0 = time.time()

    _mesh_ctx = jax.set_mesh(mesh)
    _mesh_ctx.__enter__()
    if shape.kind == "train":
        step_fn, state_ps, init_fn, solver = steps.build_train(
            arch, cfg, mesh, variant.get("solver", "ltadmm"), recipe
        )
        n_agents = solver.graph.n_agents
        state_sds = steps.abstract_train_state(arch, cfg, solver)
        data_sds = input_specs(arch_id, shape_name, n_agents=n_agents)
        data_ps = shd.train_data_pspec(
            mesh, {k: len(v.shape) for k, v in data_sds.items()}
        )
        in_sh = (
            _sharding_tree(mesh, state_ps),
            _sharding_tree(mesh, data_ps),
            NamedSharding(mesh, P()),
        )
        fn = jax.jit(
            step_fn, in_shardings=in_sh,
            out_shardings=_sharding_tree(mesh, state_ps),
        )
        lowered = fn.lower(
            state_sds, data_sds, jax.ShapeDtypeStruct((), jnp.uint32)
        )
    elif shape.kind == "prefill":
        n_agents = None
        prefill, pps = steps.build_prefill(arch, cfg, mesh, mode=serve_mode)
        params_sds = abstract_params(steps.model_specs(arch, cfg), cfg.dtype)
        data_sds = input_specs(arch_id, shape_name)
        data_ps = {
            k: shd.batch_pspec(mesh, v.shape) for k, v in data_sds.items()
        }
        in_sh = (
            _sharding_tree(mesh, pps),
            _sharding_tree(mesh, data_ps),
        )
        fn = jax.jit(prefill, in_shardings=in_sh)
        lowered = fn.lower(params_sds, data_sds)
    else:  # decode
        n_agents = None
        serve, pps, abstract_cache = steps.build_serve(
            arch, cfg, mesh, mode=serve_mode
        )
        params_sds = abstract_params(steps.model_specs(arch, cfg), cfg.dtype)
        data_sds = dict(input_specs(arch_id, shape_name))
        data_sds["_max_len"] = shape.seq_len
        cache_sds = abstract_cache(params_sds, data_sds)
        data_sds.pop("_max_len")
        data_sds.pop("memory", None)
        cache_ps = shd.cache_pspec(mesh, cache_sds)
        data_ps = {
            k: shd.batch_pspec(mesh, v.shape) if v.shape else P()
            for k, v in data_sds.items()
        }
        in_sh = (
            _sharding_tree(mesh, pps),
            _sharding_tree(mesh, cache_ps),
            _sharding_tree(mesh, data_ps),
        )
        fn = jax.jit(serve, in_shardings=in_sh)
        lowered = fn.lower(params_sds, cache_sds, data_sds)

    compiled = lowered.compile()
    _mesh_ctx.__exit__(None, None, None)
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    stats = ha.analyze(compiled.as_text())
    terms = ha.roofline_terms(stats)
    mf = model_flops(
        arch, cfg, shape, shape.kind, n_agents or 1, recipe
    )
    chips = math.prod(mesh.shape.values())
    rec = {
        "arch": arch_id,
        "shape": shape_name,
        "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names),
        "multi_pod": multi_pod,
        "agent_axis": aaxis if shape.kind == "train" else None,
        "n_agents": n_agents,
        "chips": chips,
        "compile_s": round(t_compile, 1),
        "bytes_per_device": {
            "args": mem.argument_size_in_bytes,
            "out": mem.output_size_in_bytes,
            "temp": mem.temp_size_in_bytes,
            "alias": mem.alias_size_in_bytes,
            "total_live": mem.argument_size_in_bytes
            + mem.output_size_in_bytes
            + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        "xla_cost_analysis_flops": ca.get("flops"),
        "hlo": stats.as_dict(),
        "roofline": terms,
        "model_flops_global": mf,
        "model_flops_per_chip": mf / chips,
        "useful_fraction": (mf / chips) / stats.dot_flops
        if stats.dot_flops
        else None,
        "variant": variant,
    }
    if verbose:
        print(json.dumps(rec, indent=1, default=str))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument(
        "--multi-pod", default="single", choices=["single", "multi", "both"]
    )
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    pods = {"single": [False], "multi": [True], "both": [False, True]}[
        args.multi_pod
    ]
    combos = []
    archs = list(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    for a in archs:
        for s in shapes:
            for mp in pods:
                combos.append((a, s, mp))

    records, failures = [], []
    for a, s, mp in combos:
        tag = f"{a} x {s} x {'2x16x16' if mp else '16x16'}"
        print(f"=== dryrun {tag}", flush=True)
        try:
            records.append(dryrun_one(a, s, mp, verbose=not args.all))
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            failures.append({"combo": tag, "error": f"{type(e).__name__}: {e}"})
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            for r in records:
                f.write(json.dumps(r, default=str) + "\n")
    print(f"\n{len(records)} ok, {len(failures)} failed")
    for f_ in failures:
        print("FAILED:", f_["combo"], "->", f_["error"])
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
