"""Distributed-training driver: any registered solver on a real model.

Runs LT-ADMM-CC (default) or any baseline from ``core.solver.SOLVERS``
end-to-end: agents hold heterogeneous synthetic data shards, train
locally, and exchange (compressed) messages over the agent graph
selected with ``--topology`` (ring, grid2d, star, complete, erdos,
smallworld) or a time-varying ``--topology-schedule`` (cycle:ring|star,
drop:p=0.2,..., gossip:edges=2,..., and the node-level participation
schedules churn:p=0.1,..., burst:fail=0.1,recover=0.5,...,
sample:frac=0.25,...).  On a single host device the graph is simulated
(same code path, gather-by-index exchange); on a multi-device mesh the
exchange is one collective-permute per neighbor slot over the (union)
agent axis — schedules keep that program static and mask inactive
edges per round; node schedules additionally freeze a churned-out
agent's params for the round (asynchronous-ADMM semantics).

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
        --agents 4 --rounds 20 --compressor qbit --topology complete
    PYTHONPATH=src python -m repro.launch.train --smoke --agents 4 \
        --rounds 20 --topology-schedule drop:p=0.25,base=complete
    PYTHONPATH=src python -m repro.launch.train --smoke --agents 4 \
        --rounds 20 --solver choco:lr=0.02 --topology ring

Observability: ``--telemetry`` wraps the solver in the in-trace counter
plane (``repro.obs.telemetry``) — measured wire bytes, messages,
fault-plane rejects, participation and gradient evaluations accumulate
on-device in the scanned state (no host syncs, trajectories unchanged)
and print as one JSON line at the end.  The driver loop's spans
(``train.build``, and per chunk ``train.chunk``, ``train.eval``,
``train.watchdog``, ``train.log``, ``train.checkpoint``, each with the
chunk's ``first_round``) are always ``jax.profiler`` annotations, so
they land in any profiler capture beside the device's ops; the LT-ADMM
round's ops carry the ``ltadmm.*`` named scopes of its phases.
``--trace out.json`` also writes the spans as Chrome-trace JSONL —
load it in Perfetto or summarize with ``python -m repro.obs.summary
out.json``; ``--trace-profile DIR`` additionally captures the
jax.profiler device trace over the run.

Fitting one chip: ``--layers N`` and ``--vocab-rows V`` cut depth and
embedding rows of the published config (widths stay as published); the
banner prints the cut as ``reduced``.  ``main(argv)`` can be called
in-process and returns a summary of the run (see ``main``).
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.configs import ARCHS
from repro.core import compression, vr
from repro.core.schedule import SCHEDULES, TopologySchedule, build_graph
from repro.core.solver import (
    SOLVERS,
    consensus_error,
    make_solver,
    solver_entry,
)
from repro.core.topology import TOPOLOGIES
from repro.data import SyntheticLMDataset
from repro.launch import compile_cache, hlo_analysis
from repro.launch.steps import (
    DivergenceWatchdog,
    TrainRecipe,
    model_loss,
    model_specs,
)
from repro.models.common import init_params, param_count
from repro.obs import telemetry, trace


def build(args):
    arch = ARCHS[args.arch]
    cfg = arch.make_smoke() if args.smoke else arch.make(None)
    cut = {k: v for k, v in (("n_layers", args.layers),
                             ("vocab", args.vocab_rows)) if v}
    if cut:
        cfg = dataclasses.replace(cfg, **cut)
    if arch.kind == "encdec" or getattr(cfg, "inputs_via_embeds", False):
        raise SystemExit(
            "train.py drives token-LM archs; embed/enc-dec archs are "
            "exercised via the dry-run and tests"
        )
    spec = args.topology_schedule or args.topology
    # Topology or TopologySchedule + host-simulated exchange (see
    # tests/_distributed_check for the ppermute-backed mesh variant —
    # identical trajectories); a schedule compiles the union graph's
    # wire program once, per-round masks select the active edges
    graph, ex = build_graph(spec, args.agents)
    comp_spec = (
        f"qbit:bits={args.bits}" if args.compressor == "qbit" else
        f"randk:fraction={args.fraction},sampler=block"
        if args.compressor == "randk" else args.compressor
    )
    recipe = TrainRecipe(
        tau=args.tau,
        gamma=args.gamma,
        beta=args.beta,
        batch_size=args.batch_size,
        compressor=comp_spec,
        topology=spec,
    )
    entry = solver_entry(args.solver)
    loss = model_loss(arch, cfg)
    grad = jax.grad(loss)
    est = (
        vr.SvrgAnchor(batch_grad=grad, full_grad=grad)
        if entry.estimator == "vr"
        else vr.PlainSgd(batch_grad=grad)
    )
    defaults = recipe.solver_defaults(entry.name)
    if getattr(args, "faults", None):
        # every registered solver accepts a faults= param; spec params win
        defaults["faults"] = args.faults
    solver = make_solver(args.solver, graph, ex, est, defaults=defaults)
    return arch, cfg, solver, loss, cut


def _compressor_impl(solver):
    """Backend the solver's (x-message) compressor resolved to here."""
    cfg = getattr(solver, "cfg", None)
    comp = (getattr(cfg, "compressor_x", None)
            or getattr(solver, "compressor", None)
            or getattr(cfg, "compressor", None))
    return None if comp is None else compression.resolved_impl(comp)


def main(argv=None):
    """Run the trainer; ``argv=None`` reads the command line.

    Returns a summary: ``params``, ``reduced`` (the depth/vocab cut),
    ``compressor_impl``, ``wire_bytes`` (analytic, per agent per round),
    ``compile_s`` (ahead-of-time compiles of the round chunks),
    ``pallas_in_round`` (a Pallas kernel in the compiled chunk),
    ``round_scopes`` (each top-level instruction of the compiled chunk
    that runs under an ``ltadmm.*`` named scope -> that scope),
    ``losses`` (``[round, mean_loss]`` per logged chunk),
    ``telemetry`` (the counters with ``--telemetry``, else None) and
    ``watchdog`` (the divergence watchdog's ``counters()``, None with
    ``--watchdog-blowup 0``).
    """
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the config to this many layers")
    ap.add_argument("--vocab-rows", type=int, default=None,
                    help="cut the embedding/unembedding to this many rows")
    ap.add_argument("--agents", type=int, default=4)
    ap.add_argument("--solver", default="ltadmm",
                    help=f"solver spec, one of {sorted(SOLVERS)} with "
                         "optional :k=v,... params (e.g. ltadmm:tau=8, "
                         "choco:lr=0.02); CLI hyperparameter flags are "
                         "defaults — spec params win")
    ap.add_argument("--topology", default="ring",
                    help=f"agent graph spec, one of {TOPOLOGIES} with "
                         "optional :k=v,... params (e.g. erdos:p=0.4,seed=1)")
    ap.add_argument("--topology-schedule", default=None,
                    help="time-varying graph spec, one of "
                         f"{SCHEDULES} — e.g. cycle:ring|star, "
                         "drop:p=0.2,base=complete, "
                         "gossip:edges=2,base=ring; overrides --topology")
    ap.add_argument("--m-local", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--tau", type=int, default=3)
    ap.add_argument("--gamma", type=float, default=0.05)
    ap.add_argument("--beta", type=float, default=0.005)
    ap.add_argument("--batch-size", type=int, default=2)
    ap.add_argument("--compressor", default="qbit",
                    choices=["qbit", "randk", "topk", "identity"])
    ap.add_argument("--bits", type=int, default=8)
    ap.add_argument("--fraction", type=float, default=0.25)
    ap.add_argument("--heterogeneity", type=float, default=0.7)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--faults", default=None,
                    help="fault-injection spec, e.g. "
                         "faults:drop=0.05,corrupt=1e-3,crash=0.01,seed=0 "
                         "— seeded message drops / payload bit-flips / "
                         "stale rounds / crash-restarts at the exchange "
                         "boundary (spec faults= param wins)")
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="with --checkpoint PATH: every N rounds also "
                         "write the FULL solver state to PATH.state "
                         "(atomic; resumable via --resume PATH.state)")
    ap.add_argument("--resume", default=None,
                    help="checkpoint dir written by --checkpoint-every; "
                         "continues bitwise-exactly from the saved round")
    ap.add_argument("--watchdog-blowup", type=float, default=1e4,
                    help="divergence watchdog: roll back to the last-good "
                         "state when mean loss is NaN/Inf or exceeds "
                         "blowup x the best seen (0 disables)")
    ap.add_argument("--log-every", type=int, default=1,
                    help="rounds per jitted scan chunk (one host dispatch "
                         "and one metrics eval per chunk; raise for speed)")
    ap.add_argument("--telemetry", action="store_true",
                    help="accumulate in-trace counters (wire bytes, "
                         "messages, fault rejects, participation, grad "
                         "evals) in the solver state; printed as one JSON "
                         "line at the end — trajectories unchanged")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="also write the spans (build, chunks, eval, "
                         "watchdog, log, checkpoints, rollbacks) as "
                         "Chrome-trace JSONL; summarize with python -m "
                         "repro.obs.summary PATH")
    ap.add_argument("--trace-profile", default=None, metavar="DIR",
                    help="with --trace: also capture a jax.profiler "
                         "device trace into DIR over the run")
    args = ap.parse_args(argv)
    if args.checkpoint_every and not args.checkpoint:
        ap.error("--checkpoint-every requires --checkpoint PATH")
    if args.trace_profile and not args.trace:
        ap.error("--trace-profile requires --trace PATH")

    tracer = (trace.Tracer(args.trace, args.trace_profile)
              if args.trace else trace.ANNOTATIONS)
    with tracer.span("train.build", arch=args.arch, solver=args.solver):
        arch, cfg, solver, loss, cut = build(args)
    impl = _compressor_impl(solver)
    if args.telemetry:
        solver = telemetry.with_telemetry(solver)
    ds = SyntheticLMDataset(
        vocab=cfg.vocab, seq_len=args.seq_len, n_agents=args.agents,
        m_local=args.m_local, heterogeneity=args.heterogeneity,
    )
    data = {"tokens": ds.sample(jax.random.key(args.seed))}

    params0 = init_params(jax.random.key(args.seed + 1), model_specs(arch, cfg))
    n_params = param_count(model_specs(arch, cfg))
    print(f"# arch={cfg.name} params={n_params:,} "
          f"agents={args.agents} solver={args.solver} "
          f"topology={args.topology_schedule or args.topology} "
          f"compressor_impl={impl}"
          + (f" reduced={json.dumps(cut)}" if cut else ""))
    # wire accounting: for a time-varying schedule only the links active
    # in a round carry payloads — report the exact round-0 cost alongside
    # the period-mean; static graphs have a single per-round figure.
    # DDP equivalent: one LT-ADMM round covers tau local steps (tau f32
    # all-reduces); one baseline iteration covers one
    tau = getattr(getattr(solver, "cfg", None), "tau", 1)
    ddp = 2 * tau * sum(x.nbytes for x in jax.tree.leaves(params0))
    if isinstance(solver.graph, TopologySchedule):
        print(f"# wire bytes/agent/round: "
              f"{solver.wire_bytes(params0, t=0):,} at round 0, "
              f"{solver.wire_bytes(params0):,} period-mean "
              f"(f32 DDP equivalent: {ddp:,})")
    else:
        print(f"# wire bytes/agent/round: {solver.wire_bytes(params0):,} "
              f"(f32 DDP equivalent: {ddp:,})")
    if hasattr(solver, "degree_cap"):
        # learned-graph solver: the candidate topology only bounds the
        # support — at most degree_cap edges per agent ever carry bytes
        from repro.core.schedule import union_topology
        cand = int(np.max(union_topology(solver.graph).degrees()))
        print(f"# learned graph: degree_cap={solver.degree_cap} live "
              f"edges/agent (candidate degree {cand}), graph round every "
              f"{solver.graph_every} rounds")

    x0 = jax.tree.map(
        lambda t: jnp.broadcast_to(t[None], (args.agents,) + t.shape).copy(),
        params0,
    )
    # init aliases x0 into several state fields (x, x_hat, the neighbor
    # mirrors); donation rejects the same buffer appearing twice, so
    # un-alias once up front — every later chunk gets distinct buffers
    # straight from XLA.
    state = jax.tree.map(jnp.array, solver.init(x0))
    done = 0
    if args.resume:
        # crash-exact resume: all persistent solver state lives in the
        # state tree and round keys are pure functions of the round
        # index, so restoring the tree and the round counter continues
        # the interrupted trajectory bitwise-identically.
        template = jax.eval_shape(solver.init, x0)
        restored, manifest = load_checkpoint(args.resume, like_tree=template)
        state = jax.tree.map(jnp.array, restored)
        done = int(manifest["step"])
        print(f"# resumed from {args.resume} at round {done}")

    # One jitted dispatch per LOG POINT, not per round: scan over the
    # rounds of a chunk, with the solver state donated so XLA reuses the
    # (parameter-sized x edge-slots) state buffers in place across chunks.
    # The data is an argument, not a constant of the program, so every
    # seed shares one executable (and its compilation-cache entry).
    @functools.partial(jax.jit, static_argnums=3, donate_argnums=0)
    def run_chunk(state, data, first_round, n_rounds):
        def body(st, r):
            return solver.step(st, data, jax.random.key(1000 + r)), None

        state, _ = jax.lax.scan(
            body, state, first_round + jnp.arange(n_rounds)
        )
        return state

    compiled = {}
    summary = {"params": n_params, "reduced": cut, "compressor_impl": impl,
               "wire_bytes": solver.wire_bytes(params0), "compile_s": 0.0,
               "pallas_in_round": None, "round_scopes": None, "losses": [],
               "telemetry": None, "watchdog": None}

    def chunk_runner(state, first_round, n):
        """AOT-compile each chunk length once, timing it as set-up."""
        if n not in compiled:
            t0 = time.perf_counter()
            compiled[n] = run_chunk.lower(state, data, first_round,
                                          n).compile()
            summary["compile_s"] += time.perf_counter() - t0
            if summary["pallas_in_round"] is None:
                text = compiled[n].as_text()
                summary["pallas_in_round"] = "tpu_custom_call" in text
                summary["round_scopes"] = hlo_analysis.named_scopes(
                    text, "ltadmm.")
        return compiled[n]

    # the log point's mean loss and consensus error, one program (run
    # eagerly it dispatches hundreds of small ops and lowers the loss's
    # scan again); the tokens are an argument, as in run_chunk
    @jax.jit
    def evaluate(state, tokens):
        x = solver.consensus_params(state)
        pbar = jax.tree.map(lambda t: jnp.mean(t, axis=0), x)
        ls = jax.vmap(lambda d: loss(pbar, {"tokens": d}))(tokens)
        return jnp.stack([jnp.mean(ls), consensus_error(x)])

    watchdog = (DivergenceWatchdog(blowup=args.watchdog_blowup)
                if args.watchdog_blowup > 0 else None)
    t_start = time.time()
    cold = True
    try:
        while done < args.rounds:
            # spans of one chunk share its first round
            r0, n = done, min(args.log_every, args.rounds - done)
            with tracer.span("train.chunk", first_round=r0, rounds=n,
                             cold=cold):
                first = jnp.int32(done)
                state = chunk_runner(state, first, n)(state, data, first)
                if watchdog is not None:
                    # the snapshot's host copy runs beside the eval
                    watchdog.prefetch(state)
                if isinstance(tracer, trace.Tracer):
                    jax.block_until_ready(state)
            cold = False
            done += n
            with tracer.span("train.eval", first_round=r0):
                ml, cerr = (float(v) for v in
                            np.asarray(evaluate(state, data["tokens"])))
            if watchdog is not None:
                with tracer.span("train.watchdog", first_round=r0,
                                 prefetched=watchdog.has_prefetch(state)):
                    state, rolled_back = watchdog.observe(state, ml)
                if rolled_back:
                    # skip-ahead: restore last-good state but keep
                    # advancing rounds — rewinding would
                    # deterministically replay the same divergence
                    tracer.instant("watchdog-rollback", round=done - 1,
                                   mean_loss=ml)
                    print(json.dumps({
                        "round": done - 1, "watchdog": "rollback",
                        "mean_loss": ml, "rollbacks": watchdog.rollbacks,
                    }))
                    continue
            summary["losses"].append([done - 1, ml])
            with tracer.span("train.log", first_round=r0):
                print(json.dumps({
                    "round": done - 1,
                    "mean_loss": round(ml, 4),
                    "consensus_err": cerr,
                    "wall_s": round(time.time() - t_start, 1),
                }))
            if (args.checkpoint_every and done < args.rounds
                    and done % args.checkpoint_every == 0):
                with tracer.span("train.checkpoint", first_round=r0):
                    save_checkpoint(
                        args.checkpoint + ".state", state, step=done,
                        extra={"arch": args.arch, "smoke": args.smoke,
                               "solver": args.solver})
        if args.telemetry:
            tel = {k: np.asarray(v).tolist()
                   for k, v in telemetry.counters(state).items()}
            summary["telemetry"] = tel
            print(json.dumps({"telemetry": tel}))
        if args.checkpoint:
            x = solver.consensus_params(state)
            pbar = jax.tree.map(lambda t: jnp.mean(t, axis=0), x)
            with tracer.span("train.checkpoint", round=args.rounds):
                save_checkpoint(
                    args.checkpoint, pbar, step=args.rounds,
                    extra={"arch": args.arch, "smoke": args.smoke,
                           "solver": args.solver})
            print(f"# checkpoint written to {args.checkpoint}")
    finally:
        tracer.close()
    if watchdog is not None:
        summary["watchdog"] = watchdog.counters()
    return summary


if __name__ == "__main__":
    compile_cache.enable()
    main()
