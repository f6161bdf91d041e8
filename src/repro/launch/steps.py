"""Step builders: solver train_step (LT-ADMM-CC or any registered
baseline), all-reduce DDP train_step, prefill_step and serve_step — each
with full sharding trees for jit.

This is where the paper's algorithms meet the model zoo: the solver state
is a pytree over the *model parameters* with a leading agent axis, the
gradient estimator wraps the model's loss gradient, and the (compressed)
neighbor exchange runs over the mesh agent axis.  ``build_train`` works
for ANY solver in ``core.solver.SOLVERS`` — the solver, like the
topology, is chosen by spec string.
"""
from __future__ import annotations

import collections
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import vr
from repro.core.schedule import build_graph
from repro.core.solver import make_solver, solver_entry
from repro.launch import sharding as shd
from repro.launch.mesh import agent_axis_for
from repro.models import encdec, transformer as tr
from repro.models.common import abstract_params
from repro.optim import optimizers


# ---------------------------------------------------------------------------
# Model plumbing
# ---------------------------------------------------------------------------


def model_specs(arch_def, cfg):
    if arch_def.kind == "encdec":
        return encdec.model_specs(cfg)
    return tr.model_specs(cfg)


def model_loss(arch_def, cfg):
    if arch_def.kind == "encdec":
        return lambda p, b: encdec.loss_fn(p, cfg, b)
    return lambda p, b: tr.loss_fn(p, cfg, b)


# ---------------------------------------------------------------------------
# Solver train step (LT-ADMM-CC + every registered baseline)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TrainRecipe:
    """Transformer-scale solver defaults.

    gamma is much smaller than the convex-experiment value (0.3): L for a
    transformer loss is far larger.  batch_size counts sequences per inner
    step out of the agent's m_local.  Every field is a DEFAULT — params in
    the solver spec string given to ``build_train`` win.
    """

    rho: float = 0.1
    beta: float = 0.01
    gamma: float = 0.02
    r: float = 1.0
    eta: float = 1.0
    tau: int = 5
    batch_size: int = 4
    # compressor spec string ("qbit:bits=4", "randk:fraction=0.25,
    # sampler=block", ...); paper Fig.2 default: 8-bit quantizer
    compressor: str = "qbit"
    # agent graph spec — anything accepted by schedule.make_graph: a static
    # family ("ring", "grid2d", "star", "complete", "erdos:p=0.3", ...) or a
    # time-varying schedule ("cycle:ring|star", "drop:p=0.2,base=complete",
    # "gossip:edges=2,base=ring").  Ring and grid2d map to single-hop CPs on
    # an ICI torus axis; the others lower to one CP per neighbor slot; a
    # schedule compiles its union graph's slots once and masks per round.
    topology: str = "ring"
    # §Perf: sequentialize the SVRG anchor full-gradient over m_local in
    # this many microbatches (lax.map) — bounds live activation memory at
    # the cost of a scan (1 = single fused pass)
    anchor_microbatches: int = 1

    def solver_defaults(self, solver_name: str) -> dict:
        """Fallback params for ``make_solver`` (spec params override;
        keys a solver does not accept are dropped there)."""
        if solver_name == "ltadmm":
            return {
                "rho": self.rho,
                "beta": self.beta,
                "gamma": self.gamma,
                "r": self.r,
                "eta": self.eta,
                "tau": self.tau,
                "batch_size": self.batch_size,
                "compressor": self.compressor,
            }
        return {
            "batch_size": self.batch_size,
            "compressor": self.compressor,
        }


def build_estimator(arch_def, cfg, recipe: TrainRecipe, kind: str):
    """Gradient estimator over the model loss: ``"vr"`` -> SVRG anchor
    (optionally microbatched over m_local), ``"sgd"`` -> plain minibatch
    gradients (the regime where the paper's baselines plateau)."""
    grad_fn = jax.grad(model_loss(arch_def, cfg))
    if kind != "vr":
        return vr.PlainSgd(batch_grad=grad_fn)
    if recipe.anchor_microbatches > 1:
        nmb = recipe.anchor_microbatches

        def full_grad(params, data):
            m = jax.tree.leaves(data)[0].shape[0]
            assert m % nmb == 0, (m, nmb)
            chunked = jax.tree.map(
                lambda x: x.reshape((nmb, m // nmb) + x.shape[1:]), data
            )
            grads = jax.lax.map(lambda c: grad_fn(params, c), chunked)
            return jax.tree.map(lambda g: jnp.mean(g, axis=0), grads)
    else:
        full_grad = grad_fn
    return vr.SvrgAnchor(batch_grad=grad_fn, full_grad=full_grad)


def build_train(arch_def, cfg, mesh, solver_spec: str,
                recipe: TrainRecipe | None = None):
    """Train-step builder for ANY registered solver.

    Returns ``(step_fn, state_sharding, init_fn, solver)``:
    ``step_fn(state, data, seed)`` advances one outer round,
    ``state_sharding`` is the jit in/out sharding tree,
    ``init_fn(x0_stacked)`` builds the state from stacked ``[A, ...]``
    params, and ``solver`` carries the graph/config/accounting hooks.
    The recipe supplies topology + hyperparameter defaults; params in
    ``solver_spec`` win.
    """
    recipe = recipe or TrainRecipe()
    aaxis = agent_axis_for(mesh)
    n_agents = mesh.shape[aaxis]
    graph, exchange = build_graph(recipe.topology, n_agents,
                                  axis=aaxis, mesh=mesh)
    entry = solver_entry(solver_spec)
    est = build_estimator(arch_def, cfg, recipe, entry.estimator)
    solver = make_solver(solver_spec, graph, exchange, est,
                         defaults=recipe.solver_defaults(entry.name))

    def step_fn(state, data, seed):
        return solver.step(state, data, jax.random.PRNGKey(seed))

    # ---- shardings ---------------------------------------------------------
    if getattr(solver, "packed", False):
        # packed plane: the parameter dim is flattened into one [A, N]
        # buffer — shard over the agent axis, plane replicated elsewhere
        # (per-leaf TP shardings need the pytree path: spec packed=false)
        x_ps = P(aaxis)
        edge_ps = P(aaxis, None)
    else:
        specs = model_specs(arch_def, cfg)
        pps = shd.param_pspec(mesh, "admm", specs)
        x_ps = shd.prefix_pspec(pps, aaxis)  # [A, ...]
        edge_ps = shd.prefix_pspec(pps, aaxis, None)  # [A, S, ...]
    state_ps = solver.state_sharding(x_ps, edge_ps, P())
    return step_fn, state_ps, solver.init, solver


def _host_copy(leaf) -> np.ndarray:
    """One host copy of ``leaf`` that the caller owns.

    On an accelerator ``np.asarray`` hands back the host buffer the
    device-to-host transfer filled (the one ``copy_to_host_async``
    started), with no second copy; the array caches it too, until its
    buffers are deleted.  A CPU array's ``np.asarray`` is a view of the
    live device buffer, which donation would take from under the ring,
    so there one explicit copy is made.
    """
    if isinstance(leaf, jax.Array) and all(
            d.platform != "cpu" for d in leaf.devices()):
        return np.asarray(leaf)
    return np.array(leaf)


class DivergenceWatchdog:
    """Divergence detection + rollback to a last-good snapshot ring.

    Host-side companion of the fault plane: after every logged chunk the
    driver reports ``(state, metric)``; a NaN/Inf metric or a blow-up
    beyond ``blowup x`` the best metric seen marks the window poisoned
    and rolls the solver state back to the OLDEST snapshot in the ring
    (the state most distant from the divergence).  Healthy states are
    snapshotted as host-memory copies: the ring survives donation of
    the live state by the jitted chunk runner and costs no device
    memory (at model scale one solver state is several GB, and
    ``depth`` device copies would not fit beside it).

    Prefetch: ``prefetch(state)`` right after the chunk that produces
    ``state`` is dispatched starts every leaf's device-to-host copy
    (``copy_to_host_async``), so the transfer runs as soon as the chunk
    finishes on the device, beside the training loop's evaluation of the
    metric.  ``observe`` on that same state then only waits for it.
    Either way each snapshot is exactly ONE host copy per leaf, owned by
    the ring (``_host_copy``): the transfer's own buffer on an
    accelerator, one explicit copy on CPU.  At an unhealthy point the
    prefetched tree is dropped unread.  ``observe`` without a prefetch
    copies synchronously, as before.

    Rollback does NOT rewind the round counter: the driver keeps
    advancing rounds, so the replayed trajectory diverges from the
    poisoned one (with deterministic per-round keys, rewinding would
    replay the identical divergence forever).  ``max_consecutive``
    rollbacks without an intervening healthy window raise — a watchdog
    that cannot re-stabilize should fail loudly, not spin.

    Counters (``counters()``): ``snapshots`` taken, of them
    ``prefetched`` (copy started before ``observe``), ``discarded``
    prefetches (dropped at an unhealthy point), ``rollbacks`` and
    ``snapshot_bytes`` (host bytes of all snapshots taken).
    """

    def __init__(self, depth: int = 3, blowup: float = 1e4,
                 max_consecutive: int = 3):
        assert depth >= 1 and blowup > 1.0, (depth, blowup)
        self.blowup = float(blowup)
        self.max_consecutive = max_consecutive
        self._ring = collections.deque(maxlen=depth)
        self._best = math.inf
        self._consecutive = 0
        self._pending = None
        self.snapshots = 0
        self.prefetched = 0
        self.discarded = 0
        self.rollbacks = 0
        self.snapshot_bytes = 0

    def _bad(self, m: float) -> bool:
        if not math.isfinite(m):
            return True
        return (math.isfinite(self._best)
                and m > self.blowup * max(self._best, 1e-12))

    def prefetch(self, state) -> None:
        """Start the host copy of every leaf of ``state`` without
        waiting for it; the next ``observe(state, ...)`` takes it."""
        for leaf in jax.tree.leaves(state):
            if isinstance(leaf, jax.Array):
                leaf.copy_to_host_async()
        self._pending = state

    def has_prefetch(self, state) -> bool:
        """Whether ``observe(state, ...)`` finds its copy started."""
        return self._pending is state

    def observe(self, state, metric):
        """-> ``(state, rolled_back)``: the input state (now snapshotted)
        when healthy, else the last-good rollback state."""
        m = float(metric)
        prefetched = self.has_prefetch(state)
        self._pending = None
        if not self._bad(m):
            self._best = min(self._best, m)
            snap = jax.tree.map(_host_copy, state)
            self._ring.append(snap)
            self.snapshots += 1
            self.prefetched += prefetched
            self.snapshot_bytes += sum(t.nbytes for t in jax.tree.leaves(snap))
            self._consecutive = 0
            return state, False
        self.discarded += prefetched
        self.rollbacks += 1
        self._consecutive += 1
        if not self._ring:
            raise RuntimeError(
                f"divergence (metric={m}) before any healthy snapshot")
        if self._consecutive > self.max_consecutive:
            raise RuntimeError(
                f"divergence watchdog: {self._consecutive} consecutive "
                f"rollbacks without re-stabilizing (metric={m})")
        # fresh device buffers: the caller's jitted chunk donates its
        # input, and the ring entry must survive a second rollback
        return jax.tree.map(jnp.asarray, self._ring[0]), True

    def counters(self) -> dict:
        """How often each path engaged (see the class docstring)."""
        return {"snapshots": self.snapshots, "prefetched": self.prefetched,
                "discarded": self.discarded, "rollbacks": self.rollbacks,
                "snapshot_bytes": self.snapshot_bytes}


def abstract_train_state(arch_def, cfg, solver):
    """Abstract solver state for lowering (no allocation)."""
    specs = model_specs(arch_def, cfg)
    ap = abstract_params(specs, cfg.dtype)
    a = solver.graph.n_agents
    x_sds = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((a,) + s.shape, s.dtype), ap
    )
    return solver.abstract_state(x_sds)


# ---------------------------------------------------------------------------
# All-reduce DDP baseline train step (what the paper's method replaces)
# ---------------------------------------------------------------------------


def build_ddp_train(arch_def, cfg, mesh, lr=1e-3):
    """Standard data-parallel Adam training step; data [B, ...] global."""
    loss = model_loss(arch_def, cfg)
    opt = optimizers.adam(lr)

    def step_fn(params, opt_state, batch, seed):
        del seed
        loss_val, grads = jax.value_and_grad(loss)(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optimizers.apply_updates(params, updates)
        return params, opt_state, loss_val

    specs = model_specs(arch_def, cfg)
    pps = shd.param_pspec(mesh, "serve", specs)  # TP + FSDP
    return step_fn, pps, opt


# ---------------------------------------------------------------------------
# Inference steps
# ---------------------------------------------------------------------------


def build_prefill(arch_def, cfg, mesh, mode="serve"):
    if arch_def.kind == "encdec":

        def prefill(params, batch):
            logits = encdec.forward(
                params, cfg, batch["src_embeds"], batch["tgt_tokens"]
            )
            return logits[:, -1:, :]

    else:

        def prefill(params, batch):
            logits, _ = tr.forward(
                params,
                cfg,
                tokens=batch.get("tokens"),
                embeds=batch.get("embeds"),
            )
            return logits[:, -1:, :]

    specs = model_specs(arch_def, cfg)
    pps = shd.param_pspec(mesh, mode, specs)
    return prefill, pps


def build_serve(arch_def, cfg, mesh, mode="serve"):
    """One-token decode step (the decode_32k / long_500k shapes)."""
    if arch_def.kind == "encdec":

        def serve(params, cache, batch):
            logits, cache = encdec.decode_step(
                params, cfg, cache, batch["token"], batch["pos"]
            )
            return logits, cache

        def abstract_cache(params_sds, data_specs):
            return jax.eval_shape(
                lambda p, m: encdec.init_cache(
                    p, cfg, m, data_specs["_max_len"]
                ),
                params_sds,
                data_specs["memory"],
            )

    else:

        def serve(params, cache, batch):
            logits, cache = tr.decode_step(
                params, cfg, cache, token=batch["token"], pos=batch["pos"]
            )
            return logits, cache

        def abstract_cache(params_sds, data_specs):
            b = data_specs["token"].shape[0]
            return jax.eval_shape(
                lambda: tr.init_cache(cfg, b, data_specs["_max_len"])
            )

    specs = model_specs(arch_def, cfg)
    pps = shd.param_pspec(mesh, mode, specs)
    return serve, pps, abstract_cache
