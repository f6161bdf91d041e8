"""LT-ADMM-CC (paper Algorithm 1) on arbitrary parameter pytrees.

Global-view formulation: every state tensor carries a leading **agent axis**
``A``; per-agent math is ``vmap``-ed and the only cross-agent operations are
the two neighbor exchanges (x-messages and z-messages) routed through
``topology.Exchange`` — collective-permutes on the mesh agent axis in
production, a gather-by-index in host simulation.  All graph structure
(neighbor slots, per-agent degrees, slot masks) comes from the
``topology.Topology`` object — ring, torus, star, complete and random
graphs all run through this one implementation.  The same code therefore
runs:

* on one CPU device (paper-scale repro and tests),
* sharded over the ``data`` axis of a 16x16 pod (agents = data slices),
* sharded over the ``pod`` axis of a 2x16x16 multi-pod mesh (agents = pods,
  FSDP+TP inside each pod) — the hierarchical beyond-paper mode.

State indexing convention at the top of round k:

    x         = x_{i,k}           x_hat     = x̂_{i,k}       u     = u_{i,k}
    z[:,s]    = z_{i j_s,k}       s_[:,s]   = s_{i j_s,k}
    s_tilde   = mirror of s_{j_s i,k}
    x_hat_nbr = x̂_{j_s,k}         u_nbr     = mirror of u_{j_s,k}

Round-k timeline (audited against Algorithm 1):
  1. local phase (lines 2-8, eqs. (7)-(8)):  x_{k+1} from x_k, z_k
  2. u_{k+1} = (1-eta) u_k + eta x̂_k                                   (6)
  3. m_x = C(x_{k+1} - u_{k+1})   transmitted                    (line 10)
  4. x̂_{k+1} = u_{k+1} + m_x                                          (5a)
  5. m_z = C(z_{ij,k} - s_{ij,k}) transmitted                    (line 10)
  6. ẑ_{ij,k} = s_{ij,k} + m_z ;  s_{ij,k+1} = ẑ_{ij,k}           (5b),(6)
  7. receiver mirrors: u_{j,k+1}, x̂_{j,k+1}, ẑ_{ji,k}, s̃_{k+1}  (line 11)
  8. z_{ij,k+1} = ½(ẑ_{ij,k} - ẑ_{ji,k}) + rρ x_{i,k+1}
                  - rρ (x̂_{i,k+1} - x̂_{j,k+1})                        (4)

Initialization (any common or heterogeneous x_0): u_0 = x_0, x̂_0 = x_0,
z_0 = s_0 = s̃_0 = 0.  Message-consistent because C(0) = 0 exactly for every
implemented compressor.

With eta == 1 (the paper's experiments), u_{k+1} == x̂_k, so u/u_nbr need not
be stored ("lean" mode — 3 fewer parameter-sized buffers per agent).
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

import numpy as np

from repro.common.trees import (
    tree_consensus_error,
    tree_consensus_mean,
    tree_lerp,
    tree_map,
    tree_sub,
    tree_zeros_like,
)
from repro.core import compression
from repro.core.topology import Exchange, Topology
from repro.obs import telemetry


@dataclasses.dataclass(frozen=True)
class LTADMMConfig:
    """Hyper-parameters of Algorithm 1 (defaults = paper §III)."""

    rho: float = 0.1  # ADMM penalty
    beta: float = 0.2  # local-training regularization weight
    gamma: float = 0.3  # local step size
    r: float = 1.0  # relaxation
    eta: float = 1.0  # error-feedback EMA rate, in (0, 1]
    tau: int = 5  # local steps between communication rounds
    batch_size: int = 1  # |B_i|
    compressor_x: Any = compression.Identity()
    compressor_z: Any = compression.Identity()
    # core.faults.FaultPlane | None: payloads are sealed (crc + round
    # tag), exchanges fault-injected, and detected failures downgrade
    # edges to the async-ADMM hold — packed schedule path only
    faults: Any = None

    @property
    def lean(self) -> bool:
        return self.eta == 1.0


class LTADMMState(NamedTuple):
    x: Any  # [A, ...]
    x_hat: Any  # [A, ...]
    u: Any  # [A, ...] | None (lean)
    z: Any  # [A, S, ...]
    s: Any  # [A, S, ...]
    s_tilde: Any  # [A, S, ...]
    x_hat_nbr: Any  # [A, S, ...]
    u_nbr: Any  # [A, S, ...] | None (lean)
    k: jax.Array


def _stack_slots(per_slot):
    return tree_map(lambda *xs: jnp.stack(xs, axis=1), *per_slot)


def _slot(tree, s):
    return tree_map(lambda x: x[:, s], tree)


_LEAF_STRUCT = jax.tree.structure(0)


def _is_packed(x) -> bool:
    """True when the per-agent parameters are a single flat array (the
    ``core.packing`` plane) rather than a pytree — selects the
    slot-batched hot path."""
    return jax.tree.structure(x) == _LEAF_STRUCT


def init(cfg: LTADMMConfig, topo: Topology, exchange: Exchange, x0):
    """x0: params with leading agent axis [A, ...].

    ``topo`` may be a ``schedule.TopologySchedule`` — dispatches to the
    time-varying state (``init_schedule``)."""
    if hasattr(topo, "round_mask"):
        return init_schedule(cfg, topo, exchange, x0)
    zeros_edge = _stack_slots(
        tuple(tree_zeros_like(x0) for _ in range(topo.n_slots))
    )
    x_hat_nbr = _stack_slots(exchange.gather_from_neighbors(x0))
    return LTADMMState(
        x=x0,
        x_hat=x0,
        u=None if cfg.lean else x0,
        z=zeros_edge,
        s=zeros_edge,
        s_tilde=zeros_edge,
        x_hat_nbr=x_hat_nbr,
        u_nbr=None if cfg.lean else x_hat_nbr,
        k=jnp.zeros((), jnp.int32),
    )


# ---------------------------------------------------------------------------
# Message-key derivation — sender and receiver MUST derive identical keys
# (this is what lets RandK keep indices off the wire entirely).
# ---------------------------------------------------------------------------


def _key_x(round_key, sender):
    return jax.random.fold_in(jax.random.fold_in(round_key, 11), sender)


def _key_z(round_key, sender, receiver):
    k = jax.random.fold_in(round_key, 13)
    return jax.random.fold_in(jax.random.fold_in(k, sender), receiver)


def _key_batch(round_key, agent, t):
    k = jax.random.fold_in(round_key, 7)
    return jax.random.fold_in(jax.random.fold_in(k, agent), t)


def _key_xe(round_key, sender, receiver):
    """Per-edge x-message key (time-varying schedules): over link
    failures the x error-feedback stream is PER EDGE, so the key folds
    in both endpoints like a z-message (distinct salt)."""
    k = jax.random.fold_in(round_key, 17)
    return jax.random.fold_in(jax.random.fold_in(k, sender), receiver)


def _like_per_agent(stacked):
    """[A, ...] tree -> per-agent ShapeDtypeStruct template."""
    return tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), stacked
    )


def local_phase(cfg: LTADMMConfig, topo: Topology, vr_est, x, z, data,
                round_key):
    """Lines 2-8: tau VR-gradient steps per agent.  Returns x_{k+1} [A,...].

    ``d_i`` is the per-agent degree vector of the topology — heterogeneous
    for non-regular graphs (star, random) — broadcast over the parameter
    dims of each leaf.  ``z`` is zero on masked slots, so the plain slot-sum
    is the sum over actual incident edges.
    """
    A = jax.tree.leaves(x)[0].shape[0]
    m = jax.tree.leaves(data)[0].shape[1]
    d = jnp.asarray(topo.degrees(), jax.tree.leaves(x)[0].dtype)
    z_sum = tree_map(lambda t: jnp.sum(t, axis=1), z)
    corr = tree_map(
        lambda xs, zs: cfg.beta * (
            cfg.r**2 * cfg.rho * d.reshape((A,) + (1,) * (xs.ndim - 1)) * xs
            - cfg.r * zs
        ),
        x,
        z_sum,
    )

    def one_agent(x_i, corr_i, data_i, aid):
        vr_state = vr_est.reset(x_i, data_i)

        def body(carry, t):
            phi, vrs = carry
            idx = jax.random.randint(
                _key_batch(round_key, aid, t), (cfg.batch_size,), 0, m
            )
            g, vrs = vr_est.estimate(vrs, phi, data_i, idx)
            phi = tree_map(
                lambda p, gg, c: p - cfg.gamma * gg - c, phi, g, corr_i
            )
            return (phi, vrs), None

        (phi, _), _ = jax.lax.scan(body, (x_i, vr_state), jnp.arange(cfg.tau))
        return phi

    return jax.vmap(one_agent)(x, corr, data, jnp.arange(A))


def _mask_slot(tree, mask_s):
    """Zero a per-slot [A, ...] tree where the slot is inactive (static
    host-numpy masks only; the time-varying path gates with
    ``_select_slot`` on a traced mask instead)."""
    if bool(np.all(mask_s)):
        return tree
    m = np.asarray(mask_s)
    return tree_map(
        lambda t: jnp.where(m.reshape((m.shape[0],) + (1,) * (t.ndim - 1)),
                            t, 0), tree
    )


def _select_slot(mask_s, on_tree, off_tree):
    """Per-agent select on a slot tree: agent i takes ``on_tree`` where
    ``mask_s[i]`` (edge active this round), ``off_tree`` (held state)
    otherwise."""
    return tree_map(
        lambda a, b: jnp.where(
            jnp.reshape(mask_s, (a.shape[0],) + (1,) * (a.ndim - 1)), a, b
        ),
        on_tree,
        off_tree,
    )


def _select_agents(node_mask, on_tree, off_tree):
    """Per-agent select on an ``[A, ...]`` tree: agent i advances where
    ``node_mask[i]`` (participating this round), holds otherwise.
    ``node_mask is None`` (no node layer) keeps ``on_tree`` untouched,
    so edge-only schedules compile the exact program they always did."""
    if node_mask is None:
        return on_tree
    return _select_slot(node_mask, on_tree, off_tree)


def _emit_round_telemetry(cfg, vr_est, data, deg, per_msg, node_k, *, A,
                          fault_counters=None):
    """Telemetry tap shared by the four round implementations: charge
    each agent its active-degree messages (``per_msg`` measured bytes
    for the x+z pair), its participation, and the local phase's
    grad-eval recipe.  Only reached when a ``with_telemetry`` wrapper is
    tracing (``telemetry.active()``) — plain uint32 adds, no host sync."""
    part = (jnp.ones((A,), jnp.uint32) if node_k is None
            else node_k.astype(jnp.uint32))
    m = jax.tree.leaves(data)[0].shape[1]
    evals = telemetry.local_phase_evals(vr_est, m, cfg.tau, cfg.batch_size)
    counters = dict(
        tx_bytes=deg * jnp.uint32(per_msg),
        tx_msgs=deg * jnp.uint32(2),
        participations=part,
        grad_evals=jnp.uint32(evals) * part,
    )
    if fault_counters:
        counters.update(fault_counters)
    telemetry.emit(**counters)


def step(
    cfg: LTADMMConfig,
    topo: Topology,
    exchange: Exchange,
    vr_est,
    state: LTADMMState,
    data,
    round_key,
):
    """One outer round of Algorithm 1.  ``data`` leaves: [A, m, ...].

    All graph structure comes from ``topo``: slot ``sl`` of agent ``i``
    names the incident edge to ``neighbor_table()[i, sl]`` (or is masked).
    Masked slots still move a (self-addressed) message through the
    exchange so both Exchange implementations stay bit-identical, but all
    edge state on them is forced to zero, which makes the slot-sum in
    ``local_phase`` and the stored s/s̃ mirrors exact for heterogeneous
    degrees.

    ``topo`` may be a ``schedule.TopologySchedule`` — dispatches to the
    time-varying round (``step_schedule``).  When the per-agent
    parameters are a single flat array (the ``core.packing`` plane), the
    round runs slot-batched (``_step_packed``): identical math, one
    ``[A, S, N]`` expression per update instead of a Python slot loop.
    """
    if hasattr(topo, "round_mask"):
        return step_schedule(cfg, topo, exchange, vr_est, state, data,
                             round_key)
    if cfg.faults is not None:
        raise ValueError(
            "cfg.faults requires a TopologySchedule (the hold semantics "
            "live on the schedule path); wrap static graphs with "
            "schedule.static_schedule — make_solver does this "
            "automatically")
    if _is_packed(state.x):
        return _step_packed(cfg, topo, exchange, vr_est, state, data,
                            round_key)
    return _step_tree(cfg, topo, exchange, vr_est, state, data, round_key)


def _step_tree(
    cfg: LTADMMConfig,
    topo: Topology,
    exchange: Exchange,
    vr_est,
    state: LTADMMState,
    data,
    round_key,
):
    """Pytree-state round: per-leaf compression, Python loop over slots.

    Kept alongside the packed path for models whose parameter plane must
    stay a pytree (per-leaf compression scales, tensor-parallel leaf
    shardings); bit-identical to ``_step_packed`` on single-leaf trees
    (pinned by tests/test_packing.py)."""
    A = topo.n_agents
    agent_ids = jnp.arange(A)
    like = _like_per_agent(state.x)
    cx, cz = cfg.compressor_x, cfg.compressor_z
    nbr_table = topo.neighbor_table()  # [A, S] numpy, self where masked
    slot_mask = topo.slot_mask()  # [A, S] numpy bool

    # ---- 1. local training ------------------------------------------------
    x_new = local_phase(cfg, topo, vr_est, state.x, state.z, data, round_key)

    # ---- 2-4. sender-side error feedback for x ----------------------------
    u_new = (
        state.x_hat
        if cfg.lean
        else tree_lerp(state.u, state.x_hat, cfg.eta)
    )

    def compress_x(aid, delta):
        kx = _key_x(round_key, aid)
        p = compression.compress_tree(cx, kx, delta)
        rec = compression.decompress_tree(cx, kx, p, like)
        return p, rec

    m_x, dx = jax.vmap(compress_x)(agent_ids, tree_sub(x_new, u_new))
    x_hat_new = tree_map(jnp.add, u_new, dx)

    # ---- 5-6. sender-side error feedback for z (per edge slot) ------------
    nbr_ids = [jnp.asarray(nbr_table[:, sl]) for sl in range(topo.n_slots)]
    m_z, z_hat_own = [], []
    for sl in range(topo.n_slots):
        def compress_z(aid, nid, delta):
            kz = _key_z(round_key, aid, nid)
            p = compression.compress_tree(cz, kz, delta)
            rec = compression.decompress_tree(cz, kz, p, like)
            return p, rec

        delta = tree_sub(_slot(state.z, sl), _slot(state.s, sl))
        p, rec = jax.vmap(compress_z)(agent_ids, nbr_ids[sl], delta)
        m_z.append(p)
        z_hat_own.append(
            _mask_slot(tree_map(jnp.add, _slot(state.s, sl), rec),
                       slot_mask[:, sl])
        )

    # ---- the only cross-agent communication --------------------------------
    recv_x = exchange.gather_from_neighbors(m_x)
    recv_z = exchange.exchange_edges(tuple(m_z))

    if telemetry.active() and m_z:
        deg = jnp.asarray(np.asarray(slot_mask).sum(axis=1), jnp.uint32)
        per_msg = (telemetry.payload_nbytes(m_x, nd=1)
                   + telemetry.payload_nbytes(m_z[0], nd=1))
        _emit_round_telemetry(cfg, vr_est, data, deg, per_msg, None, A=A)

    # ---- 7. receiver-side mirrors ------------------------------------------
    u_nbr_new = (
        state.x_hat_nbr
        if cfg.lean
        else tree_lerp(state.u_nbr, state.x_hat_nbr, cfg.eta)
    )
    x_hat_nbr_new, z_hat_nbr = [], []
    for sl in range(topo.n_slots):
        def decomp_x(sid, payload):
            return compression.decompress_tree(
                cx, _key_x(round_key, sid), payload, like
            )

        dxr = jax.vmap(decomp_x)(nbr_ids[sl], recv_x[sl])
        x_hat_nbr_new.append(
            tree_map(jnp.add, _slot(u_nbr_new, sl), dxr)
        )

        def decomp_z(sid, rid, payload):
            return compression.decompress_tree(
                cz, _key_z(round_key, sid, rid), payload, like
            )

        dzr = jax.vmap(decomp_z)(nbr_ids[sl], agent_ids, recv_z[sl])
        z_hat_nbr.append(
            _mask_slot(tree_map(jnp.add, _slot(state.s_tilde, sl), dzr),
                       slot_mask[:, sl])
        )

    # ---- 8. z update, eq. (4) ----------------------------------------------
    z_new = []
    rrho = cfg.r * cfg.rho
    for sl in range(topo.n_slots):
        z_new.append(
            _mask_slot(
                tree_map(
                    lambda zo, zn, xn, xh, xhj: 0.5 * (zo - zn)
                    + rrho * xn
                    - rrho * (xh - xhj),
                    z_hat_own[sl],
                    z_hat_nbr[sl],
                    x_new,
                    x_hat_new,
                    x_hat_nbr_new[sl],
                ),
                slot_mask[:, sl],
            )
        )

    return LTADMMState(
        x=x_new,
        x_hat=x_hat_new,
        u=None if cfg.lean else u_new,
        z=_stack_slots(tuple(z_new)),
        s=_stack_slots(tuple(z_hat_own)),
        s_tilde=_stack_slots(tuple(z_hat_nbr)),
        x_hat_nbr=_stack_slots(tuple(x_hat_nbr_new)),
        u_nbr=None if cfg.lean else u_nbr_new,
        k=state.k + 1,
    )


# ---------------------------------------------------------------------------
# Packed-plane hot path (core.packing): slot-batched [A, S, N] round
# ---------------------------------------------------------------------------


def _edge_mask(mask) -> jnp.ndarray | None:
    """[A, S] slot mask -> broadcastable [A, S, 1] (None when all-active,
    so fully-regular graphs pay no select at all)."""
    if bool(np.all(mask)):
        return None
    return jnp.asarray(mask)[:, :, None]


def _masked(arr, mask3):
    return arr if mask3 is None else jnp.where(mask3, arr, 0.0)


def _step_packed(
    cfg: LTADMMConfig,
    topo: Topology,
    exchange: Exchange,
    vr_est,
    state: LTADMMState,
    data,
    round_key,
):
    """Slot-batched round on the packed plane: state leaves are single
    arrays (x: ``[A, N]``, edge state: ``[A, S, N]``).

    Same math as ``_step_tree`` — each per-slot ``tree_map`` becomes one
    vectorized expression over the slot axis, the whole z-exchange ONE
    batched routing call, and compression ONE ``plane_compress`` per
    message class: a single fused Pallas launch (in-kernel counter-PRNG
    randomness, no index arrays in HBM) when the compressor resolves to
    ``impl=pallas`` and supports it, else the bit-identical vmapped
    per-(agent, slot) path."""
    A, S = topo.n_agents, topo.n_slots
    agent_ids = jnp.arange(A)
    aid2 = jnp.broadcast_to(agent_ids[:, None], (A, S))
    like = jax.ShapeDtypeStruct(state.x.shape[1:], state.x.dtype)
    cx, cz = cfg.compressor_x, cfg.compressor_z
    nbr = jnp.asarray(topo.neighbor_table())  # [A, S]
    mask3 = _edge_mask(topo.slot_mask())
    # fused-path base seeds: same salts as _key_x/_key_z, folded once
    # here and per (sender, receiver) inside the kernel
    bx = jax.random.fold_in(round_key, 11)
    bz = jax.random.fold_in(round_key, 13)

    # ---- 1. local training ------------------------------------------------
    with jax.named_scope("ltadmm.local"):
        x_new = local_phase(cfg, topo, vr_est, state.x, state.z, data,
                            round_key)

    with jax.named_scope("ltadmm.compress"):
        # ---- 2-4. sender-side error feedback for x ------------------------
        u_new = (
            state.x_hat
            if cfg.lean
            else tree_lerp(state.u, state.x_hat, cfg.eta)
        )

        # x is broadcast to every neighbor: one payload per SENDER
        m_x, dx = compression.plane_compress(
            cx, lambda aid: _key_x(round_key, aid), bx,
            agent_ids, None, x_new - u_new, like, exchange=exchange,
        )
        x_hat_new = u_new + dx

        # ---- 5-6. sender-side error feedback for z (all slots at once) ----
        m_z, rec_z = compression.plane_compress(
            cz, lambda aid, nid: _key_z(round_key, aid, nid), bz,
            aid2, nbr, state.z - state.s, like, exchange=exchange,
        )
        z_hat_own = _masked(state.s + rec_z, mask3)

    # ---- the only cross-agent communication -------------------------------
    with jax.named_scope("ltadmm.exchange"):
        recv_x = exchange.gather_batched(m_x)  # payload leaves [A, S, ...]
        recv_z = exchange.exchange_batched(m_z)

    if telemetry.active():
        # one x-message to every neighbor + one z-message per edge;
        # masked union slots move self-addressed placeholders and are
        # not charged, matching the analytic wire accounting
        deg = jnp.asarray(np.asarray(topo.slot_mask()).sum(axis=1),
                          jnp.uint32)
        per_msg = (telemetry.payload_nbytes(m_x, nd=1)
                   + telemetry.payload_nbytes(m_z, nd=2))
        _emit_round_telemetry(cfg, vr_est, data, deg, per_msg, None, A=A)

    with jax.named_scope("ltadmm.update"):
        # ---- 7. receiver-side mirrors -------------------------------------
        u_nbr_new = (
            state.x_hat_nbr
            if cfg.lean
            else tree_lerp(state.u_nbr, state.x_hat_nbr, cfg.eta)
        )

        x_hat_nbr_new = u_nbr_new + compression.plane_decompress(
            cx, lambda sid: _key_x(round_key, sid), bx,
            nbr, None, recv_x, like, nd=2, exchange=exchange,
        )

        z_hat_nbr = _masked(
            state.s_tilde + compression.plane_decompress(
                cz, lambda sid, rid: _key_z(round_key, sid, rid), bz,
                nbr, aid2, recv_z, like, nd=2, exchange=exchange,
            ),
            mask3,
        )

        # ---- 8. z update, eq. (4) — one fused [A, S, N] expression --------
        rrho = cfg.r * cfg.rho
        z_new = _masked(
            0.5 * (z_hat_own - z_hat_nbr)
            + rrho * x_new[:, None]
            - rrho * (x_hat_new[:, None] - x_hat_nbr_new),
            mask3,
        )

    return LTADMMState(
        x=x_new,
        x_hat=x_hat_new,
        u=None if cfg.lean else u_new,
        z=z_new,
        s=z_hat_own,
        s_tilde=z_hat_nbr,
        x_hat_nbr=x_hat_nbr_new,
        u_nbr=None if cfg.lean else u_nbr_new,
        k=state.k + 1,
    )


# ---------------------------------------------------------------------------
# Time-varying topologies (schedule.TopologySchedule)
# ---------------------------------------------------------------------------
#
# Asynchronous-ADMM semantics (Wei & Ozdaglar): round k activates the
# edge subset sched.round_mask(k) of the UNION graph.  On inactive edges
# both endpoints hold all edge state (z, s, s̃, and the error-feedback
# mirrors) and ignore the exchanged payloads; the local x-update keeps
# the union degrees and the full (held) dual sum, so the static
# union-graph fixed point satisfies every round's update and exact
# convergence survives under persistent activation.
#
# Node-level participation (sched.round_node_mask(k), None when the
# schedule has no node layer) extends the same argument to flapping
# AGENTS: an inactive node freezes its x and skips its tau local epochs
# on top of the held edge state — its incident slots are all off by
# construction (schedule builders merge the node mask into the edge
# masks), so the per-edge holds below need no extra gating, and the
# static fixed point (where x_{k+1} = x_k) still satisfies every
# round's update.  Persistent node activation is what validate_schedule
# checks in place of per-edge persistence alone.
#
# One structural change vs. the static state: over link failures the
# x-message error-feedback stream desynchronizes if x̂ is per agent (a
# neighbor that missed a round can never resync, because later deltas
# are relative to the sender's CURRENT x̂).  The schedule state therefore
# carries x̂ (and u) PER EDGE — x_hat_edge[:, s] is the sender-side
# estimate mirrored by the slot-s neighbor — updated only on rounds the
# edge is active, which both ends agree on (the mask is shared).


class LTADMMScheduleState(NamedTuple):
    x: Any  # [A, ...]
    x_hat_edge: Any  # [A, S, ...] sender-side per-edge x estimate
    u_edge: Any  # [A, S, ...] | None (lean)
    z: Any  # [A, S, ...]
    s: Any  # [A, S, ...]
    s_tilde: Any  # [A, S, ...]
    x_hat_nbr: Any  # [A, S, ...] receiver-side mirror of the neighbor's
    u_nbr: Any  # [A, S, ...] | None (lean)      x_hat_edge reverse slot
    k: jax.Array


def init_schedule(cfg: LTADMMConfig, sched, exchange: Exchange, x0):
    """x0: params with leading agent axis [A, ...]; ``sched`` a
    ``schedule.TopologySchedule`` whose union matches ``exchange.topo``."""
    topo = sched.union
    zeros_edge = _stack_slots(
        tuple(tree_zeros_like(x0) for _ in range(topo.n_slots))
    )
    x_edge = _stack_slots(tuple(x0 for _ in range(topo.n_slots)))
    x_hat_nbr = _stack_slots(exchange.gather_from_neighbors(x0))
    return LTADMMScheduleState(
        x=x0,
        x_hat_edge=x_edge,
        u_edge=None if cfg.lean else x_edge,
        z=zeros_edge,
        s=zeros_edge,
        s_tilde=zeros_edge,
        x_hat_nbr=x_hat_nbr,
        u_nbr=None if cfg.lean else x_hat_nbr,
        k=jnp.zeros((), jnp.int32),
    )


def step_schedule(
    cfg: LTADMMConfig,
    sched,
    exchange: Exchange,
    vr_est,
    state: LTADMMScheduleState,
    data,
    round_key,
):
    """One outer round of Algorithm 1 over a time-varying topology.

    The compiled program is static: every union slot always moves a
    payload through the exchange; ``sched.round_mask(state.k)`` (one
    gather on the periodic mask stack) selects, per agent and slot,
    whether the advanced state or the held state is kept.  Packed-plane
    states (single-array leaves) take the slot-batched fast path.
    """
    if _is_packed(state.x):
        return _step_schedule_packed(cfg, sched, exchange, vr_est, state,
                                     data, round_key)
    if cfg.faults is not None:
        raise NotImplementedError(
            "fault injection runs on the packed schedule path only "
            "(packed=true); the tree path has no sealed wire format")
    return _step_schedule_tree(cfg, sched, exchange, vr_est, state, data,
                               round_key)


def _step_schedule_tree(
    cfg: LTADMMConfig,
    sched,
    exchange: Exchange,
    vr_est,
    state: LTADMMScheduleState,
    data,
    round_key,
):
    topo = sched.union
    A = topo.n_agents
    agent_ids = jnp.arange(A)
    like = _like_per_agent(state.x)
    cx, cz = cfg.compressor_x, cfg.compressor_z
    nbr_table = topo.neighbor_table()
    mask_k = sched.round_mask(state.k)  # [A, S] traced bool
    node_k = sched.round_node_mask(state.k)  # [A] traced bool | None
    active = [mask_k[:, sl] for sl in range(topo.n_slots)]
    nbr_ids = [jnp.asarray(nbr_table[:, sl]) for sl in range(topo.n_slots)]

    # ---- 1. local training: union degrees + full held dual sum ------------
    # An inactive NODE freezes its x entirely (= skips its tau local
    # epochs; the uniform SPMD program still runs them, the select
    # discards the result).  Its edges are all inactive by construction,
    # so duals and EF mirrors hold through the per-edge gates below —
    # at the static union fixed point x_{k+1} = x_k anyway, so freezing
    # preserves it.
    x_new = local_phase(cfg, topo, vr_est, state.x, state.z, data, round_key)
    x_new = _select_agents(node_k, x_new, state.x)

    # ---- 2-4. per-edge sender-side error feedback for x -------------------
    m_x, x_hat_edge_new, u_edge_new = [], [], []
    for sl in range(topo.n_slots):
        xh_sl = _slot(state.x_hat_edge, sl)
        u_adv = (
            xh_sl if cfg.lean
            else tree_lerp(_slot(state.u_edge, sl), xh_sl, cfg.eta)
        )

        def compress_xe(aid, nid, delta):
            kx = _key_xe(round_key, aid, nid)
            p = compression.compress_tree(cx, kx, delta)
            rec = compression.decompress_tree(cx, kx, p, like)
            return p, rec

        p, rec = jax.vmap(compress_xe)(
            agent_ids, nbr_ids[sl], tree_sub(x_new, u_adv)
        )
        xh_adv = tree_map(jnp.add, u_adv, rec)
        m_x.append(p)
        x_hat_edge_new.append(_select_slot(active[sl], xh_adv, xh_sl))
        if not cfg.lean:
            u_edge_new.append(
                _select_slot(active[sl], u_adv, _slot(state.u_edge, sl))
            )

    # ---- 5-6. sender-side error feedback for z (gated below) --------------
    m_z, z_hat_own = [], []
    for sl in range(topo.n_slots):
        def compress_z(aid, nid, delta):
            kz = _key_z(round_key, aid, nid)
            p = compression.compress_tree(cz, kz, delta)
            rec = compression.decompress_tree(cz, kz, p, like)
            return p, rec

        delta = tree_sub(_slot(state.z, sl), _slot(state.s, sl))
        p, rec = jax.vmap(compress_z)(agent_ids, nbr_ids[sl], delta)
        m_z.append(p)
        z_hat_own.append(tree_map(jnp.add, _slot(state.s, sl), rec))

    # ---- the only cross-agent communication (all slots, every round) ------
    recv_x = exchange.exchange_edges(tuple(m_x))
    recv_z = exchange.exchange_edges(tuple(m_z))

    if telemetry.active() and m_z:
        deg = jnp.sum(mask_k, axis=1, dtype=jnp.uint32)
        per_msg = (telemetry.payload_nbytes(m_x[0], nd=1)
                   + telemetry.payload_nbytes(m_z[0], nd=1))
        _emit_round_telemetry(cfg, vr_est, data, deg, per_msg, node_k, A=A)

    # ---- 7. receiver-side mirrors, gated by the same mask -----------------
    x_hat_nbr_new, u_nbr_new, z_hat_nbr = [], [], []
    for sl in range(topo.n_slots):
        xhn_sl = _slot(state.x_hat_nbr, sl)
        un_adv = (
            xhn_sl if cfg.lean
            else tree_lerp(_slot(state.u_nbr, sl), xhn_sl, cfg.eta)
        )

        def decomp_xe(sid, rid, payload):
            return compression.decompress_tree(
                cx, _key_xe(round_key, sid, rid), payload, like
            )

        dxr = jax.vmap(decomp_xe)(nbr_ids[sl], agent_ids, recv_x[sl])
        xhn_adv = tree_map(jnp.add, un_adv, dxr)
        x_hat_nbr_new.append(_select_slot(active[sl], xhn_adv, xhn_sl))
        if not cfg.lean:
            u_nbr_new.append(
                _select_slot(active[sl], un_adv, _slot(state.u_nbr, sl))
            )

        def decomp_z(sid, rid, payload):
            return compression.decompress_tree(
                cz, _key_z(round_key, sid, rid), payload, like
            )

        dzr = jax.vmap(decomp_z)(nbr_ids[sl], agent_ids, recv_z[sl])
        z_hat_nbr.append(
            tree_map(jnp.add, _slot(state.s_tilde, sl), dzr)
        )

    # ---- 8. z / s / s̃ updates on active edges only (held elsewhere) ------
    z_new, s_new, s_tilde_new = [], [], []
    rrho = cfg.r * cfg.rho
    for sl in range(topo.n_slots):
        z_eq4 = tree_map(
            lambda zo, zn, xn, xh, xhj: 0.5 * (zo - zn)
            + rrho * xn
            - rrho * (xh - xhj),
            z_hat_own[sl],
            z_hat_nbr[sl],
            x_new,
            x_hat_edge_new[sl],
            x_hat_nbr_new[sl],
        )
        z_new.append(_select_slot(active[sl], z_eq4, _slot(state.z, sl)))
        s_new.append(
            _select_slot(active[sl], z_hat_own[sl], _slot(state.s, sl))
        )
        s_tilde_new.append(
            _select_slot(active[sl], z_hat_nbr[sl],
                         _slot(state.s_tilde, sl))
        )

    return LTADMMScheduleState(
        x=x_new,
        x_hat_edge=_stack_slots(tuple(x_hat_edge_new)),
        u_edge=None if cfg.lean else _stack_slots(tuple(u_edge_new)),
        z=_stack_slots(tuple(z_new)),
        s=_stack_slots(tuple(s_new)),
        s_tilde=_stack_slots(tuple(s_tilde_new)),
        x_hat_nbr=_stack_slots(tuple(x_hat_nbr_new)),
        u_nbr=None if cfg.lean else _stack_slots(tuple(u_nbr_new)),
        k=state.k + 1,
    )


def _step_schedule_packed(
    cfg: LTADMMConfig,
    sched,
    exchange: Exchange,
    vr_est,
    state: LTADMMScheduleState,
    data,
    round_key,
):
    """Slot-batched time-varying round on the packed plane (same
    asynchronous-ADMM semantics as ``_step_schedule_tree``): the round's
    ``[A, S]`` activity mask gates one select per state field instead of
    a per-slot Python loop, and both exchanges are single batched
    routing calls on the union slots."""
    topo = sched.union
    A, S = topo.n_agents, topo.n_slots
    agent_ids = jnp.arange(A)
    aid2 = jnp.broadcast_to(agent_ids[:, None], (A, S))
    like = jax.ShapeDtypeStruct(state.x.shape[1:], state.x.dtype)
    cx, cz = cfg.compressor_x, cfg.compressor_z
    nbr = jnp.asarray(topo.neighbor_table())
    act = sched.round_mask(state.k)[:, :, None]  # [A, S, 1] traced bool
    node_k = sched.round_node_mask(state.k)  # [A] traced bool | None
    fp = cfg.faults
    if fp is not None:
        # a crashed agent is inert for the round: x frozen (node hold),
        # every incident edge dark (folded into ok below) — "restart"
        # resumes from the held state, the async-ADMM recovery
        alive = ~fp.crash_mask(state.k, A)  # [A]
        node_k = alive if node_k is None else node_k & alive
    # fused-path base seeds (salts of _key_xe/_key_z)
    bxe = jax.random.fold_in(round_key, 17)
    bz = jax.random.fold_in(round_key, 13)

    # ---- 1. local training: union degrees + full held dual sum ------------
    # Inactive nodes freeze their x / skip local training (see
    # _step_schedule_tree); their edges are off, so all edge state holds
    # through the act-gated selects below.
    with jax.named_scope("ltadmm.local"):
        x_new = local_phase(cfg, topo, vr_est, state.x, state.z, data,
                            round_key)
        x_new = _select_agents(node_k, x_new, state.x)

    with jax.named_scope("ltadmm.compress"):
        # ---- 2-4. per-edge sender-side error feedback for x ---------------
        xh = state.x_hat_edge  # [A, S, N]
        u_adv = xh if cfg.lean else tree_lerp(state.u_edge, xh, cfg.eta)

        m_x, rec_x = compression.plane_compress(
            cx, lambda aid, nid: _key_xe(round_key, aid, nid), bxe,
            aid2, nbr, x_new[:, None] - u_adv, like,
            exchange=exchange,
        )

        # ---- 5-6. sender-side error feedback for z (gated below) ----------
        m_z, rec_z = compression.plane_compress(
            cz, lambda aid, nid: _key_z(round_key, aid, nid), bz,
            aid2, nbr, state.z - state.s, like, exchange=exchange,
        )
        z_hat_own = state.s + rec_z

    # ---- the only cross-agent communication (all slots, every round) ------
    tx_x, tx_z = m_x, m_z  # what actually hits the wire (sealed if faulted)
    fault_counters = None
    with jax.named_scope("ltadmm.exchange"):
        if fp is None:
            recv_x = exchange.exchange_batched(m_x)
            recv_z = exchange.exchange_batched(m_z)
        else:
            # seal -> fault-armed exchange -> verify: a failed checksum or
            # stale/poisoned round tag marks the slot not-ok; both payloads
            # of a round share the link, so one ok mask covers x and z
            armed = dataclasses.replace(exchange, faults=fp)
            tx_x = compression.seal_plane(m_x, state.k, nd=2)
            tx_z = compression.seal_plane(m_z, state.k, nd=2)
            recv_x, ok_x, crc_x, tag_x = compression.verify_plane_kinds(
                armed.exchange_batched(tx_x, round_index=state.k), state.k)
            recv_z, ok_z, crc_z, tag_z = compression.verify_plane_kinds(
                armed.exchange_batched(tx_z, round_index=state.k), state.k)
            ok = ok_x & ok_z & alive[:, None]
            # NAK symmetrization over the (assumed reliable) control
            # plane: an edge advances only when BOTH endpoints received
            # cleanly, else duals + EF mirrors hold on both sides in
            # lockstep
            edge_ok = ok & exchange.exchange_batched(ok)
            act = act & edge_ok[:, :, None]
            if telemetry.active():
                # receiver-side detection verdicts, counted per message
                # on schedule-active slots (dark union slots carry
                # placeholders)
                sched_act = sched.round_mask(state.k)

                def _per_agent(mask):
                    return jnp.sum(sched_act & mask, axis=1,
                                   dtype=jnp.uint32)

                fault_counters = {
                    "rx_crc_rejects": (_per_agent(~crc_x)
                                       + _per_agent(~crc_z)),
                    "rx_tag_rejects": (_per_agent(crc_x & ~tag_x)
                                       + _per_agent(crc_z & ~tag_z)),
                    "rx_dropped": _per_agent(~ok_x) + _per_agent(~ok_z),
                    "naks": _per_agent(ok & ~edge_ok),
                }
    if telemetry.active():
        # transmission is charged on the SCHEDULE's active edges (a
        # dropped message was still sent); faults only add rx counters
        sched_act = sched.round_mask(state.k)
        deg = jnp.sum(sched_act, axis=1, dtype=jnp.uint32)
        per_msg = (telemetry.payload_nbytes(tx_x, nd=2)
                   + telemetry.payload_nbytes(tx_z, nd=2))
        _emit_round_telemetry(cfg, vr_est, data, deg, per_msg, node_k, A=A,
                              fault_counters=fault_counters)
    with jax.named_scope("ltadmm.compress"):
        x_hat_edge_new = jnp.where(act, u_adv + rec_x, xh)
        u_edge_new = (
            None if cfg.lean else jnp.where(act, u_adv, state.u_edge)
        )

    with jax.named_scope("ltadmm.update"):
        # ---- 7. receiver-side mirrors, gated by the same mask -------------
        xhn = state.x_hat_nbr
        un_adv = xhn if cfg.lean else tree_lerp(state.u_nbr, xhn, cfg.eta)

        xhn_adv = un_adv + compression.plane_decompress(
            cx, lambda sid, rid: _key_xe(round_key, sid, rid), bxe,
            nbr, aid2, recv_x, like, nd=2, exchange=exchange,
        )
        x_hat_nbr_new = jnp.where(act, xhn_adv, xhn)
        u_nbr_new = (
            None if cfg.lean else jnp.where(act, un_adv, state.u_nbr)
        )

        z_hat_nbr = state.s_tilde + compression.plane_decompress(
            cz, lambda sid, rid: _key_z(round_key, sid, rid), bz,
            nbr, aid2, recv_z, like, nd=2, exchange=exchange,
        )

        # ---- 8. z / s / s̃ updates on active edges only (held elsewhere) --
        rrho = cfg.r * cfg.rho
        z_eq4 = (
            0.5 * (z_hat_own - z_hat_nbr)
            + rrho * x_new[:, None]
            - rrho * (x_hat_edge_new - x_hat_nbr_new)
        )
        z_new = jnp.where(act, z_eq4, state.z)
        s_new = jnp.where(act, z_hat_own, state.s)
        s_tilde_new = jnp.where(act, z_hat_nbr, state.s_tilde)
    return LTADMMScheduleState(
        x=x_new,
        x_hat_edge=x_hat_edge_new,
        u_edge=u_edge_new,
        z=z_new,
        s=s_new,
        s_tilde=s_tilde_new,
        x_hat_nbr=x_hat_nbr_new,
        u_nbr=u_nbr_new,
        k=state.k + 1,
    )


# ---------------------------------------------------------------------------
# Diagnostics
# ---------------------------------------------------------------------------


def consensus_mean(state: LTADMMState):
    return tree_consensus_mean(state.x)


def consensus_error(state: LTADMMState):
    return tree_consensus_error(state.x)


def _edge_payload_bytes(cfg: LTADMMConfig, params) -> int:
    bx = compression.tree_wire_bytes(cfg.compressor_x, params)
    bz = compression.tree_wire_bytes(cfg.compressor_z, params)
    # sealed payloads (fault detection) carry crc + tag on both messages
    seal = 2 * compression.SEAL_BYTES if cfg.faults is not None else 0
    return bx + bz + seal


def wire_bytes_per_round(cfg: LTADMMConfig, topo: Topology, params) -> int:
    """Bytes the busiest agent transmits per outer round: one x-message to
    every neighbor + one z-message per incident edge (the paper's '2 t_c').
    On non-regular graphs this is the bottleneck (max-degree) agent; see
    ``wire_bytes_total`` for aggregate traffic.

    For a ``TopologySchedule``, ``degrees()`` is the period-mean ACTIVE
    degree, so only live links are charged (use ``wire_bytes_at`` for an
    exact single round)."""
    per_edge = _edge_payload_bytes(cfg, params)
    return int(round(float(np.max(topo.degrees())) * per_edge))


def wire_bytes_total(cfg: LTADMMConfig, topo: Topology, params) -> int:
    """Aggregate bytes on the wire per outer round, summed over agents
    (= 2 |E| * per-edge payload on any graph; period-mean active edges
    for a schedule)."""
    per_edge = _edge_payload_bytes(cfg, params)
    return int(round(float(np.sum(topo.degrees())) * per_edge))


def wire_bytes_at(cfg: LTADMMConfig, graph, params, t: int) -> int:
    """Exact busiest-agent bytes at round ``t``: only the links active
    that round carry payloads.  Accepts a ``TopologySchedule`` or a
    static ``Topology`` — on a static graph every round is identical,
    so ``t`` selects the same (constant) exact value the schedule path
    would: callers can always pass an explicit round."""
    per_edge = _edge_payload_bytes(cfg, params)
    deg = (graph.round_degrees(t) if hasattr(graph, "round_degrees")
           else graph.degrees())
    return int(np.max(deg)) * per_edge
