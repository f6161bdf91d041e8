"""In-trace telemetry plane: measured wire/compute/fault counters.

The repo's cost claims are otherwise only *predicted* (``core.costmodel``,
the analytic ``wire_bytes`` contracts).  This module MEASURES them from
the running rounds: a typed counter pytree (``Telemetry``) rides the
scanned round's carry, accumulated by instrumentation taps inside the
solver step functions (``core.admm``, ``core.baselines``,
``core.graphlearn``).  Everything is ordinary traced uint32 arithmetic —
no host callbacks, no syncs — so the counters work inside the donated
jitted ``lax.scan`` hot loop, and ``tests/test_obs.py`` pins the
measured wire bytes bitwise-equal to every analytic ``wire_bytes``
prediction.

Opt-in is a wrapper, not a flag::

    solver = with_telemetry(make_solver(spec, graph, ex, est))
    state  = solver.init(x0)            # TelemetryState(inner, telemetry, high)
    state  = solver.step(state, data, key)
    counts = counters(state)            # host numpy dict

The taps are trace-time no-ops when no collector is installed
(``active()`` is False), so un-wrapped solvers compile the exact program
they always did — golden trajectories are untouched by construction.

Counting conventions (what the parity tests rely on):

* ``tx_bytes[i]`` charges agent ``i`` for every message the wire
  contract bills: one payload per schedule-active incident edge (the
  mask BEFORE fault refinement — a dropped message was still
  transmitted), with per-message bytes measured from the actual payload
  leaves (``payload_nbytes``), so sealed payloads naturally cost
  ``SEAL_BYTES`` more.  Masked union slots move self-addressed
  placeholders through the static SPMD exchange; those are simulation
  artifacts and are not charged, exactly as in the analytic accounting.
* fault counters are receiver-side, gated by the same schedule mask:
  ``rx_crc_rejects`` (checksum mismatch: drops, corruption),
  ``rx_tag_rejects`` (checksum-consistent stale rounds),
  ``rx_dropped`` (any failed verification), ``naks`` (clean receives
  the agent still held because the peer NAK'd the edge).
* ``grad_evals`` counts component-gradient evaluations from the bound
  estimator's published recipe (SAGA reset sweeps all ``m``, SVRG
  anchors cost a second batch, ...), charged only to participating
  agents.
* each counter is carried as two uint32 words, ``telemetry`` (the low
  word) and ``high`` (the wraps of the low word so far); ``counters``
  joins them into exact uint64 counts past 2**32.  A round adds less
  than 2**32 to any counter, so one wrap per round at most.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

import numpy as np


class Telemetry(NamedTuple):
    """Per-agent counter vectors ``[A]`` (uint32, cumulative) + two
    scalar counters.  Rides the scan carry of a wrapped solver."""

    tx_bytes: Any  # [A] bytes transmitted (measured on the wire format)
    tx_msgs: Any  # [A] messages transmitted
    rx_dropped: Any  # [A] received messages failing seal verification
    rx_crc_rejects: Any  # [A]   ... of which checksum mismatches
    rx_tag_rejects: Any  # [A]   ... of which stale round tags (crc ok)
    naks: Any  # [A] clean receives held because the peer NAK'd the edge
    participations: Any  # [A] rounds the agent participated in
    grad_evals: Any  # [A] component-gradient evaluations
    graph_rounds: Any  # [] learned-graph (dada) graph-round occurrences
    rounds: Any  # [] rounds stepped through the wrapper

    @classmethod
    def zeros(cls, n_agents: int) -> "Telemetry":
        vec = [jnp.zeros((n_agents,), jnp.uint32) for _ in range(8)]
        return cls(*vec, jnp.zeros((), jnp.uint32), jnp.zeros((), jnp.uint32))


# ---------------------------------------------------------------------------
# Trace-time collector — how the taps inside the step functions reach the
# wrapper.  Thread-local so concurrent traces (pjit compiles on worker
# threads, parallel tests) cannot cross-talk.
# ---------------------------------------------------------------------------

_LOCAL = threading.local()


def active() -> bool:
    """True while a ``with_telemetry`` step is being traced — the taps
    in the solver step functions guard on this, so an un-wrapped solver
    pays nothing and compiles an unchanged program."""
    return getattr(_LOCAL, "collector", None) is not None


def emit(**counters) -> None:
    """Add contributions to the active collector (no-op when inactive).
    Keyword names must be ``Telemetry`` fields; values are cast to
    uint32 and summed into the round's totals."""
    col = getattr(_LOCAL, "collector", None)
    if col is None:
        return
    for name, value in counters.items():
        if name not in Telemetry._fields:
            raise ValueError(f"unknown telemetry counter {name!r}")
        v = jnp.asarray(value).astype(jnp.uint32)
        col[name] = v if name not in col else col[name] + v


@contextlib.contextmanager
def _collect():
    prev = getattr(_LOCAL, "collector", None)
    _LOCAL.collector = {}
    try:
        yield _LOCAL.collector
    finally:
        _LOCAL.collector = prev


# ---------------------------------------------------------------------------
# Measured message sizes
# ---------------------------------------------------------------------------


def payload_nbytes(payload, nd: int) -> int:
    """Wire bytes of ONE message of a batched payload tree whose leaves
    carry ``nd`` leading batch dims (e.g. ``[A, S, ...]`` -> nd=2).
    Static (a Python int): leaf shapes are known at trace time.  Counts
    every leaf — compressed values, scales, explicit indices, and the
    crc/tag words of sealed payloads."""
    total = 0
    for leaf in jax.tree.leaves(payload):
        n = 1
        for d in leaf.shape[nd:]:
            n *= int(d)
        total += n * np.dtype(leaf.dtype).itemsize
    return int(total)


def message_nbytes(comp, like) -> int:
    """Wire bytes of one compressed message of a ``like``-shaped tree
    (per-agent ShapeDtypeStructs), measured from the payload the
    compressor actually emits (via ``jax.eval_shape`` — nothing runs)."""
    from repro.core import compression  # local: keep obs import-standalone

    p = jax.eval_shape(
        lambda: compression.compress_tree(
            comp,
            jax.random.key(0),
            jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), like),
        )
    )
    return payload_nbytes(p, nd=0)


# ---------------------------------------------------------------------------
# Gradient-evaluation recipes (per the estimator protocol in core.vr)
# ---------------------------------------------------------------------------


def _est_name(est) -> str:
    # unwrap the packed-plane adapter (core.packing.PackedEstimator)
    return type(getattr(est, "est", est)).__name__


def local_phase_evals(est, m: int, tau: int, batch_size: int) -> int:
    """Component-gradient evaluations of ONE agent's LT-ADMM local phase
    (reset + tau estimator steps)."""
    name = _est_name(est)
    if name == "SagaTable":  # reset sweeps the table, steps refresh a batch
        return m + tau * batch_size
    if name == "SvrgAnchor":  # reset anchors a full grad, steps cost 2x
        return m + 2 * tau * batch_size
    if name == "FullGrad":  # every step is a full sweep
        return tau * m
    return tau * batch_size  # PlainSgd


def round_grad_evals(est, m: int, batch_size: int) -> int:
    """Component-gradient evaluations of one gossip-baseline iteration
    (a single stateless estimate per agent)."""
    name = _est_name(est)
    if name == "FullGrad":
        return m
    if name == "SvrgAnchor":
        return 2 * batch_size
    return batch_size


# ---------------------------------------------------------------------------
# The opt-in wrapper
# ---------------------------------------------------------------------------


class TelemetryState(NamedTuple):
    inner: Any  # the wrapped solver's state, untouched
    telemetry: Telemetry  # low 32 bits of each counter
    high: Telemetry  # high 32 bits: wraps of the low word so far


def _n_agents(tree) -> int:
    return jax.tree.leaves(tree)[0].shape[0]


@dataclasses.dataclass(frozen=True)
class TelemetrySolver:
    """``Solver``-protocol wrapper that carries a ``Telemetry`` counter
    pytree alongside the wrapped solver's state.  ``step`` installs the
    trace-time collector, traces the inner step (whose taps add their
    round contributions), and folds the totals into the carried
    counters — plain uint32 adds and carries in the compiled program,
    nothing else."""

    solver: Any

    def __getattr__(self, name):
        # everything shape-preserving (name, graph, wire_bytes,
        # round_cost, cfg, degree_cap, ...) delegates to the inner solver
        return getattr(object.__getattribute__(self, "solver"), name)

    def init(self, x0):
        inner = self.solver.init(x0)
        a = _n_agents(x0)
        return TelemetryState(inner, Telemetry.zeros(a), Telemetry.zeros(a))

    def step(self, state, data, key):
        with _collect() as col:
            inner = self.solver.step(state.inner, data, key)
        lo, hi = state.telemetry, state.high
        inc = dict(col, rounds=jnp.uint32(1))
        new_lo = {k: getattr(lo, k) + v for k, v in inc.items()}
        # the low word wrapped iff it came out below where it was
        wrapped = {k: (new_lo[k] < getattr(lo, k)).astype(jnp.uint32)
                   for k in inc}
        new_hi = {k: getattr(hi, k) + w for k, w in wrapped.items()}
        return TelemetryState(inner, lo._replace(**new_lo),
                              hi._replace(**new_hi))

    def consensus_params(self, state):
        return self.solver.consensus_params(state.inner)

    def abstract_state(self, x_sds):
        inner = self.solver.abstract_state(x_sds)
        a = _n_agents(x_sds)
        tel = jax.eval_shape(lambda: Telemetry.zeros(a))
        return TelemetryState(inner, tel, tel)

    def state_sharding(self, x_ps, edge_ps, scalar_ps):
        inner = self.solver.state_sharding(x_ps, edge_ps, scalar_ps)
        # counters are tiny; replicate them
        tel = Telemetry(*([scalar_ps] * len(Telemetry._fields)))
        return TelemetryState(inner, tel, tel)


def with_telemetry(solver) -> TelemetrySolver:
    """Wrap any registered solver with the telemetry plane (idempotent)."""
    if isinstance(solver, TelemetrySolver):
        return solver
    return TelemetrySolver(solver)


def counters(state: TelemetryState) -> dict[str, np.ndarray]:
    """Host-side numpy view of the cumulative counters, exact past
    2**32 (uint64: the high word shifted over the low one).  One
    device->host transfer; call it at sample points, never inside the
    loop."""
    return {
        f: (np.asarray(hi, np.uint64) << np.uint64(32))
        | np.asarray(lo, np.uint64)
        for f, lo, hi in zip(Telemetry._fields, state.telemetry, state.high)
    }
