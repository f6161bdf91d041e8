"""Fault plane: seeded injection, sealed-payload detection, recovery.

Pins the robustness contracts: fault masks are bit-replayable from the
spec seed; the wire-path detection (checksum + round tag + NAK
symmetrization) equals the ``FaultPlane.edge_ok`` oracle the
dense-gossip baselines consult; LT-ADMM-CC still converges below the
paper tolerance under simultaneous drop + corruption + crash faults;
and the divergence watchdog rolls back without rewinding rounds.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compression, solver, vr
from repro.core.faults import FaultPlane, get_faults, validate_spec
from repro.core.schedule import static_schedule
from repro.core.topology import Exchange, Ring, Star
from repro.launch.steps import DivergenceWatchdog
from repro.problems.logistic import LogisticProblem

PROB = LogisticProblem()
DATA = PROB.make_data(jax.random.key(0))
TOPO = Ring(PROB.n_agents)
EX = Exchange(TOPO)
SGD = vr.PlainSgd(batch_grad=PROB.batch_grad)

# acceptance recipe: simultaneous drops + bit-flips + crashes
FAULTY_LTADMM = ("ltadmm:compressor=qbit:bits=8,"
                 "faults=faults:drop=0.05|corrupt=1e-3|crash=0.01|seed=0")


def _saga():
    return vr.SagaTable(sample_grad=PROB.sample_grad, m=PROB.m)


def _est_for(spec):
    return _saga() if solver.solver_entry(spec).estimator == "vr" else SGD


# ---------------------------------------------------------------------------
# Spec parsing + registry
# ---------------------------------------------------------------------------


def test_spec_parsing():
    fp = get_faults("faults:drop=0.05,corrupt=1e-3,stale=0.02,crash=0.01")
    assert fp == FaultPlane(drop=0.05, corrupt=1e-3, stale=0.02, crash=0.01)
    # | accepted for , (nested inside solver specs); passthroughs
    assert get_faults("faults:drop=0.1|seed=3") == FaultPlane(drop=0.1,
                                                              seed=3)
    assert get_faults(None) is None
    assert get_faults(fp) is fp


def test_spec_validation_errors():
    with pytest.raises(ValueError, match="unknown fault model"):
        get_faults("bogus:drop=0.1")
    with pytest.raises(ValueError, match="valid params"):
        get_faults("faults:drp=0.1")
    with pytest.raises(ValueError, match=r"outside \[0, 1\]"):
        get_faults("faults:drop=1.5")
    with pytest.raises(ValueError, match="malformed fault param"):
        validate_spec("faults:drop")
    # the solver grammar validates nested fault specs up front
    with pytest.raises(ValueError, match="valid params"):
        solver.parse_solver_spec("ltadmm:faults=faults:drp=0.1")


def test_masks_bit_replayable_from_seed():
    a = FaultPlane(drop=0.3, corrupt=0.1, stale=0.2, crash=0.15, seed=42)
    b = FaultPlane(drop=0.3, corrupt=0.1, stale=0.2, crash=0.15, seed=42)
    c = dataclasses.replace(a, seed=43)
    for k in (0, 1, 17):
        for ma, mb, mc in zip(a.message_masks(k, TOPO),
                              b.message_masks(k, TOPO),
                              c.message_masks(k, TOPO)):
            np.testing.assert_array_equal(np.asarray(ma), np.asarray(mb))
        assert not all(
            np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(a.message_masks(k, TOPO),
                            c.message_masks(k, TOPO))
        )
        np.testing.assert_array_equal(
            np.asarray(a.crash_mask(k, TOPO.n_agents)),
            np.asarray(b.crash_mask(k, TOPO.n_agents)))
    # rounds draw independent masks
    assert not np.array_equal(np.asarray(a.crash_mask(0, 64)),
                              np.asarray(a.crash_mask(1, 64)))


def test_start_delays_all_fault_kinds():
    fp = FaultPlane(drop=0.9, corrupt=0.9, stale=0.9, crash=0.9, start=5)
    for k in (0, 4):
        assert not any(bool(np.asarray(m).any())
                       for m in fp.message_masks(k, TOPO))
        assert not bool(np.asarray(fp.crash_mask(k, TOPO.n_agents)).any())
    assert bool(np.asarray(fp.crash_mask(5, TOPO.n_agents)).any())


# ---------------------------------------------------------------------------
# Sealed wire format
# ---------------------------------------------------------------------------


def _payload(key, topo, d=5):
    shape = (topo.n_agents, topo.n_slots, d)
    return compression.Payload(data=jax.random.normal(key, shape,
                                                      jnp.float32))


def test_seal_verify_roundtrip():
    p = _payload(jax.random.key(0), TOPO)
    sealed = compression.seal_plane(p, 7, nd=2)
    stripped, ok = compression.verify_plane(sealed, 7)
    assert bool(np.asarray(ok).all())
    np.testing.assert_array_equal(np.asarray(stripped["data"]),
                                  np.asarray(p["data"]))
    # wrong expected tag rejects everywhere
    _, bad = compression.verify_plane(sealed, 8)
    assert not bool(np.asarray(bad).any())


def test_any_single_bit_flip_is_caught():
    """The additive mod-2^32 checksum changes by a nonzero power of two
    under any single bit flip, so every position is detected."""
    p = _payload(jax.random.key(1), TOPO, d=3)
    sealed = compression.seal_plane(p, 3, nd=2)
    raw = np.asarray(sealed["data"]).copy()
    view = raw.view(np.uint32)
    for flat_idx in (0, 7, view.size - 1):
        for bit in (0, 13, 31):
            v = view.copy()
            v.reshape(-1)[flat_idx] ^= np.uint32(1) << np.uint32(bit)
            tampered = compression.Payload(
                data=jnp.asarray(v.view(np.float32).reshape(raw.shape)),
                crc=sealed["crc"], tag=sealed["tag"])
            _, ok = compression.verify_plane(tampered, 3)
            edge = np.unravel_index(flat_idx, raw.shape)[:2]
            assert not bool(np.asarray(ok)[edge]), (flat_idx, bit)


def test_stale_rewind_is_crc_consistent_but_tag_rejected():
    """Stale injection (tag-1, crc-1) keeps the checksum equation valid
    — the payload is a GENUINE old-round message, rejected by the tag
    alone, so staleness and corruption are distinguishable."""
    fp = FaultPlane(stale=1.0, seed=5)
    sealed = compression.seal_plane(_payload(jax.random.key(2), TOPO), 9,
                                    nd=2)
    injected = fp.inject(sealed, TOPO, 9)
    # every tag rewound by exactly one round...
    np.testing.assert_array_equal(np.asarray(injected["tag"]),
                                  np.asarray(sealed["tag"]) - 1)
    # ...rejected against round 9 but crc-valid against round 8
    _, ok_now = compression.verify_plane(injected, 9)
    _, ok_prev = compression.verify_plane(injected, 8)
    assert not bool(np.asarray(ok_now).any())
    assert bool(np.asarray(ok_prev).all())


def test_inject_requires_sealed_payloads():
    fp = FaultPlane(drop=0.5)
    with pytest.raises(ValueError, match="seal_plane"):
        fp.inject(_payload(jax.random.key(0), TOPO), TOPO, 0)


# ---------------------------------------------------------------------------
# Detection == oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("topo", [Ring(6), Star(6)],
                         ids=["ring6", "star6"])
def test_wire_detection_equals_edge_ok_oracle(topo):
    """Checksum/tag verification + crash-aware alive mask + NAK
    symmetrization over the control plane produces EXACTLY the mask
    ``edge_ok`` computes — the baselines' oracle is the wire truth."""
    ex = Exchange(topo)
    fp = FaultPlane(drop=0.2, corrupt=0.05, stale=0.1, crash=0.1, seed=7)
    armed = dataclasses.replace(ex, faults=fp)
    smask = np.asarray(topo.slot_mask())
    for k in range(6):
        sealed = compression.seal_plane(
            _payload(jax.random.key(k), topo), k, nd=2)
        recv = armed.exchange_batched(sealed, round_index=k)
        _, ok = compression.verify_plane(recv, k)
        alive = ~fp.crash_mask(k, topo.n_agents)
        ok = ok & alive[:, None]
        detected = ok & ex.exchange_batched(ok)  # NAK round-trip
        np.testing.assert_array_equal(
            np.asarray(detected) & smask,
            np.asarray(fp.edge_ok(k, topo)), err_msg=f"round {k}")


# ---------------------------------------------------------------------------
# End-to-end recovery
# ---------------------------------------------------------------------------


def _run(spec, rounds, graph=None, seed_stream=1000):
    s = solver.make_solver(spec, TOPO if graph is None else graph, EX,
                           _est_for(spec))
    st = s.init(jnp.zeros((PROB.n_agents, PROB.n)))

    def body(st, r):
        return s.step(st, DATA, jax.random.key(seed_stream + r)), None

    st, _ = jax.jit(
        lambda st: jax.lax.scan(body, st, jnp.arange(rounds))
    )(st)
    return s, st


def test_ltadmm_converges_under_faults_to_paper_tol():
    """Acceptance pin: under drop=0.05 + corrupt=1e-3 + crash=0.01 the
    sealed wire + async-ADMM holds keep LT-ADMM-CC converging below the
    paper tolerance ||grad||^2 < 1e-10 (fixed seed)."""
    s, st = _run(FAULTY_LTADMM, 300)
    xbar = jnp.mean(s.consensus_params(st), axis=0)
    gn = float(PROB.global_grad_norm_sq(xbar, DATA))
    assert gn < 1e-10, gn


def test_faulty_run_is_bitwise_replayable():
    _, st1 = _run(FAULTY_LTADMM, 12)
    _, st2 = _run(FAULTY_LTADMM, 12)
    for a, b in zip(jax.tree.leaves(st1), jax.tree.leaves(st2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_zero_rate_faults_keep_exact_trajectory():
    """An all-zero-rate FaultPlane arms the sealed wire but injects
    nothing — the trajectory must match the unarmed schedule path to
    float-reassociation tolerance (the armed graph compiles with extra
    where/verify ops, so XLA fusion differs; sealing must be overhead,
    not perturbation)."""
    graph = static_schedule(TOPO)
    _, st_plain = _run("ltadmm:compressor=qbit:bits=8", 6, graph=graph)
    _, st_armed = _run("ltadmm:compressor=qbit:bits=8,faults=faults:seed=0",
                       6, graph=graph)
    for a, b in zip(jax.tree.leaves(st_plain), jax.tree.leaves(st_armed)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_seal_wire_overhead_accounted():
    plain = solver.make_solver("ltadmm:compressor=qbit:bits=8", TOPO, EX,
                               _saga())
    armed = solver.make_solver(FAULTY_LTADMM, TOPO, EX, _saga())
    params = {"w": np.zeros((64,), np.float32)}
    assert armed.wire_bytes(params) > plain.wire_bytes(params)


@pytest.mark.parametrize("name", ["dsgd", "choco", "lead", "cold",
                                  "cedas", "dpdc", "dada"])
def test_baselines_stay_finite_under_faults(name):
    """Every gossip/learned-graph solver accepts faults= and survives
    drops + crashes via held (identity-row) gossip weights."""
    from test_solver import ROUNDTRIP_SPECS

    spec = (ROUNDTRIP_SPECS[name]
            + ",faults=faults:drop=0.15|stale=0.05|crash=0.1|seed=3")
    s, st = _run(spec, 8)
    for leaf in jax.tree.leaves(s.consensus_params(st)):
        assert bool(np.isfinite(np.asarray(leaf)).all())


def test_total_crash_freezes_params():
    """crash=1.0: every agent is inert every round — params hold exactly
    (the 'restart' resumes from the held state)."""
    s, st = _run("dsgd:lr=0.1,faults=faults:crash=1.0", 4)
    np.testing.assert_array_equal(np.asarray(st["x"]),
                                  np.zeros((PROB.n_agents, PROB.n)))


# ---------------------------------------------------------------------------
# Divergence watchdog
# ---------------------------------------------------------------------------


def test_watchdog_passthrough_and_rollback():
    wd = DivergenceWatchdog(depth=2, blowup=10.0)
    s1 = {"x": jnp.asarray([1.0])}
    s2 = {"x": jnp.asarray([2.0])}
    out, rb = wd.observe(s1, 1.0)
    assert out is s1 and not rb
    out, rb = wd.observe(s2, 0.5)
    assert out is s2 and not rb
    # NaN metric -> rollback to the OLDEST ring entry (s1), round NOT
    # rewound (skip-ahead is the caller's loop; the watchdog only
    # restores state)
    diverged = {"x": jnp.asarray([jnp.nan])}
    out, rb = wd.observe(diverged, float("nan"))
    assert rb and float(out["x"][0]) == 1.0
    assert wd.rollbacks == 1
    # blowup relative to best-seen (0.5): 100 > 10 * 0.5
    out, rb = wd.observe(s2, 100.0)
    assert rb and float(out["x"][0]) == 1.0


def test_watchdog_raises_after_consecutive_rollbacks():
    wd = DivergenceWatchdog(blowup=10.0, max_consecutive=2)
    wd.observe({"x": jnp.asarray([1.0])}, 1.0)
    wd.observe({"x": jnp.asarray([0.0])}, float("inf"))
    wd.observe({"x": jnp.asarray([0.0])}, float("nan"))
    with pytest.raises(RuntimeError, match="consecutive"):
        wd.observe({"x": jnp.asarray([0.0])}, float("nan"))


def test_watchdog_divergence_before_any_snapshot_raises():
    wd = DivergenceWatchdog()
    with pytest.raises(RuntimeError, match="before any healthy"):
        wd.observe({"x": jnp.asarray([0.0])}, float("nan"))


def test_watchdog_snapshots_survive_donation():
    """Ring entries are buffer copies: deleting (donating) the observed
    state must not invalidate a later rollback."""
    wd = DivergenceWatchdog(depth=1, blowup=10.0)
    live = {"x": jnp.arange(4.0)}
    wd.observe(live, 1.0)
    live["x"].delete()  # what jit donation does to the caller's buffers
    out, rb = wd.observe({"x": jnp.zeros(4)}, float("nan"))
    assert rb
    np.testing.assert_array_equal(np.asarray(out["x"]),
                                  np.arange(4.0))


def test_watchdog_snapshots_live_on_host():
    """The ring holds host copies, so a model-scale state does not cost
    ``depth`` extra copies of device memory; rollback hands back device
    arrays of the same dtypes."""
    wd = DivergenceWatchdog(depth=2, blowup=10.0)
    state = {"x": jnp.arange(4.0), "k": jnp.arange(3, dtype=jnp.uint32)}
    wd.observe(state, 1.0)
    assert all(isinstance(t, np.ndarray) for t in jax.tree.leaves(wd._ring[0]))
    out, rb = wd.observe(state, float("nan"))
    assert rb and all(isinstance(t, jax.Array) for t in jax.tree.leaves(out))
    assert out["k"].dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(out["x"]), np.arange(4.0))


def test_watchdog_prefetched_snapshot_is_one_owned_host_copy():
    """A prefetched snapshot is an owned host array equal to the state,
    and it outlives a real donation of the state it was taken from."""
    wd = DivergenceWatchdog(depth=2, blowup=10.0)
    state = {"x": jnp.arange(6.0) * 0.5,
             "k": jnp.arange(3, dtype=jnp.uint32)}
    want = jax.tree.map(np.array, state)
    wd.prefetch(state)
    assert wd.has_prefetch(state)
    out, rb = wd.observe(state, 1.0)
    assert out is state and not rb
    snap = wd._ring[0]
    for name, t in snap.items():
        assert isinstance(t, np.ndarray) and t.flags.owndata
        assert t.ctypes.data != state[name].unsafe_buffer_pointer()
        np.testing.assert_array_equal(t, want[name])
    step = jax.jit(lambda s: jax.tree.map(lambda t: t * 3, s),
                   donate_argnums=0)
    jax.block_until_ready(step(state))
    assert state["x"].is_deleted()
    out, rb = wd.observe({"x": jnp.zeros(6), "k": jnp.zeros(3, jnp.uint32)},
                         float("nan"))
    assert rb
    for name, t in out.items():
        assert t.dtype == want[name].dtype
        np.testing.assert_array_equal(np.asarray(t), want[name])
    assert wd.counters() == {"snapshots": 1, "prefetched": 1,
                             "discarded": 0, "rollbacks": 1,
                             "snapshot_bytes": 6 * 4 + 3 * 4}


def test_watchdog_prefetch_dropped_at_unhealthy_point():
    """An unhealthy metric drops the prefetched copy unread and rolls
    back to the oldest healthy entry."""
    wd = DivergenceWatchdog(depth=2, blowup=10.0)
    wd.observe({"x": jnp.asarray([1.0])}, 1.0)
    wd.prefetch(s2 := {"x": jnp.asarray([2.0])})
    wd.observe(s2, 0.5)
    bad = {"x": jnp.asarray([jnp.nan])}
    wd.prefetch(bad)
    out, rb = wd.observe(bad, float("nan"))
    assert rb and float(out["x"][0]) == 1.0
    assert not wd.has_prefetch(bad) and len(wd._ring) == 2
    c = wd.counters()
    assert (c["snapshots"], c["prefetched"], c["discarded"],
            c["rollbacks"]) == (2, 1, 1, 1)


def test_watchdog_prefetch_of_another_state_is_not_taken():
    """``observe`` takes a prefetch only for the state it was started
    on; any other state is copied synchronously, once."""
    wd = DivergenceWatchdog(blowup=10.0)
    wd.prefetch({"x": jnp.asarray([1.0])})
    other = {"x": jnp.asarray([3.0])}
    assert not wd.has_prefetch(other)
    wd.observe(other, 1.0)
    np.testing.assert_array_equal(wd._ring[0]["x"], [3.0])
    assert wd.counters()["snapshots"] == 1
    assert wd.counters()["prefetched"] == 0


@pytest.mark.parametrize("blowup", [1e4, 0.0])
def test_train_main_reports_watchdog_counters(blowup):
    """With the watchdog on, every log point of a healthy run is one
    prefetched snapshot; ``--watchdog-blowup 0`` reports None."""
    from repro.launch import train

    summary = train.main([
        "--smoke", "--agents", "2", "--topology", "complete",
        "--compressor", "identity", "--rounds", "3", "--log-every", "1",
        "--seq-len", "16", "--m-local", "2", "--tau", "1",
        "--batch-size", "1", "--watchdog-blowup", str(blowup)])
    wd = summary["watchdog"]
    if blowup == 0:
        assert wd is None
        return
    assert wd["snapshots"] == len(summary["losses"]) == 3
    assert wd["prefetched"] == wd["snapshots"]
    assert wd["discarded"] == wd["rollbacks"] == 0
    assert wd["snapshot_bytes"] > 0
    assert wd["snapshot_bytes"] % wd["snapshots"] == 0
