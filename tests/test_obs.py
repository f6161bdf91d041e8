"""Observability plane: measured counters vs analytic contracts.

The heart of the suite is the bitwise wire-byte parity matrix — for
EVERY registered solver spec, on static and time-varying graphs (and
with the fault plane nested in), the per-round increment of the
measured ``tx_bytes`` counter of the busiest agent must equal the
analytic ``wire_bytes(params, t)`` prediction exactly.  The rest pins
the fault-kind split, participation/grad-eval accounting, the
no-host-callback / donation-safety guarantees, and the trace layer
round-trip.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compression, solver, vr
from repro.core.schedule import build_graph
from repro.obs import summary, telemetry, trace
from repro.obs.telemetry import counters, with_telemetry
from repro.problems.logistic import LogisticProblem

PROB = LogisticProblem()
DATA = PROB.make_data(jax.random.key(0))
PARAMS = {"w": np.zeros((PROB.n,), np.float32)}
SGD = vr.PlainSgd(batch_grad=PROB.batch_grad)


def _saga():
    return vr.SagaTable(sample_grad=PROB.sample_grad, m=PROB.m)


def _est_for(spec):
    return _saga() if solver.solver_entry(spec).estimator == "vr" else SGD


# every registered solver, with at least one param + nested compressor
SOLVER_SPECS = {
    "ltadmm": "ltadmm:tau=3,compressor=qbit:bits=8",
    "dsgd": "dsgd:lr=0.1",
    "choco": "choco:lr=0.1,compressor=qbit:bits=8",
    "lead": "lead:lr=0.1,compressor=qbit:bits=8",
    "cold": "cold:lr=0.1,compressor=randk:fraction=0.5,sampler=block",
    "cedas": "cedas:lr=0.1,compressor=qbit:bits=4",
    "dpdc": "dpdc:lr=0.1,compressor=qbit:bits=8",
    "dada": "dada:lr=0.1,mu=0.5,lambda_g=0.1,graph_every=2,degree_cap=2,"
            "compressor=qbit:bits=8",
}
GRAPH_SPECS = {
    "static": "ring",
    "drop": "drop:p=0.3,base=complete,seed=0",
    "churn": "churn:p=0.2,base=complete,seed=0",
}
FAULTS = "faults:drop=0.1|corrupt=5e-3|stale=0.05|crash=0.02|seed=0"


def _measured_run(solver_spec, graph_spec, rounds=4):
    """-> (wrapped solver, graph, per-round host counter snapshots)."""
    graph, ex = build_graph(graph_spec, PROB.n_agents)
    s = with_telemetry(
        solver.make_solver(solver_spec, graph, ex, _est_for(solver_spec))
    )
    st = s.init(jnp.zeros((PROB.n_agents, PROB.n)))
    step = jax.jit(s.step)
    snaps = [counters(st)]
    for t in range(rounds):
        st = step(st, DATA, jax.random.key(t))
        snaps.append(counters(st))
    return s, graph, snaps


def _round_delta(snaps, t, field):
    # exact per-round increment of the uint64 counts
    return snaps[t + 1][field] - snaps[t][field]


def test_specs_cover_every_registered_solver():
    assert set(SOLVER_SPECS) == set(solver.SOLVERS)


@pytest.mark.parametrize("gname", sorted(GRAPH_SPECS))
@pytest.mark.parametrize("sname", sorted(SOLVER_SPECS))
def test_measured_wire_bytes_bitwise_equal_analytic(sname, gname):
    """Busiest agent's measured per-round TX bytes == the analytic
    ``wire_bytes(params, t)`` contract, bitwise, for every solver on
    static, edge-schedule and node-schedule graphs."""
    s, _, snaps = _measured_run(SOLVER_SPECS[sname], GRAPH_SPECS[gname])
    for t in range(len(snaps) - 1):
        measured = int(_round_delta(snaps, t, "tx_bytes").max())
        assert measured == s.wire_bytes(PARAMS, t=t), (sname, gname, t)


@pytest.mark.parametrize("sname", sorted(SOLVER_SPECS))
def test_measured_wire_bytes_with_faults_nested(sname):
    """Same parity with the fault plane nested into the spec: sealed
    LT-ADMM payloads measure SEAL_BYTES more per message (and the
    analytic contract charges them); oracle-dark baselines keep the
    unsealed wire format."""
    spec = f"{SOLVER_SPECS[sname]},faults={FAULTS}"
    s, _, snaps = _measured_run(spec, GRAPH_SPECS["drop"])
    for t in range(len(snaps) - 1):
        measured = int(_round_delta(snaps, t, "tx_bytes").max())
        assert measured == s.wire_bytes(PARAMS, t=t), (sname, t)


def test_fault_kind_counters_split():
    """drop+corrupt+stale+crash all at once: every receiver-side kind
    fires, and the kinds partition the dropped receives."""
    spec = f"ltadmm:compressor=qbit:bits=8,faults={FAULTS}"
    _, _, snaps = _measured_run(spec, "ring", rounds=8)
    last = snaps[-1]
    crc = int(last["rx_crc_rejects"].sum())
    tag = int(last["rx_tag_rejects"].sum())
    dropped = int(last["rx_dropped"].sum())
    assert crc > 0 and tag > 0 and dropped > 0
    assert dropped == crc + tag  # the kinds partition the failures
    assert int(last["naks"].sum()) > 0  # symmetric NAK holds fired


def test_stale_only_faults_reject_by_tag():
    spec = "ltadmm:compressor=qbit:bits=8,faults=faults:stale=0.5|seed=0"
    _, _, snaps = _measured_run(spec, "ring", rounds=6)
    last = snaps[-1]
    assert int(last["rx_tag_rejects"].sum()) > 0
    assert int(last["rx_crc_rejects"].sum()) == 0  # checksum-consistent
    assert int(last["rx_dropped"].sum()) == int(last["rx_tag_rejects"].sum())


def test_corrupt_only_faults_reject_by_crc():
    spec = "ltadmm:compressor=qbit:bits=8,faults=faults:corrupt=0.05|seed=0"
    _, _, snaps = _measured_run(spec, "ring", rounds=6)
    last = snaps[-1]
    assert int(last["rx_crc_rejects"].sum()) > 0
    assert int(last["rx_tag_rejects"].sum()) == 0
    assert int(last["rx_dropped"].sum()) == int(last["rx_crc_rejects"].sum())


def test_participation_counts_follow_node_schedule():
    """Churn: each round's participation increment IS the schedule's
    node mask; grad evals are charged only to participating agents."""
    s, sched, snaps = _measured_run(SOLVER_SPECS["ltadmm"],
                                    GRAPH_SPECS["churn"], rounds=5)
    for t in range(len(snaps) - 1):
        mask = sched.round_node_mask_host(t).astype(np.uint32)
        np.testing.assert_array_equal(
            _round_delta(snaps, t, "participations"), mask)
        per_agent = PROB.m + s.cfg.tau * s.cfg.batch_size
        np.testing.assert_array_equal(
            _round_delta(snaps, t, "grad_evals"),
            np.uint32(per_agent) * mask)


def test_grad_eval_recipes_pinned():
    """SAGA local phase: m (reset sweep) + tau * batch_size; PlainSgd
    baseline iteration: batch_size — per agent per round."""
    s, _, snaps = _measured_run(SOLVER_SPECS["ltadmm"], "ring", rounds=2)
    want = PROB.m + s.cfg.tau * s.cfg.batch_size
    np.testing.assert_array_equal(
        _round_delta(snaps, 0, "grad_evals"),
        np.full((PROB.n_agents,), want, np.uint32))
    s2, _, snaps2 = _measured_run(SOLVER_SPECS["dsgd"], "ring", rounds=2)
    np.testing.assert_array_equal(
        _round_delta(snaps2, 0, "grad_evals"),
        np.full((PROB.n_agents,), s2.batch_size, np.uint32))


def test_dada_graph_rounds_counted():
    s, _, snaps = _measured_run(SOLVER_SPECS["dada"], "ring", rounds=5)
    # graph_every=2 -> graph message rounds at k = 0, 2, 4
    assert int(snaps[-1]["graph_rounds"]) == 3
    assert int(snaps[-1]["rounds"]) == 5


def test_wrapper_preserves_trajectory_bitwise():
    """The golden guarantee: wrapping adds counters NEXT TO the solver
    state — the inner trajectory is bit-identical to the unwrapped
    solver's."""
    spec = SOLVER_SPECS["ltadmm"]
    graph, ex = build_graph("drop:p=0.3,base=complete,seed=0",
                            PROB.n_agents)
    plain = solver.make_solver(spec, graph, ex, _saga())
    wrapped = with_telemetry(solver.make_solver(spec, graph, ex, _saga()))
    x0 = jnp.zeros((PROB.n_agents, PROB.n))
    st_p, st_w = plain.init(x0), wrapped.init(x0)
    for t in range(3):
        st_p = jax.jit(plain.step)(st_p, DATA, jax.random.key(t))
        st_w = jax.jit(wrapped.step)(st_w, DATA, jax.random.key(t))
    for a, b in zip(jax.tree.leaves(st_p), jax.tree.leaves(st_w.inner)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_no_host_callbacks_and_donation_safe():
    """The counters are plain traced uint32 adds: no callback primitives
    in the jaxpr, and the state survives buffer donation across chunks
    (the launch driver's hot-loop contract)."""
    graph, ex = build_graph("ring", PROB.n_agents)
    s = with_telemetry(
        solver.make_solver("ltadmm:compressor=qbit:bits=8", graph, ex,
                           _saga())
    )
    # un-alias once, exactly as the launch driver does: init aliases x0
    # into several fields (and zero counters into one constant buffer),
    # and donation rejects the same buffer appearing twice
    st = jax.tree.map(jnp.array, s.init(jnp.zeros((PROB.n_agents, PROB.n))))

    def chunk(st):
        def body(c, r):
            return s.step(c, DATA, jax.random.key(1000 + r)), None

        c, _ = jax.lax.scan(body, st, jnp.arange(4))
        return c

    txt = str(jax.make_jaxpr(chunk)(st))
    for bad in ("pure_callback", "io_callback", "debug_callback"):
        assert bad not in txt, bad
    run = jax.jit(chunk, donate_argnums=0)
    st = run(st)
    assert int(counters(st)["rounds"]) == 4
    st = run(st)
    assert int(counters(st)["rounds"]) == 8


def test_solver_protocol_passthrough():
    """The wrapper conforms to the Solver protocol: abstract state
    mirrors the real state, shardings mirror the tree, and attribute
    introspection (cfg, name, wire accounting) delegates."""
    graph, ex = build_graph("ring", PROB.n_agents)
    inner = solver.make_solver("ltadmm:tau=3,compressor=qbit:bits=8",
                               graph, ex, _saga())
    s = with_telemetry(inner)
    assert with_telemetry(s) is s  # idempotent
    assert s.name == "ltadmm" and s.cfg.tau == 3
    assert s.wire_bytes(PARAMS) == inner.wire_bytes(PARAMS)
    x0 = jnp.zeros((PROB.n_agents, PROB.n))
    x_sds = jax.tree.map(
        lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype), x0)
    sds = s.abstract_state(x_sds)
    real = jax.tree.map(
        lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype), s.init(x0))
    assert jax.tree.structure(sds) == jax.tree.structure(real)
    assert jax.tree.leaves(sds) == jax.tree.leaves(real)
    ps = s.state_sharding("X", "E", "K")
    assert isinstance(ps, telemetry.TelemetryState)
    assert set(jax.tree.leaves(
        ps.telemetry, is_leaf=lambda x: isinstance(x, str))) == {"K"}


# ---------------------------------------------------------------------------
# Measured message sizes vs the compressor wire contracts
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", [
    "identity",
    "qbit:bits=8",
    "qbit:bits=4",
    "randk:fraction=0.5,sampler=block",
    "topk:fraction=0.25",
])
def test_message_nbytes_matches_compressor_contract(spec):
    comp = compression.get_compressor(spec)
    like = {"w": jax.ShapeDtypeStruct((257,), jnp.float32)}
    assert telemetry.message_nbytes(comp, like) == \
        compression.tree_wire_bytes(comp, like)


def test_payload_nbytes_counts_seal_words():
    comp = compression.get_compressor("qbit:bits=8")
    payload = compression.compress_tree(
        comp, jax.random.key(0), jnp.zeros((4, 3, 64)))
    raw = telemetry.payload_nbytes(payload, nd=2)
    sealed = compression.seal_plane(payload, 0, nd=2)
    assert telemetry.payload_nbytes(sealed, nd=2) == \
        raw + compression.SEAL_BYTES


# ---------------------------------------------------------------------------
# Trace layer
# ---------------------------------------------------------------------------


def test_tracer_roundtrip_and_summary(tmp_path):
    path = str(tmp_path / "out.json")
    with trace.Tracer(path) as tr:
        with tr.span("chunk", rounds=4, cold=True):
            pass
        with tr.span("chunk", rounds=4, cold=False):
            pass
        tr.instant("watchdog-rollback", round=7)
    events = trace.load_events(path)
    assert [e["ph"] for e in events] == ["X", "X", "i"]
    assert all(e["ts"] >= 0 for e in events)
    # the file doubles as a Chrome trace: leading '[', one event/line
    with open(path) as f:
        first = f.readline().strip()
    assert first == "["
    report = summary.summarize(events)
    assert "chunk" in report and "watchdog-rollback" in report
    assert summary.main([path]) == 0


def test_load_events_tolerates_torn_tail(tmp_path):
    path = str(tmp_path / "torn.json")
    tr = trace.Tracer(path)
    tr.instant("ok")
    tr.close()
    with open(path, "a") as f:
        f.write('{"name": "torn", "ph":')  # crashed mid-write
    events = trace.load_events(path)
    assert [e["name"] for e in events] == ["ok"]


def test_null_tracer_is_total_noop(tmp_path, monkeypatch):
    """The default tracer (no ``--trace``) writes no file."""
    monkeypatch.chdir(tmp_path)
    with trace.ANNOTATIONS.span("x", a=1):
        trace.ANNOTATIONS.instant("y")
    trace.ANNOTATIONS.close()
    assert list(tmp_path.iterdir()) == []


def _host_events(profile_dir, name):
    from jax.profiler import ProfileData

    (path,) = profile_dir.glob("plugins/profile/*/*.xplane.pb")
    data = ProfileData.from_file(str(path))
    return [e for p in data.planes if p.name == "/host:CPU"
            for line in p.lines for e in line.events if e.name == name]


def test_default_tracer_spans_land_in_a_profiler_capture(tmp_path):
    """Annotation-only spans show on the host plane of a profiler
    capture, on the device trace's clock, arguments as stats."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.ANNOTATIONS.span("train.eval", first_round=16):
            jnp.arange(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (ev,) = _host_events(tmp_path, "train.eval")
    assert ev.duration_ns > 0
    assert dict(ev.stats)["first_round"] == 16


def test_train_main_returns_round_scopes():
    """``train.main`` maps the compiled round's top-level ops to the
    four ``ltadmm.*`` phases.  Uncompressed messages: with a compressor,
    XLA's CPU backend fuses the host-simulated exchange's gathers into
    the receiver's decompression, so the exchange has no op of its own
    there (the v5e compile keeps them: tests/test_tpu_compile.py)."""
    from repro.launch import train

    summary = train.main([
        "--smoke", "--agents", "3", "--topology", "complete",
        "--compressor", "identity", "--rounds", "1", "--seq-len", "16",
        "--m-local", "2", "--tau", "1", "--batch-size", "1"])
    assert set(summary["round_scopes"].values()) == {
        "ltadmm.local", "ltadmm.compress", "ltadmm.exchange",
        "ltadmm.update"}


def test_timeit_smoke():
    f = jax.jit(lambda x: x + 1)
    us = trace.timeit(f, jnp.zeros((8,)), iters=2)
    assert us > 0


def test_summary_cli_empty(tmp_path, capsys):
    path = str(tmp_path / "empty.json")
    trace.Tracer(path).close()
    assert summary.main([path]) == 0
    assert "(no events)" in capsys.readouterr().out


def test_counters_exact_past_two_to_the_32():
    """The high word carries the low word's wraps: ``tx_bytes`` counts
    exactly across 2**32, one exact ``wire_bytes`` per round."""
    graph, ex = build_graph("ring", PROB.n_agents)
    s = with_telemetry(solver.make_solver(SOLVER_SPECS["ltadmm"], graph,
                                          ex, _saga()))
    st = s.init(jnp.zeros((PROB.n_agents, PROB.n)))
    per_round = s.wire_bytes(PARAMS)
    start = 2**32 - per_round - per_round // 2  # wraps in round 2
    st = st._replace(telemetry=st.telemetry._replace(
        tx_bytes=jnp.full((PROB.n_agents,), start, jnp.uint32)))
    step = jax.jit(s.step)
    seen = []
    for t in range(3):
        st = step(st, DATA, jax.random.key(t))
        seen.append(int(counters(st)["tx_bytes"].max()))
    assert seen == [start + (t + 1) * per_round for t in range(3)]
    assert seen[-1] > 2**32
    assert int(np.asarray(st.high.tx_bytes).max()) == 1


def test_counters_json_serializable():
    _, _, snaps = _measured_run(SOLVER_SPECS["dsgd"], "ring", rounds=1)
    tel = {k: np.asarray(v).tolist() for k, v in snaps[-1].items()}
    json.dumps(tel)  # what launch/train.py --telemetry prints
