"""Unified Solver protocol + registry: spec-string round-trips, golden
parity with the pre-refactor implementations, and the perf-regression
gate."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import admm, compression, solver, vr
from repro.core.schedule import drop_schedule
from repro.core.topology import Complete, Exchange, Ring
from repro.problems.logistic import LogisticProblem

PROB = LogisticProblem()
DATA = PROB.make_data(jax.random.key(0))
TOPO = Ring(PROB.n_agents)
EX = Exchange(TOPO)
SGD = vr.PlainSgd(batch_grad=PROB.batch_grad)


def _saga():
    return vr.SagaTable(sample_grad=PROB.sample_grad, m=PROB.m)


def _est_for(spec):
    return _saga() if solver.solver_entry(spec).estimator == "vr" else SGD


# spec exercising at least one param (+ nested compressor where supported)
ROUNDTRIP_SPECS = {
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


def test_registry_covers_every_method():
    assert set(solver.SOLVERS) == {
        "ltadmm", "dsgd", "choco", "lead", "cold", "cedas", "dpdc", "dada"
    }
    assert set(ROUNDTRIP_SPECS) == set(solver.SOLVERS)


@pytest.mark.parametrize("name", sorted(ROUNDTRIP_SPECS))
def test_spec_roundtrip(name):
    """Every registered solver builds from its spec string and conforms
    to the protocol: init/step/consensus/wire accounting/abstract state."""
    spec = ROUNDTRIP_SPECS[name]
    s = solver.make_solver(spec, TOPO, EX, _est_for(spec))
    assert isinstance(s, solver.Solver)
    assert s.name == name

    x0 = jnp.zeros((PROB.n_agents, PROB.n))
    st = s.init(x0)
    st = jax.jit(s.step)(st, DATA, jax.random.key(0))
    x = s.consensus_params(st)
    assert jax.tree.leaves(x)[0].shape == (PROB.n_agents, PROB.n)
    assert all(bool(jnp.all(jnp.isfinite(leaf)))
               for leaf in jax.tree.leaves(x))

    params = {"w": np.zeros((PROB.n,), np.float32)}
    wb = s.wire_bytes(params)
    assert isinstance(wb, int) and wb > 0

    # abstract state matches the real state structure and shapes
    x_sds = jax.tree.map(
        lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype), x0
    )
    sds = s.abstract_state(x_sds)
    real_sds = jax.tree.map(
        lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype), s.init(x0)
    )
    assert jax.tree.structure(sds) == jax.tree.structure(real_sds)
    assert jax.tree.leaves(sds) == jax.tree.leaves(real_sds)

    # sharding hook mirrors the state tree (leaf markers stand in for
    # PartitionSpecs; edge fields exist only for ltadmm)
    ps = s.state_sharding("X", "E", "K")
    assert jax.tree.structure(
        ps, is_leaf=lambda x: isinstance(x, str)
    ).num_leaves >= 2


def test_ltadmm_solver_absorbs_schedule_dispatch():
    """One class, both graph kinds: a TopologySchedule flips the state
    to the per-edge LTADMMScheduleState without caller involvement."""
    sched = drop_schedule(Complete(PROB.n_agents), p=0.3, seed=0)
    s = solver.make_solver(
        "ltadmm:compressor=qbit:bits=8", sched, Exchange(sched.union),
        _saga(),
    )
    st = s.init(jnp.zeros((PROB.n_agents, PROB.n)))
    assert isinstance(st, admm.LTADMMScheduleState)
    st = jax.jit(s.step)(st, DATA, jax.random.key(0))
    assert int(st.k) == 1
    # schedule-aware wire accounting: exact round < full union graph
    params = {"w": np.zeros((1000,), np.float32)}
    full = admm.wire_bytes_per_round(s.cfg, sched.union, params)
    assert s.wire_bytes(params, t=0) <= full
    assert s.wire_bytes(params) < full  # period-mean active degree

    static = solver.make_solver(
        "ltadmm:compressor=qbit:bits=8", TOPO, EX, _saga()
    )
    assert isinstance(
        static.init(jnp.zeros((PROB.n_agents, PROB.n))), admm.LTADMMState
    )


def test_spec_parsing_nested_and_errors():
    # nested compressor params via plain commas (unknown keys fold into
    # the preceding compressor value) and via pipes
    s = solver.make_solver(
        "ltadmm:compressor=randk:fraction=0.25,sampler=block,tau=7",
        TOPO, EX, _saga(),
    )
    assert s.cfg.tau == 7
    assert s.cfg.compressor_x == compression.RandK(
        fraction=0.25, sampler="block"
    )
    s2 = solver.make_solver(
        "ltadmm:tau=7,compressor=randk:fraction=0.25|sampler=block",
        TOPO, EX, _saga(),
    )
    assert s2.cfg == s.cfg

    with pytest.raises(ValueError, match="unknown solver"):
        solver.make_solver("sgdx:lr=0.1", TOPO, EX, SGD)
    with pytest.raises(ValueError, match="unknown param"):
        solver.make_solver("dsgd:learning_rate=0.1", TOPO, EX, SGD)

    # defaults lose to spec params and unsupported keys are dropped
    s3 = solver.make_solver(
        "dsgd:lr=0.3", TOPO, EX, SGD,
        defaults={"lr": 0.1, "compressor": "qbit:bits=8"},
    )
    assert s3.lr == 0.3


def test_compressor_spec_strings():
    assert compression.get_compressor("qbit:bits=4") == \
        compression.BBitQuantizer(bits=4)
    assert compression.get_compressor("randk:fraction=0.25,sampler=block") \
        == compression.RandK(fraction=0.25, sampler="block")
    assert compression.get_compressor("randk:fraction=0.25|sampler=block") \
        == compression.RandK(fraction=0.25, sampler="block")
    assert compression.get_compressor("identity") == compression.Identity()
    # legacy kwargs construction keeps working
    assert compression.get_compressor("qbit", bits=4) == \
        compression.BBitQuantizer(bits=4)
    with pytest.raises(ValueError, match="unknown compressor"):
        compression.get_compressor("gzip")
    with pytest.raises(ValueError, match=r"unknown param\(s\).*bitz"):
        compression.get_compressor("qbit:bitz=4")
    with pytest.raises(ValueError, match="malformed"):
        compression.get_compressor("qbit:8bits")


# ---------------------------------------------------------------------------
# Parity with the pre-refactor implementations (captured fixture)
# ---------------------------------------------------------------------------

GOLD = json.load(open(os.path.join(os.path.dirname(__file__),
                                   "golden_trajectories.json")))
PARITY_SPECS = {
    "dsgd": "dsgd:lr=0.1",
    "choco": "choco:lr=0.1,compressor=qbit:bits=8",
    "lead": "lead:lr=0.1,compressor=qbit:bits=8",
    "cold": "cold:lr=0.1,compressor=qbit:bits=8",
    "cedas": "cedas:lr=0.1,compressor=qbit:bits=8",
    "dpdc": "dpdc:lr=0.1,compressor=qbit:bits=8",
    "ltadmm": "ltadmm:compressor=qbit:bits=8",
    # dada has no pre-refactor ancestor — its entry pins the learned-
    # graph trajectory against drift since its introduction
    "dada": "dada:lr=0.1,mu=0.5,lambda_g=0.1,graph_every=2,degree_cap=2,"
            "compressor=qbit:bits=8",
}


@pytest.mark.parametrize("name", sorted(PARITY_SPECS))
def test_golden_parity_with_pre_refactor_trajectories(name):
    """Each method under the unified API reproduces its pinned
    gradient-norm trajectory (tests/golden_trajectories.json, first
    captured from the pre-refactor ad-hoc implementations).  The fixture
    holds the jax 0.9.0 ``jax.random`` stream (threefry partitionable,
    the default since jax 0.5), which draws the minibatches and the
    stochastic rounding; log-space tolerance absorbs float jitter across
    machines."""
    spec = PARITY_SPECS[name]
    s = solver.make_solver(spec, TOPO, EX, _est_for(spec))
    st = s.init(jnp.zeros((PROB.n_agents, PROB.n)))
    step = jax.jit(s.step)
    traj = []
    for i in range(GOLD["iters"]):
        st = step(st, DATA, jax.random.key(i))
        if (i + 1) % GOLD["every"] == 0:
            xbar = jnp.mean(s.consensus_params(st), axis=0)
            traj.append(float(PROB.global_grad_norm_sq(xbar, DATA)))
    got = np.log(np.asarray(traj))
    want = np.log(np.asarray(GOLD["traj"][name]))
    np.testing.assert_allclose(got, want, atol=0.5)


@pytest.mark.parametrize("name", sorted(ROUNDTRIP_SPECS))
def test_wire_bytes_honors_explicit_t_on_static_graphs(name):
    """Regression: an explicit ``t`` used to be silently ignored on
    static graphs for LT-ADMM.  Every registered solver must now honor
    it via the uniform exact-round path — and on a static graph every
    round is the same constant, so t=0, t=5 and t=None all agree.
    Exception: dada is PERIODIC even on a static graph (graph rounds
    carry the extra per-edge weight scalar), so its contract is
    graph_every-periodicity with t=None amortizing the graph message."""
    spec = ROUNDTRIP_SPECS[name]
    s = solver.make_solver(spec, TOPO, EX, _est_for(spec))
    params = {"w": np.zeros((64,), np.float32)}
    if name == "dada":
        ge = s.graph_every
        assert s.wire_bytes(params, t=0) == s.wire_bytes(params, t=ge)
        assert s.wire_bytes(params, t=1) == s.wire_bytes(params, t=ge + 1)
        # graph rounds cost strictly more; the amortized figure sits
        # strictly between the two round kinds
        assert s.wire_bytes(params, t=0) > s.wire_bytes(params, t=1)
        assert (s.wire_bytes(params, t=1) < s.wire_bytes(params)
                < s.wire_bytes(params, t=0))
        return
    assert s.wire_bytes(params, t=0) == s.wire_bytes(params, t=5) \
        == s.wire_bytes(params)


def test_ltadmm_wire_bytes_t_agrees_with_admm_module():
    """Solver-level and admm-module wire accounting agree round by
    round, on static graphs and on schedules (packed solvers charge
    the whole-plane message, so compare on the abstract plane)."""
    from repro.core import packing

    params = {"w": np.zeros((100,), np.float32)}
    plane = packing.abstract_plane(packing.layout_of(params))
    s = solver.make_solver("ltadmm:compressor=qbit:bits=8", TOPO, EX,
                           _saga())
    for t in (0, 3, 17):
        assert s.wire_bytes(params, t=t) == admm.wire_bytes_at(
            s.cfg, TOPO, plane, t
        )
    sched = drop_schedule(Complete(PROB.n_agents), p=0.3, seed=0)
    ss = solver.make_solver("ltadmm:compressor=qbit:bits=8", sched,
                            Exchange(sched.union), _saga())
    per_round = [ss.wire_bytes(params, t=t) for t in range(sched.period)]
    assert per_round == [
        admm.wire_bytes_at(ss.cfg, sched, plane, t)
        for t in range(sched.period)
    ]
    assert len(set(per_round)) > 1  # drop schedule varies by round


# ---------------------------------------------------------------------------
# Perf-regression gate
# ---------------------------------------------------------------------------


def test_check_regression_thresholds():
    from benchmarks.check_regression import check

    base = {"results": [{
        "name": "admm/ring", "rounds_to_tol": 100, "tol": 1e-8,
        "warm_wall_s": 1.0, "final_gradnorm_sq": 1e-16,
    }]}

    def pr(**over):
        r = dict(base["results"][0])
        r.update(over)
        return {"results": [r]}

    assert check(pr(), base) == []
    assert check(pr(rounds_to_tol=120, warm_wall_s=2.0), base) == []
    assert len(check(pr(rounds_to_tol=200), base)) == 1  # slower to tol
    assert len(check(pr(rounds_to_tol=None), base)) == 1  # never converges
    assert len(check(pr(warm_wall_s=4.0), base)) == 1  # wall-time blow-up
    assert len(check(pr(final_gradnorm_sq=1e-8), base)) == 1  # floor rose
    assert len(check({"results": []}, base)) == 1  # benchmark dropped
