"""Smoke test of the training system on a TPU, in one process.

    python chip_smoke.py             # one chip: kernel parity + training
    python chip_smoke.py --chips 4   # four chips: agents on chips only

One chip:
  (a) device check — exits non-zero unless JAX's first device is a TPU;
  (b) fused compression kernels against their jnp oracles, bitwise, at a
      real plane width: ``quantize_plane`` (8 and 4 bits) and
      ``randk_gather_plane``/``randk_scatter_plane`` (block and stride
      samplers);
  (c) ``repro.launch.train.main`` in-process: LT-ADMM on the packed plane
      with the qbit compressor (``impl=auto``), Qwen3-0.6B at its
      published widths with depth and vocabulary rows cut to fit one
      chip, two agents on a complete graph, measured telemetry on;
  (d) the divergence watchdog: a prefetched host snapshot outlives the
      donation of the live state, and a planted NaN rolls back to it bit
      for bit (run alone: ``python -c "import chip_smoke as c;
      c.watchdog_phase()"`` with ``src`` on ``PYTHONPATH``).

Four chips (``--chips 4``), and nothing else: four agents on a ring, one
per chip, through ``launch.steps.build_train`` (one collective-permute
per neighbour slot), against the same solver with the host-simulated
gather-by-index exchange on the same agent-sharded placement.

Every phase that fails exits non-zero; the last line of a passing run
is the JSON object ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

# Qwen3-0.6B widths (d_model 1024, 16 q / 8 kv heads x 128, d_ff 3072)
# with 4 of 28 layers and 1/8 of the 151,936 vocabulary rows: ~82M
# parameters, so the LT-ADMM state of the agents fits 16 GB of HBM.
ARCH = "qwen3-0.6b"
CUT = ["--layers", "4", "--vocab-rows", "18992"]
TRAIN_ARGV = [
    "--arch", ARCH, *CUT, "--agents", "2", "--topology", "complete",
    "--rounds", "4", "--m-local", "4", "--seq-len", "128",
    "--batch-size", "2", "--tau", "2", "--telemetry",
]
PLANE_N = (1 << 22) + 123  # parity plane width: ~4.2M, not tile-aligned
PLANE_LEAD = (2, 2)  # [A, S] messages
MESH_ROUNDS = 3
# ppermute vs gather-by-index move identical bytes; only the XLA
# programs around them differ, so float summation order may too
MESH_RTOL = 1e-4


class PhaseError(RuntimeError):
    pass


def check(cond, what):
    if not cond:
        raise PhaseError(what)


def device_phase(chips):
    import jax

    devs = jax.devices()
    d = devs[0]
    print(f"# device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}")
    check(d.platform == "tpu", f"no TPU: JAX's first device is {d.platform}")
    check(len(devs) >= chips, f"--chips {chips} but {len(devs)} devices")
    return d


def parity_phase(n=PLANE_N, lead=PLANE_LEAD):
    """Compiled plane kernels vs the ``ref.py`` oracles, bitwise."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import prng, resolve_interpret
    from repro.kernels.quantize import ops as q_ops, ref as q_ref
    from repro.kernels.sparse_gather import ops as sg_ops, ref as sg_ref

    print(f"# parity: plane {lead} x {n}, kernels compiled="
          f"{not resolve_interpret(None)}")
    key = jax.random.key(11)
    x = jax.random.normal(key, lead + (n,), jnp.float32)
    seed = prng.key_seed(jax.random.key(7))
    sids = jnp.broadcast_to(
        jnp.arange(lead[0], dtype=jnp.uint32)[:, None], lead)
    rids = jnp.broadcast_to(
        jnp.arange(lead[1], dtype=jnp.uint32)[None, :] + 1, lead)

    def same(name, got, want):
        got, want = np.asarray(got), np.asarray(want)
        check(got.shape == want.shape,
              f"{name}: shape {got.shape} != {want.shape}")
        bad = int(np.sum(got != want))
        print(f"# parity {name}: {got.size} values, {bad} differ")
        check(bad == 0, f"{name}: {bad} values differ from the oracle")

    for bits in (8, 4):
        t0 = time.perf_counter()
        q, scale = jax.block_until_ready(jax.jit(
            lambda s, a, b, xx, bits=bits: q_ops.quantize_plane(
                s, a, b, xx, bits=bits))(seed, sids, rids, x))
        dt = time.perf_counter() - t0
        qr, sr = jax.jit(
            lambda s, a, b, xx, bits=bits: q_ref.quantize_plane_ref(
                s, a, b, xx, bits=bits))(seed, sids, rids, x)
        same(f"quantize_plane bits={bits} q", q, qr)
        same(f"quantize_plane bits={bits} scale", scale, sr)
        print(f"# quantize_plane bits={bits}: first call (with compile) "
              f"{dt:.3f} s")
    k = n // 4
    for sampler in ("block", "stride"):
        strides = (1,) if sampler == "block" else prng.coprime_strides(n)
        v = jax.jit(lambda s, a, b, xx: sg_ops.randk_gather_plane(
            s, a, b, xx, k=k, strides=strides))(seed, sids, rids, x)
        vr = jax.jit(lambda s, a, b, xx: sg_ref.randk_gather_plane_ref(
            s, a, b, xx, k=k, strides=strides))(seed, sids, rids, x)
        same(f"randk_gather_plane {sampler}", v, vr)
        out = jax.jit(lambda s, a, b, vv: sg_ops.randk_scatter_plane(
            s, a, b, vv, n=n, gain=n / k, strides=strides))(
                seed, sids, rids, v)
        outr = jax.jit(lambda s, a, b, vv: sg_ref.randk_scatter_plane_ref(
            s, a, b, vv, n=n, gain=n / k, strides=strides))(
                seed, sids, rids, vr)
        same(f"randk_scatter_plane {sampler}", out, outr)
        nnz = int(jnp.sum(out[0, 0] != 0))
        print(f"# randk {sampler}: {nnz} nonzeros of k={k} in message 0")
        check(nnz <= k and nnz > 0.99 * k,
              f"randk {sampler}: scatter wrote {nnz} values, k={k}")


def _peak_bytes(devices):
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


def train_phase(argv=TRAIN_ARGV):
    """The training entry point in-process, on the chip."""
    import jax
    import numpy as np

    from repro.launch import train

    t0 = time.perf_counter()
    s = train.main(list(argv))
    wall = time.perf_counter() - t0
    print(f"# train: params={s['params']:,} reduced={s['reduced']} "
          f"compressor_impl={s['compressor_impl']} "
          f"pallas_in_round={s['pallas_in_round']} "
          f"compile_s={s['compile_s']:.1f} wall_s={wall:.1f}")
    check(s["compressor_impl"] == "pallas",
          f"compressor resolved to {s['compressor_impl']}, not pallas")
    check(s["pallas_in_round"], "no tpu_custom_call in the compiled round")
    losses = [loss for _, loss in s["losses"]]
    print(f"# train: per-round loss {losses}")
    check(len(losses) >= 2 and all(math.isfinite(v) for v in losses),
          f"losses not finite: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    vocab = s["reduced"].get("vocab")
    if vocab:
        print(f"# train: round-0 loss {losses[0]:.4f} vs ln(vocab rows) "
              f"{math.log(vocab):.4f}")
    tel = s["telemetry"]
    rounds = tel["rounds"]
    busiest = int(np.max(tel["tx_bytes"]))
    print(f"# train: telemetry tx_bytes (busiest agent) {busiest:,} over "
          f"{rounds} rounds; analytic wire_bytes {s['wire_bytes']:,}/round")
    check(busiest == rounds * s["wire_bytes"],
          "measured tx_bytes != rounds x analytic wire_bytes")
    print(f"# train: peak HBM bytes {_peak_bytes(jax.devices()[:1])}")


def watchdog_phase(n=1 << 22):
    """The divergence watchdog on the chip: a prefetched snapshot, the
    live state donated by a jitted step, a planted NaN, then a rollback
    that must give back the snapshot bit for bit, twice."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.steps import DivergenceWatchdog

    key = jax.random.key(3)
    state = {"x": jax.random.normal(key, (2, n), jnp.float32),
             "h": jax.random.normal(key, (2, n // 4), jnp.bfloat16),
             "k": jnp.arange(n // 8, dtype=jnp.uint32)}
    # from device copies: reading ``state`` itself would fill its host
    # cache before the watchdog's transfer does
    want = jax.tree.map(lambda t: np.array(jnp.copy(t)), state)
    step = jax.jit(lambda s: jax.tree.map(lambda t: t * 3 + 1, s),
                   donate_argnums=0)
    wd = DivergenceWatchdog(depth=2, blowup=10.0)
    wd.prefetch(state)
    state, rolled = wd.observe(state, 1.0)
    check(not rolled, "a healthy point rolled back")
    snap = wd._ring[0]
    owned = {k: bool(t.flags.owndata) for k, t in snap.items()}
    print(f"# watchdog: snapshot leaves own their memory {owned}")
    check(all(isinstance(t, np.ndarray) for t in snap.values()),
          "a snapshot leaf is not a host array")
    for i in range(2):
        live, state = state, jax.block_until_ready(step(state))
        check(live["x"].is_deleted(), f"step {i} did not donate the state")
        state = jax.tree.map(lambda t: t.at[0].set(jnp.nan)
                             if t.dtype != jnp.uint32 else t, state)
        wd.prefetch(state)
        state, rolled = wd.observe(state, float("nan"))
        check(rolled, f"rollback {i}: the planted NaN did not roll back")
        bad = {k: int(np.sum(np.asarray(state[k]).view(np.uint8)
                             != want[k].view(np.uint8)))
               for k in want}
        print(f"# watchdog: rollback {i} after donation, bytes that differ "
              f"from the snapshot {bad}")
        check(not any(bad.values()), f"rollback {i} differs: {bad}")
    print(f"# watchdog: counters {wd.counters()}")
    check(wd.counters()["discarded"] == 2 and wd.counters()["rollbacks"] == 2,
          f"counters {wd.counters()}")


def _mesh_solvers(mesh, recipe, arch, cfg):
    """(ppermute solver, host-simulated solver) on the same graph and the
    same agent-sharded placement."""
    import dataclasses

    from repro.core.solver import make_solver, solver_entry
    from repro.launch.steps import build_estimator, build_train

    _, state_ps, _, spmd = build_train(
        arch, cfg, mesh, "ltadmm", recipe)
    ex = dataclasses.replace(spmd.exchange, gather=True)
    est = build_estimator(arch, cfg, recipe, solver_entry("ltadmm").estimator)
    host = make_solver("ltadmm", spmd.graph, ex, est,
                       defaults=recipe.solver_defaults("ltadmm"))
    return spmd, host, state_ps


def mesh_phase(cfg=None, m_local=4, seq_len=128, rounds=MESH_ROUNDS):
    """Four agents on a ring, one per chip: ppermute vs host-simulated.
    ``cfg=None``: the chip config, ARCH cut as in CUT."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import ARCHS
    from repro.data import SyntheticLMDataset
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import TrainRecipe, model_loss, model_specs
    from repro.models.common import init_params

    arch = ARCHS[ARCH]
    if cfg is None:
        cfg = dataclasses.replace(arch.make(None), n_layers=int(CUT[1]),
                                  vocab=int(CUT[3]))
    mesh = make_host_mesh(4)
    recipe = TrainRecipe(tau=2, batch_size=2, compressor="qbit:bits=8",
                         topology="ring")
    spmd, host, state_ps = _mesh_solvers(mesh, recipe, arch, cfg)
    a = spmd.graph.n_agents
    check(a == 4, f"agent axis holds {a} agents, not 4")
    shard = jax.tree.map(lambda ps: NamedSharding(mesh, ps), state_ps)
    data_sh = NamedSharding(mesh, P("data"))
    ds = SyntheticLMDataset(vocab=cfg.vocab, seq_len=seq_len, n_agents=a,
                            m_local=m_local, heterogeneity=0.7)
    data = {"tokens": jax.device_put(ds.sample(jax.random.key(0)), data_sh)}
    params0 = init_params(jax.random.key(1), model_specs(arch, cfg))
    # one agent's copy per device, not all four on the first
    x0 = jax.device_put(jax.tree.map(
        lambda t: jnp.broadcast_to(t[None], (a,) + t.shape), params0),
        data_sh)
    del params0
    loss = model_loss(arch, cfg)

    def run(solver, name):
        state = jax.jit(solver.init, out_shardings=shard)(x0)
        step = jax.jit(
            lambda st, d, r: solver.step(st, d, jax.random.key(1000 + r)),
            in_shardings=(shard, {"tokens": data_sh}, None),
            out_shardings=shard, donate_argnums=0)
        t0 = time.perf_counter()
        compiled = step.lower(state, data, jnp.int32(0)).compile()
        print(f"# mesh {name}: compile {time.perf_counter() - t0:.1f} s")
        hlo = compiled.as_text()
        xs, losses = [], []
        for r in range(rounds):
            state = compiled(state, data, jnp.int32(r))
            x = solver.consensus_params(state)
            pbar = jax.tree.map(lambda t: jnp.mean(t, axis=0), x)
            losses.append(float(jnp.mean(jax.vmap(
                lambda d: loss(pbar, {"tokens": d}))(data["tokens"]))))
            xs.append(np.concatenate([np.asarray(t).reshape(a, -1)
                                      for t in jax.tree.leaves(x)], axis=1))
        return state, hlo, xs, losses

    state, hlo, xs_spmd, loss_spmd = run(spmd, "ppermute")
    n_cp = hlo.count("collective-permute-start") or hlo.count(
        "collective-permute(")
    print(f"# mesh ppermute: collective-permute ops in HLO: {n_cp}")
    check("collective-permute" in hlo, "no collective-permute in the HLO")
    placed = {}
    for leaf in jax.tree.leaves(state):
        if getattr(leaf, "ndim", 0) and leaf.shape[0] == a:
            for sh in leaf.addressable_shards:
                placed.setdefault(sh.index[0].start, set()).add(sh.device.id)
    print(f"# mesh placement: agent -> device ids {dict(sorted(placed.items()))}")
    check(sorted(placed) == list(range(a))
          and all(len(v) == 1 for v in placed.values())
          and len({next(iter(v)) for v in placed.values()}) == a,
          f"agents are not one per device: {placed}")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.devices()[:a]]
    print(f"# mesh per-device bytes_in_use {in_use}")
    del state
    _, _, xs_host, loss_host = run(host, "host-sim")
    print(f"# mesh loss ppermute {loss_spmd}")
    print(f"# mesh loss host-sim {loss_host}")
    for r, (xa, xb) in enumerate(zip(xs_spmd, xs_host)):
        rel = float(np.max(np.abs(xa - xb)) / max(np.max(np.abs(xb)), 1e-30))
        print(f"# mesh round {r}: max |x_ppermute - x_host| / max |x| "
              f"= {rel:.3e} (tolerance {MESH_RTOL})")
        check(rel <= MESH_RTOL, f"round {r}: trajectories differ by {rel}")
    check(all(math.isfinite(v) for v in loss_spmd), "mesh losses not finite")
    print(f"# mesh peak HBM bytes per device "
          f"{_peak_bytes(jax.devices()[:a])}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the agents-on-chips phase")
    args = ap.parse_args(argv)
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    try:
        dev = device_phase(args.chips)
        import jax

        from repro.launch import compile_cache

        print(f"# compile cache: {compile_cache.enable()}")
        if args.chips == 4:
            mesh_phase()
        else:
            parity_phase()
            train_phase()
            watchdog_phase()
    except PhaseError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
