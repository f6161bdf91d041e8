"""The harness with the timed path broken underneath comes out not
correct, and with nothing broken, correct.

Each case skips the harness's look for a chip and drives the rest of a
run (``bench.run.run_cell``) on the CPU at the system's qwen3 smoke
size, with the compressor's Pallas kernels in interpret mode, under the
limits of the ``qwen3-0.6b.train`` cell.  Faults are planted in the
program: a round that returns its state unchanged, the anchor gradient
taken over half of each agent's sequences, and the exchange between
agents left out (every received message reads as zero).
"""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import run

DATA = Path(__file__).resolve().parent / "data"
ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module", autouse=True)
def compile_cache(tmp_path_factory):
    """The persistent compilation cache, as ``bench/run.py`` turns it on:
    ``train.main`` compiles its evaluation at every log point, and only
    the cache keeps that out of the window."""
    old = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_compilation_cache_dir",
                      str(tmp_path_factory.mktemp("jax_cache")))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    yield
    jax.config.update("jax_compilation_cache_dir", old[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old[1])


def _spec():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return {
        "cell": {"name": "tiny", "chips": 1},
        "config": json.loads((DATA / "tiny.json").read_text()),
        "mix": json.loads((DATA / "tiny_mix.json").read_text()),
        "limits": json.loads(
            (ROOT / "bench" / "limits" / "qwen3-0.6b.train.json").read_text()),
        "end_to_end": bench["end_to_end"],
        "per_layer": [],
    }


def _unchanged(monkeypatch):
    from repro.core import admm

    monkeypatch.setattr(admm, "step", lambda cfg, topo, ex, est, state, *a:
                        state._replace(k=state.k + 1))


def _half_batch(monkeypatch):
    from repro.core import vr

    def reset(self, params, data):
        half = jax.tree.map(lambda x: x[: x.shape[0] // 2], data)
        return vr.SvrgState(anchor=params,
                            anchor_grad=self.full_grad(params, half))

    monkeypatch.setattr(vr.SvrgAnchor, "reset", reset)


def _no_exchange(monkeypatch):
    from repro.core import topology

    def gather(self, tree, round_index=None):
        a, s = self.topo.n_agents, self.topo.n_slots
        return jax.tree.map(
            lambda x: jnp.zeros((a, s) + x.shape[1:], x.dtype), tree)

    monkeypatch.setattr(topology.Exchange, "gather_batched", gather)
    monkeypatch.setattr(topology.Exchange, "exchange_batched",
                        lambda self, tree, round_index=None:
                        jax.tree.map(jnp.zeros_like, tree))


@pytest.mark.parametrize("fault,correct", [
    (None, True),
    (_unchanged, False),
    (_half_batch, False),
    (_no_exchange, False),
], ids=["sound", "state_unchanged", "half_batch", "no_exchange"])
def test_harness_decides_correct(monkeypatch, fault, correct):
    if fault is not None:
        fault(monkeypatch)
    result, checks = run.run_cell(_spec(), 2**31 + 5, 0.5, 0, jax.devices())
    assert result["correct"] is correct, checks
    assert list(result)[-1] == "checks"
