"""Operation and byte counts against hand counts."""
import json
import math
import sys
import types
from pathlib import Path

import pytest

from bench import flops
from bench.ref import decoder, ltadmm

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


TRAIN = {"agents": 2, "topology": "complete", "m_local": 4, "tau": 4,
         "batch_size": 1, "seq_len": 2048, "bits": 8}


def test_param_counts_match_hand_counts():
    # qwen3 cut: 18,992 x 1,024 embedding; per layer wq 1024*16*128,
    # wk and wv 1024*8*128, wo 16*128*1024, ffn 3*1024*3072, q/k norm
    # 2*128, two block norms 2*1024; final norm 1024
    layer = (1024 * 2048 + 2 * 1024 * 1024 + 2048 * 1024
             + 3 * 1024 * 3072 + 2 * 128 + 2 * 1024)
    assert flops.param_count(_cfg("qwen3-0.6b")) == 82_372_608
    assert 18_992 * 1024 + 4 * layer + 1024 == 82_372_608
    # olmo cut: 6,288 x 2,048 embedding; per layer 4 * 2048^2 attention
    # and 3 * 2048 * 8192 ffn; no norm parameters.  The cell runs one
    # layer; two layers are pinned too
    olmo = _cfg("olmo-1b")
    assert flops.param_count(olmo) == 79_986_688
    assert flops.param_count(dict(olmo, num_hidden_layers=2)) == 147_095_552
    assert 6288 * 2048 + 2 * (4 * 2048 ** 2 + 3 * 2048 * 8192) == 147_095_552


def test_flops_per_token_is_palm_count():
    q = _cfg("qwen3-0.6b")
    matmul = 82_372_608 - 4 * (2 * 128 + 2 * 1024) - 1024
    assert decoder.matmul_params(q) == matmul
    assert flops.flops_per_token(q, 2048) == 6 * matmul + 12 * 4 * 2048 * 2048
    o = dict(_cfg("olmo-1b"), num_hidden_layers=2)
    assert flops.flops_per_token(o, 2048) == (6 * 147_095_552
                                              + 12 * 2 * 2048 * 2048)


def test_tokens_and_quantizer_bytes_per_round():
    assert flops.tokens_per_round(TRAIN) == 2 * (4 + 2 * 4) * 2048 == 49_152
    # two x broadcasts and two z messages, float32 read + int8 written
    assert flops.messages_per_round(TRAIN) == 4
    assert flops.quantize_bytes_per_round(_cfg("qwen3-0.6b"), TRAIN) == (
        4 * 82_372_608 * 5)


@pytest.mark.parametrize("name", ["qwen3-0.6b", "olmo-1b"])
def test_param_count_is_the_plane_the_reference_packs(name):
    cfg = _cfg(name)
    plane = sum(math.prod(shape)
                for shape, _, _ in decoder.leaf_specs(cfg).values())
    assert flops.param_count(cfg) == decoder.param_count(cfg) == plane


def test_counts_come_from_the_configurations_reference_module(monkeypatch):
    own = types.ModuleType("bench.ref.own_block")
    own.param_count = lambda c: 1000
    own.flops_per_token = lambda c, seq_len: 7 * seq_len
    monkeypatch.setitem(sys.modules, "bench.ref.own_block", own)
    cfg = {"reference": "bench/ref/own_block.py"}
    assert flops.param_count(cfg) == 1000
    assert flops.flops_per_round(cfg, TRAIN) == 49_152 * 7 * 2048
    assert flops.quantize_bytes_per_round(cfg, TRAIN) == 4 * 1000 * 5


@pytest.mark.parametrize("agents", [2, 3, 4, 5, 8])
def test_ring_neighbours_are_the_systems_ring(agents):
    from repro.core.topology import Ring

    ring = Ring(agents)
    table, mask = ring.neighbor_table(), ring.slot_mask()
    system = [sorted({int(j) for j in table[i][mask[i]]})
              for i in range(agents)]
    assert ltadmm.neighbours("ring", agents) == system
    degree = 1 if agents == 2 else 2
    assert all(len(n) == degree for n in system)
    ring_train = dict(TRAIN, agents=agents, topology="ring")
    assert flops.messages_per_round(ring_train) == agents * (1 + degree)


def test_other_graphs_are_refused():
    for name in ("star", "grid2d", "erdos"):
        with pytest.raises(ValueError):
            ltadmm.neighbours(name, 4)
