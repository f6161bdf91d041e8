"""The control: the reference in bfloat16 (solver state and products)
put in the program's place must come out not correct against the
float32 reference, under every cell's limits.  On the chip the same
comparison runs at each cell's own size (``bench/tools/readings.py``);
here at the system's qwen3 smoke size."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import check
from bench.ref import ltadmm

DATA = Path(__file__).resolve().parent / "data"
LIMITS = sorted((Path(__file__).resolve().parents[1] / "limits").glob("*.json"))
TRAIN = {"agents": 2, "topology": "complete", "rho": 0.1, "beta": 0.005,
         "gamma": 0.05, "r": 1.0, "eta": 1.0, "bits": 8, "tau": 2,
         "batch_size": 1, "m_local": 4, "seq_len": 32, "heterogeneity": 0.7}


@pytest.fixture(scope="module")
def runs():
    model = json.loads((DATA / "tiny.json").read_text())
    with jax.default_matmul_precision("highest"):
        ref = ltadmm.Reference(model, TRAIN).run(11, 3, 1)
    ctl = ltadmm.Reference(model, TRAIN, plane_dtype=jnp.bfloat16,
                           compute_dtype=jnp.bfloat16).run(11, 3, 1)
    return ref, ctl


@pytest.mark.parametrize("limits", LIMITS, ids=lambda p: p.stem)
def test_control_fails_every_cell(runs, limits):
    ref, ctl = runs
    ref = dict(ref, wire_per_round=0)
    checks = check.compare(check.reference_as_program(ctl), ref,
                           json.loads(limits.read_text()))
    assert not check.passed(checks), checks


def test_reference_agrees_with_itself(runs):
    ref, _ = runs
    same = check.compare(check.reference_as_program(ref), dict(ref, wire_per_round=0),
                         json.loads(LIMITS[0].read_text()))
    assert check.passed(same), same
    assert all(v == 0 for v, _ in same.values())
