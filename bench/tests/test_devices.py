"""The reference with its agents over several devices computes what it
computes on one.

Four virtual CPU devices need ``XLA_FLAGS`` before JAX starts, so the
comparison runs in a process of its own: a 4-agent ring at the system's
qwen3 smoke size, its agents over four devices and then all on the
first, three rounds logged after each."""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CHILD = r"""
import json, sys
import jax
import numpy as np
from bench.ref import ltadmm

model = json.loads(open("bench/tests/data/tiny.json").read())
train = {"agents": 4, "topology": "ring", "rho": 0.1, "beta": 0.005,
         "gamma": 0.05, "r": 1.0, "eta": 1.0, "bits": 8, "tau": 2,
         "batch_size": 1, "m_local": 4, "seq_len": 32, "heterogeneity": 0.7}
devs = jax.devices()
assert len(devs) == 4, devs
out = {}
for name, where in (("four", devs), ("one", devs[:1])):
    ref = ltadmm.Reference(model, train, devices=where)
    with jax.default_matmul_precision("highest"):
        res = ref.run(2**31 + 7, 3, 1)
    out[name] = {
        "placed": [ref.device(i).id for i in range(4)],
        "losses": res["losses"], "consensus": res["consensus"],
        "xbar": {k: np.asarray(v).tolist() for k, v in res["xbar"].items()},
        "grad0": {k: np.asarray(v).tolist() for k, v in res["grad0"].items()},
        "on": sorted({d.id for v in res["xbar"].values() for d in v.devices()}),
    }
print(json.dumps(out))
"""


def test_reference_over_four_devices_matches_one():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    run = subprocess.run([sys.executable, "-c", CHILD], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stderr[-4000:]
    out = json.loads(run.stdout.strip().splitlines()[-1])
    four, one = out["four"], out["one"]
    assert four["placed"] == [0, 1, 2, 3] and one["placed"] == [0] * 4
    # the results are gathered on the first device
    assert four["on"] == one["on"] == [0]
    assert len(four["losses"]) == len(four["consensus"]) == 3
    for key in ("losses", "consensus", "xbar", "grad0"):
        assert four[key] == one[key], key


def test_ring_check_refuses_without_four_chips(capsys):
    """Four agents on one chip would compare a chip with itself: the
    four-chip check runs on four TPU chips or not at all."""
    from bench.tools import ring_check

    assert ring_check.main(["--seeds", "1-1"]) == 3
    out = capsys.readouterr()
    assert out.out == "" and "TPU" in out.err
