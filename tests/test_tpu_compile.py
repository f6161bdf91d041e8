"""Compile rehearsal for one TPU v5e chip, with no chip attached.

The TPU compiler is installed with jaxlib, so the kernels of the main
training path can be compiled for a described ``v5e:2x2`` topology at
real plane widths: tiling, block-shape, cast and VMEM refusals surface
here, in interpret-free Mosaic lowering, instead of on the chip.  Nothing
runs — parity on the chip is ``chip_smoke.py``'s job.

The topology is described inside a module fixture (never at import):
only one process may load the TPU library, and the test runner imports
this file in every worker.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import prng
from repro.kernels.quantize import kernel as q_kernel
from repro.kernels.quantize import ops as q_ops
from repro.kernels.sparse_gather import kernel as sg_kernel
from repro.kernels.sparse_gather import ops as sg_ops

M = 4  # messages per plane: [A, S] = [2, 2]
N = (1 << 22) + 123  # plane width: ~4.2M parameters, not BLOCK-aligned


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def compiled_kernels(monkeypatch):
    """Kernels compile for the TPU instead of interpreting, and nothing
    is written to (or read from) the persistent compilation cache: a
    TPU executable cannot be loaded back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache

    def compiled(interpret):
        return False if interpret is None else bool(interpret)

    for mod in (q_kernel, sg_kernel):
        monkeypatch.setattr(mod, "resolve_interpret", compiled)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _plane_args(one_chip, width):
    u32 = jnp.uint32
    seed = (_sds(one_chip, (), u32), _sds(one_chip, (), u32))
    ids = _sds(one_chip, (M,), u32)
    return seed, ids, ids, _sds(one_chip, (M, width), jnp.float32)


@pytest.mark.parametrize("bits", [8, 4])
def test_quantize_plane_compiles(one_chip, bits):
    _compile(
        lambda s, a, b, x: q_ops.quantize_plane(s, a, b, x, bits=bits),
        *_plane_args(one_chip, N),
    )


def test_quantize_leaf_compiles(one_chip):
    """The tree path's per-leaf quantizer is the plane kernel, M = 1."""
    _compile(
        lambda k, x: q_ops.quantize_tensor(k, x, bits=4),
        _sds(one_chip, (2,), jnp.uint32),
        _sds(one_chip, (N,), jnp.float32),
    )


@pytest.mark.parametrize("sampler", ["block", "stride"])
def test_randk_plane_kernels_compile(one_chip, sampler):
    strides = (1,) if sampler == "block" else prng.coprime_strides(N)
    k = N // 4
    seed, sids, rids, x = _plane_args(one_chip, N)
    _compile(
        lambda s, a, b, xx: sg_ops.randk_gather_plane(
            s, a, b, xx, k=k, strides=strides
        ),
        seed, sids, rids, x,
    )
    _compile(
        lambda s, a, b, v: sg_ops.randk_scatter_plane(
            s, a, b, v, n=N, gain=N / k, strides=strides
        ),
        seed, sids, rids, _sds(one_chip, (M, k), jnp.float32),
    )


def test_ltadmm_round_compiles_with_pallas_quantizer(one_chip):
    """One LT-ADMM round of the smoke qwen3 model on the packed plane,
    qbit through the fused Pallas quantizer."""
    from repro.configs import ARCHS
    from repro.core.schedule import build_graph
    from repro.core.solver import make_solver
    from repro.launch.steps import TrainRecipe, build_estimator, model_specs
    from repro.models.common import abstract_params

    arch = ARCHS["qwen3-0.6b"]
    cfg = arch.make_smoke()
    agents = 2
    graph, ex = build_graph("complete", agents)
    recipe = TrainRecipe(tau=2, batch_size=2,
                         compressor="qbit:bits=8,impl=pallas")
    solver = make_solver(
        "ltadmm", graph, ex, build_estimator(arch, cfg, recipe, "vr"),
        defaults=recipe.solver_defaults("ltadmm"),
    )
    params = abstract_params(model_specs(arch, cfg), cfg.dtype)
    x0 = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((agents,) + s.shape, s.dtype), params
    )
    state = jax.tree.map(
        lambda s: _sds(one_chip, s.shape, s.dtype),
        jax.eval_shape(solver.init, x0),
    )
    data = {"tokens": _sds(one_chip, (agents, 4, 33), jnp.int32)}
    _compile(
        lambda st, d, seed: solver.step(st, d, jax.random.key(seed)),
        state, data, _sds(one_chip, (), jnp.int32),
    )
