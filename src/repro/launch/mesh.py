"""Production mesh construction.

Single pod:  16 x 16 = 256 chips, axes ("data", "model").
Multi-pod:   2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis crosses the slow inter-pod links; LT-ADMM-CC's agent graph lives
there in hierarchical mode (DESIGN.md §3).

Defined as FUNCTIONS so importing this module never touches jax device
state; the dry-run sets XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(n_devices=None, model=1):
    """Small CPU mesh for tests: ("data", "model")."""
    n = n_devices or len(jax.devices())
    assert n % model == 0
    return _make_mesh((n // model, model), ("data", "model"))


def agent_axis_for(mesh) -> str:
    """The mesh axis that carries the LT-ADMM-CC agent graph."""
    return "pod" if "pod" in mesh.axis_names else "data"
