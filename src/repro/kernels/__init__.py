"""Pallas TPU kernels for the paper's compression hot spots (quantize,
sparse gather/scatter), plus attention and SSM-scan kernels for the
model zoo.  Each kernel package holds ``kernel.py`` (the Pallas
kernels), ``ops.py`` (padding/layout wrappers) and ``ref.py`` (pure-jnp
oracles)."""
from __future__ import annotations

import jax


def resolve_interpret(interpret):
    """``None`` -> auto by backend: compiled on TPU (where the Mosaic
    pipeline exists), interpret everywhere else (CPU tests/CI).  Explicit
    True/False always wins."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
