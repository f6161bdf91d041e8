"""Jitted public wrappers: sparse/affine gather-scatter on flat planes.

Padding, the lane-dense ``[M, rows, 128]`` view, per-message
``(offset, stride)`` derivation and gain handling live here; the kernels
in ``kernel.py`` see only aligned shapes.  Exposed to the trainer
through ``core.compression.RandK``/``TopK`` with ``impl=pallas``
(``impl=auto`` on a TPU picks the affine kernels for the block and
stride samplers) — the index derivation is untouched, so the kernel
path is bit-identical to the jnp path (validated in tests/test_kernels.py
and tests/test_plane_kernels.py).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import prng
from repro.kernels.quantize.ops import plane_ids
from repro.kernels.sparse_gather.kernel import (
    BLOCK,
    LANES,
    TILE_ROWS,
    affine_gather as _affine_gather_kernel,
    affine_scatter as _affine_scatter_kernel,
    gather as _gather_kernel,
    scatter as _scatter_kernel,
)


def _pad_to(arr, size, fill=0):
    if arr.shape[0] == size:
        return arr
    return jnp.concatenate(
        [arr, jnp.full((size - arr.shape[0],), fill, arr.dtype)]
    )


def sparse_gather(x, idx, *, interpret=None):
    """out[j] = x[idx[j]] for arbitrary in-range indices ([k] <- [n])."""
    k = idx.shape[0]
    k_pad = -(-k // BLOCK) * BLOCK
    out = _gather_kernel(
        x, _pad_to(idx.astype(jnp.int32), k_pad), interpret=interpret
    )
    return out[:k]


def sparse_scatter(values, idx, n, gain=1.0, *, interpret=None):
    """zeros(n).at[idx].set(gain * values) for unique in-range indices."""
    return _scatter_kernel(
        values, idx.astype(jnp.int32), gain, n=n, interpret=interpret
    )


def _rows(length, multiple=1):
    """Rows of 128 lanes covering ``length`` elements, rounded up to a
    multiple of ``multiple`` rows."""
    r = -(-length // LANES)
    return -(-r // multiple) * multiple


def _as_rows(xf, rows):
    """``[M, len]`` -> zero-padded ``[M, rows, 128]``."""
    m, length = xf.shape
    return jnp.pad(xf, ((0, 0), (0, rows * LANES - length))).reshape(
        m, rows, LANES
    )


def randk_gather(x, off, stride, k, *, interpret=None):
    """``out[m, j] = x[m, (off[m] + j * stride[m]) % n]`` for ``x [M,
    n]`` and ``j < k`` — one launch for all M messages."""
    m, n = x.shape
    out = _affine_gather_kernel(
        off.astype(jnp.int32), stride.astype(jnp.int32),
        _as_rows(x, _rows(n, 8)), n=n, out_rows=_rows(k, TILE_ROWS),
        interpret=interpret,
    )
    return out.reshape(m, -1)[:, :k]


def randk_scatter(v, off, stride, n, gain, *, interpret=None):
    """Inverse of ``randk_gather``: ``[M, k]`` values onto ``[M, n]``
    zero planes at the affine index sets, times ``gain``."""
    m, k = v.shape
    out = _affine_scatter_kernel(
        off.astype(jnp.int32), stride.astype(jnp.int32),
        _as_rows(v, _rows(k, 8)), n=n, k=k, gain=float(gain),
        out_rows=_rows(n, 8), interpret=interpret,
    )
    return out.reshape(m, -1)[:, :n]


def _plane_affine(seed, sids, rids, lead, n, strides):
    """Per-message ``(off, stride)`` [M] of a plane: seed pairs folded
    from ``(seed, sender, receiver)`` — M scalars, no index arrays."""
    es = prng.fold(
        seed, plane_ids(sids, lead, 0), plane_ids(rids, lead, prng.BROADCAST)
    )
    return prng.affine_params(es, n, strides)


def randk_gather_plane(seed, sids, rids, x, *, k, strides, interpret=None):
    """Fused RandK compress of a batch of messages ``x [..., n]`` — one
    Pallas launch for the whole plane, index sets walked in-kernel from
    each message's seeded ``(offset, stride)`` (``rids=None`` marks
    one-to-all broadcast messages).  Returns ``[..., k]``."""
    lead, n = x.shape[:-1], x.shape[-1]
    off, stride = _plane_affine(seed, sids, rids, lead, n, strides)
    out = randk_gather(x.reshape(-1, n), off, stride, k, interpret=interpret)
    return out.reshape(lead + (k,))


def randk_scatter_plane(seed, sids, rids, v, *, n, gain, strides,
                        interpret=None):
    """Fused RandK decompress of ``v [..., k]`` back onto zero planes
    ``[..., n]`` — index sets re-derived in-kernel, never in HBM."""
    lead, k = v.shape[:-1], v.shape[-1]
    off, stride = _plane_affine(seed, sids, rids, lead, n, strides)
    out = randk_scatter(
        v.reshape(-1, k), off, stride, n, gain, interpret=interpret
    )
    return out.reshape(lead + (n,))


def cyclic_gather(x, off, k, *, interpret=None):
    """out[j] = x[(off + j) % n] — RandK block-sampler compress of one
    flat tensor (the affine kernel with stride 1)."""
    n = x.shape[0]
    off = jnp.reshape(jnp.mod(off, n), (1,))
    return randk_gather(
        x[None], off, jnp.ones((1,), jnp.int32), k, interpret=interpret
    )[0]


def cyclic_scatter(values, off, n, gain=1.0, *, interpret=None):
    """zeros(n) with gain * values written at (off + j) % n — RandK
    block-sampler decompress of one flat tensor."""
    off = jnp.reshape(jnp.mod(off, n), (1,))
    return randk_scatter(
        values[None], off, jnp.ones((1,), jnp.int32), n, gain,
        interpret=interpret,
    )[0]
