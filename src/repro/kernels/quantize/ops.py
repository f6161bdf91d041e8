"""Jitted public wrappers: quantize/dequantize message planes and tensors.

Handles the per-message seeds, the inf-norm scale pass, padding and the
lane-dense ``[M, rows, L]`` view the kernel streams; exposes the
(compress, decompress) pieces ``repro.core.compression.BBitQuantizer``
uses with ``impl=pallas`` (or ``impl=auto`` on a TPU).
"""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from repro.kernels import prng
from repro.kernels.quantize.kernel import lane_width, quantize_rows, tile_rows


def wire_len(n, bits):
    """Exact wire bytes of the quantized stream: one int8 per element
    (b=8) or one nibble-packed uint8 per element pair (b=4)."""
    return n if bits == 8 else -(-n // 2)


def _quantize_seeded(s0, s1, xf, *, bits, interpret):
    """``xf [M, n]`` under per-message seed pairs ``s0``/``s1`` [M] ->
    ``(q [M, wire_len], scale [M])``."""
    m, n = xf.shape
    # per-row inf-norm, floored so an all-zero row stays finite
    scale = jnp.maximum(
        jnp.max(jnp.abs(xf), axis=-1), jnp.finfo(jnp.float32).tiny
    )
    mult = np.float32(2 ** (bits - 1) - 1) / scale
    width = lane_width(bits)
    rows = -(-n // width)
    tb = tile_rows(rows)
    rows = -(-rows // tb) * tb
    xf = jnp.pad(xf, ((0, 0), (0, rows * width - n)))
    q = quantize_rows(
        s0, s1, mult, xf.reshape(m, rows, width), bits=bits,
        interpret=interpret,
    )
    return q.reshape(m, -1)[:, : wire_len(n, bits)], scale


def plane_ids(ids, lead, fill):
    """Per-message ids of a ``lead``-shaped message batch, flattened to
    [M] uint32 (``None`` -> ``fill`` for every message)."""
    if ids is None:
        return jnp.full((max(math.prod(lead), 1),), fill, jnp.uint32)
    return jnp.broadcast_to(ids, lead).reshape(-1).astype(jnp.uint32)


def quantize_plane(seed, sids, rids, x, *, bits=8, interpret=None):
    """Fused quantization of a batch of messages ``x [..., n]`` — ONE
    Pallas launch for the whole plane, stochastic-rounding bits derived
    in-kernel from ``(seed, sender, receiver, element)`` so no random
    stream is materialized in HBM.  ``rids=None`` marks one-to-all
    broadcast messages.  Returns ``(q [..., wire_len], scale [...])``.
    """
    lead, n = x.shape[:-1], x.shape[-1]
    s0, s1 = prng.fold(
        seed, plane_ids(sids, lead, 0), plane_ids(rids, lead, prng.BROADCAST)
    )
    q, scale = _quantize_seeded(
        s0, s1, x.reshape(-1, n).astype(jnp.float32), bits=bits,
        interpret=interpret,
    )
    return q.reshape(lead + q.shape[-1:]), scale.reshape(lead)


def dequantize_plane(q, scale, *, n, bits=8, out_dtype=jnp.float32):
    """Elementwise inverse of ``quantize_plane`` (no PRNG needed) — a
    plain jnp expression XLA fuses on its own."""
    levels = float(2 ** (bits - 1) - 1)
    if bits == 8:
        qf = q.astype(jnp.float32)
    else:
        p = q.astype(jnp.int32)
        hi = ((p >> 4) & 0xF) - 8
        lo = (p & 0xF) - 8
        qf = jnp.stack([hi, lo], axis=-1).reshape(q.shape[:-1] + (-1,))
        qf = qf[..., :n].astype(jnp.float32)
    return (scale[..., None] * qf / levels).astype(out_dtype)


def quantize_tensor(key, x, *, bits=8, interpret=None):
    """One tensor as a one-message plane whose seed pair is the key's
    own (``prng.key_seed``).  Returns payload {"q", "scale"} with exact
    wire bytes (the pad tail never travels)."""
    s0, s1 = prng.key_seed(key)
    q, scale = _quantize_seeded(
        s0[None], s1[None], jnp.reshape(x, (1, -1)).astype(jnp.float32),
        bits=bits, interpret=interpret,
    )
    return {"q": q[0], "scale": scale[0]}


def dequantize_tensor(payload, shape, dtype=jnp.float32, *, bits=8):
    x = dequantize_plane(
        payload["q"], payload["scale"], n=math.prod(shape), bits=bits,
        out_dtype=dtype,
    )
    return jnp.reshape(x, shape)
