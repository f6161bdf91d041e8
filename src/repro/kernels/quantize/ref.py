"""Pure-jnp oracle for the quantize kernel (bit-identical semantics)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import prng


def quantize_seeded_ref(s0, s1, x, *, bits=8):
    """Oracle for ``M`` messages ``x [M, n]`` under per-message seed
    pairs ``s0``/``s1`` [M]: the same counter-PRNG kappas and rounding
    as the kernel, materialized in plain jnp.  -> ``(q, scale)``."""
    n = x.shape[-1]
    levels = np.float32(2 ** (bits - 1) - 1)
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(
        jnp.max(jnp.abs(xf), axis=-1), jnp.finfo(jnp.float32).tiny
    )
    mult = levels / scale

    def one(a, b, row, mu):
        kappa = prng.uniform01(
            prng.random_bits((a, b), jnp.arange(n, dtype=jnp.uint32))
        )
        q = jnp.minimum(jnp.floor(jnp.abs(row) * mu + kappa), levels)
        q = jnp.sign(row) * q
        if bits == 8:
            return q.astype(jnp.int8)
        qi = q.astype(jnp.int32) + 8
        if n % 2:
            qi = jnp.concatenate([qi, jnp.full((1,), 8, jnp.int32)])
        return ((qi[0::2] << 4) | qi[1::2]).astype(jnp.uint8)

    return jax.vmap(one)(s0, s1, xf, mult), scale


def quantize_plane_ref(seed, sids, rids, x, *, bits=8):
    """Oracle for the fused plane quantizer: per-message seeds folded
    from ``(seed, sender, receiver)``, then ``quantize_seeded_ref``."""
    lead, n = x.shape[:-1], x.shape[-1]
    sids = jnp.broadcast_to(
        jnp.uint32(0) if sids is None else sids, lead
    ).reshape(-1)
    rids = jnp.broadcast_to(
        prng.BROADCAST if rids is None else rids, lead
    ).reshape(-1)
    s0, s1 = prng.fold(seed, sids.astype(jnp.uint32), rids.astype(jnp.uint32))
    q, scale = quantize_seeded_ref(s0, s1, x.reshape(-1, n), bits=bits)
    return q.reshape(lead + q.shape[-1:]), scale.reshape(lead)
