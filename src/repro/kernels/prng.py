"""Counter-based PRNG (threefry2x32) usable INSIDE Pallas kernel bodies.

The fused compression kernels (``kernels/quantize``,
``kernels/sparse_gather``) generate their randomness on the fly inside
the kernel — stochastic-rounding kappas and RandK index sets are derived
from a (seed, counter) pair with plain 32-bit integer arithmetic, so no
random stream is ever materialized in HBM and no index array ever hits
the wire.  That requires a PRNG that is

* **counter-based** — bits at position ``j`` are a pure function of
  ``(seed, j)``, so a grid tile can produce exactly its slice of the
  stream with no carried state;
* **backend-deterministic** — the same ops give the same bits on
  compiled TPU, in Pallas interpret mode, and in plain traced jnp
  (``pltpu.prng_random_bits`` is none of these: it is stateful per-core
  hardware RNG), which is what lets ``ref.py`` oracles pin the kernels
  bit-exactly and lets sender/receiver stay seed-synchronized across
  heterogeneous deployments.

The block cipher is standard Threefry-2x32 with 20 rounds (the same
family JAX's own PRNG uses) — adds, XORs and rotations on ``uint32``
only, all of which the TPU VPU executes natively.  This module is
deliberately dependency-free in both directions: the functions are plain
jnp expressions, so the SAME code runs inside a Pallas kernel body and
in the pure-jnp reference/compressor paths.

Seed-derivation conventions used by the compression stack:

* ``key_seed(key)`` turns a ``jax.random`` key into the ``(u32, u32)``
  seed pair (via ``key_data`` — the fold_in chain that produced the key
  is therefore inherited);
* ``message_seed(seed, sender, receiver)`` derives the per-message seed
  both endpoints of an edge agree on (``BROADCAST`` as the receiver id
  for one-to-all x-messages);
* ``derive_offset``/``derive_stride_slot`` + ``affine_indices`` define
  the seeded affine index family ``(off + j * stride) % n`` shared by
  the RandK ``block`` (stride 1) and ``stride`` (seeded coprime stride)
  samplers — exact-k, duplicate-free, unbiased (every coordinate lies
  in exactly k of the n windows for any fixed stride coprime to n).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

_PARITY = np.uint32(0x1BD11BDA)
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))

# receiver id of a one-to-all message (x broadcasts): folded in place of
# a peer id so broadcast and per-edge streams never collide
BROADCAST = np.uint32(0xFFFFFFFF)

# largest message length the affine index family supports: every
# intermediate of ``mulmod`` and of the kernels' index walk stays int32
MAX_N = 2**30


def _u32(x):
    if isinstance(x, int):  # a Python int in [2^31, 2^32) overflows int32
        return jnp.asarray(np.uint32(x))
    return jnp.asarray(x).astype(jnp.uint32)


def _rotl(x, r: int):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def threefry2x32(k0, k1, c0, c1):
    """One Threefry-2x32-20 block: hash counter ``(c0, c1)`` under key
    ``(k0, k1)``.  All inputs broadcastable ``uint32`` arrays; returns
    two ``uint32`` arrays of the broadcast shape.  Pure function of its
    inputs — safe to recompute per grid tile."""
    k0, k1, x0, x1 = _u32(k0), _u32(k1), _u32(c0), _u32(c1)
    ks = (k0, k1, k0 ^ k1 ^ _PARITY)
    x0 = x0 + k0
    x1 = x1 + k1
    for i in range(5):
        for r in _ROTATIONS[i % 2]:
            x0 = x0 + x1
            x1 = _rotl(x1, r) ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + np.uint32(i + 1)
    return x0, x1


def fold(seed, *ids):
    """Absorb integer ids into a seed pair, one cipher block per id (the
    counter lane carries the fold depth so ``fold(s, a, b)`` never
    collides with ``fold(s, b, a)`` or ``fold(s, a)``)."""
    s0, s1 = seed
    for depth, d in enumerate(ids):
        s0, s1 = threefry2x32(s0, s1, _u32(d), np.uint32(depth))
    return s0, s1


def message_seed(seed, sender, receiver=None):
    """The per-message seed pair both endpoints derive independently.
    ``receiver=None`` marks a one-to-all broadcast (x-messages)."""
    rid = BROADCAST if receiver is None else receiver
    return fold(seed, sender, rid)


def key_seed(key):
    """``jax.random`` key (typed or raw uint32[..., 2]) -> seed pair."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return _u32(key[..., 0]), _u32(key[..., 1])


def random_bits(seed, ctr, stream=0):
    """uint32 stream at counter positions ``ctr`` (any-shape array);
    ``stream`` separates independent draws under one seed."""
    b0, _ = threefry2x32(seed[0], seed[1], _u32(ctr), _u32(stream))
    return b0


def uniform01(bits):
    """uint32 bits -> f32 in [0, 1 - 2^-24] (the stochastic-rounding
    kappa): the top 24 bits, exact in f32.  Goes through int32 because
    the TPU compiler has no uint32 -> float32 cast, and a full 32-bit
    value would round up to exactly 1.0 for the top 2^7 bit patterns."""
    return (bits >> np.uint32(8)).astype(jnp.int32).astype(jnp.float32) * (
        np.float32(2.0**-24)
    )


def derive_offset(seed, n: int):
    """Seeded window offset in [0, n) (modulo bias ~ n / 2^32 — orders
    of magnitude below the Monte-Carlo noise of any unbiasedness test
    at wire-message sizes)."""
    b0, _ = threefry2x32(seed[0], seed[1], np.uint32(0), np.uint32(1))
    return (b0 % np.uint32(n)).astype(jnp.int32)


def derive_stride_slot(seed, n_strides: int):
    """Seeded slot into a static coprime-stride table."""
    _, b1 = threefry2x32(seed[0], seed[1], np.uint32(0), np.uint32(1))
    return (b1 % np.uint32(n_strides)).astype(jnp.int32)


def coprime_strides(n: int, size: int = 64) -> tuple:
    """Static (host-computed) table of strides coprime to ``n``, spread
    across [1, n).  Unbiasedness of the affine sampler holds for ANY
    fixed coprime stride (the offset alone uniformizes inclusion), so
    the table only needs diversity, not exact uniformity."""
    assert n >= 1
    if n == 1:
        return (0,)
    out = []
    step = max(1, n // size)
    for i in range(size):
        c = (1 + i * step) % n
        if c == 0:
            c = 1
        while math.gcd(c, n) != 1:
            c = c + 1 if c + 1 < n else 1
        out.append(c)
    return tuple(out)


def mulmod(a, b, n: int):
    """``(a * b) % n`` elementwise without 32-bit overflow, for int32
    ``0 <= a, b < n <= 2^30``: double-and-add over the bits of ``a``,
    every partial sum below 2^31.  Plain jnp, so it runs in kernel
    bodies (scalars) and in the oracles (vectors) alike."""
    assert 1 <= n <= MAX_N, n
    n = np.int32(n)
    r = jnp.zeros(jnp.broadcast_shapes(jnp.shape(a), jnp.shape(b)), jnp.int32)
    for bit in reversed(range(int(n).bit_length())):
        r = r + r
        r = jnp.where(r >= n, r - n, r)
        t = r + b
        t = jnp.where(t >= n, t - n, t)
        r = jnp.where(((a >> bit) & 1) == 1, t, r)
    return r


def affine_params(seed, n: int, strides: tuple):
    """The seeded ``(offset, stride)`` of the affine index family (any
    seed shape: one pair per message)."""
    off = derive_offset(seed, n)
    stride = jnp.asarray(strides, jnp.int32)[
        derive_stride_slot(seed, len(strides))
    ]
    return off, stride


def affine_indices(seed, n: int, k: int, strides: tuple):
    """The seeded affine index set ``(off + j * stride) % n`` for
    ``j < k`` — duplicate-free (stride coprime to n, k <= n), exact-k,
    never materialized by the fused kernels (each tile walks its own
    ``j`` range; THIS function is the jnp oracle).  ``j * stride`` is
    reduced exactly (``mulmod``), so the set stays duplicate-free for
    every n up to ``MAX_N``."""
    off, stride = affine_params(seed, n, strides)
    j = jnp.arange(k, dtype=jnp.int32)
    return (off + mulmod(j, stride, n)) % np.int32(n)
