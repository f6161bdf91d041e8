"""Pallas TPU kernel: fused stochastic b-bit quantization (paper's C1).

This is the compression hot spot of LT-ADMM-CC: every outer round each
agent quantizes its x- and z-messages.  ``quantize_rows`` does a whole
batch of M messages in ONE launch: the stochastic-rounding kappas are
drawn in-kernel from the counter PRNG (``repro.kernels.prng``) under a
per-message seed pair, so no random stream is ever materialized in HBM.

TPU layout:
* each message is viewed as ``[rows, L]`` lane-dense rows (L = 128 for
  b=8, 256 for b=4 so one input row packs into one 128-byte output row)
  and streamed in ``(TB, L)`` tiles, TB a multiple of 32: the f32 input
  sits on the (8, 128) tiling and the int8/uint8 output on (32, 128);
* the per-message seed pair and inf-norm multiplier are scalar-prefetch
  operands (SMEM), read once per grid step on the scalar unit;
* the inf-norm reduction is a separate cheap jnp pass, so the kernel is
  a single elementwise sweep (read f32, write b/8 bytes per element).

Dequantization is elementwise and PRNG-free; it stays plain jnp
(``ops.dequantize_plane``), which XLA fuses into its consumer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import prng, resolve_interpret

LANES = 128
ROW_ALIGN = 32  # int8/uint8 tiling is (32, 128)
MAX_TILE_ROWS = 256  # (256, 128) f32 = 128 KiB per input tile


def lane_width(bits: int) -> int:
    """Elements per kernel row: b=4 packs a 256-element row into one
    128-byte output row."""
    return LANES if bits == 8 else 2 * LANES


def tile_rows(rows: int) -> int:
    """Rows per grid step for a message of ``rows`` kernel rows: the
    whole (32-aligned) message when small, else MAX_TILE_ROWS."""
    return min(MAX_TILE_ROWS, -(-rows // ROW_ALIGN) * ROW_ALIGN)


def pack_nibbles(qi):
    """``[TB, 256]`` int32 offset-8 nibbles -> ``[TB, 128]`` bytes with
    byte c = (q[2c] << 4) | q[2c + 1] — the flat wire order.  Strided
    lane slices do not lower on TPU, so each 128-lane half is compacted
    with an in-vreg lane gather and the halves are selected by lane."""
    tb = qi.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (tb, LANES), 1)
    even = (2 * lane) % LANES
    halves = (qi[:, :LANES], qi[:, LANES:])
    hi = [jnp.take_along_axis(h, even, axis=1) for h in halves]
    lo = [jnp.take_along_axis(h, even + 1, axis=1) for h in halves]
    first = lane < LANES // 2
    return (jnp.where(first, hi[0], hi[1]) << 4) | jnp.where(
        first, lo[0], lo[1]
    )


def _quantize_rows_kernel(s0_ref, s1_ref, mult_ref, x_ref, q_ref, *,
                          levels, bits):
    m, i = pl.program_id(0), pl.program_id(1)
    tb, width = x_ref.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (tb, width), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (tb, width), 1)
    ctr = ((i * tb + row) * width + col).astype(jnp.uint32)
    kappa = prng.uniform01(prng.random_bits((s0_ref[m], s1_ref[m]), ctr))
    x = x_ref[...].astype(jnp.float32)
    q = jnp.minimum(jnp.floor(jnp.abs(x) * mult_ref[m] + kappa), levels)
    q = jnp.sign(x) * q
    if bits == 8:
        q_ref[...] = q.astype(jnp.int8)
    else:
        q_ref[...] = pack_nibbles(q.astype(jnp.int32) + 8).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def quantize_rows(s0, s1, mult, x, *, bits=8, interpret=None):
    """Quantize M messages in ONE launch.

    ``x [M, R, L]`` f32 is each message as ``R`` rows of ``L =
    lane_width(bits)`` elements (R a multiple of ``tile_rows(R)``);
    ``s0``/``s1`` [M] uint32 are the per-message seed pairs and ``mult``
    [M] f32 is ``levels / ||x_m||_inf``.  Element e of message m rounds
    with kappa from ``prng.random_bits((s0[m], s1[m]), e)``.  Returns
    ``[M, R, 128]``: int8 (b=8) or nibble-packed uint8 (b=4).
    """
    interpret = resolve_interpret(interpret)
    m, rows, width = x.shape
    assert width == lane_width(bits), (width, bits)
    tb = tile_rows(rows)
    assert rows % tb == 0, (rows, tb)
    levels = np.float32(2 ** (bits - 1) - 1)
    return pl.pallas_call(
        functools.partial(_quantize_rows_kernel, levels=levels, bits=bits),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(m, rows // tb),
            in_specs=[
                pl.BlockSpec((None, tb, width), lambda m_, i, *_: (m_, i, 0))
            ],
            out_specs=pl.BlockSpec(
                (None, tb, LANES), lambda m_, i, *_: (m_, i, 0)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct(
            (m, rows, LANES), jnp.int8 if bits == 8 else jnp.uint8
        ),
        interpret=interpret,
        name="quantize_rows",
    )(s0, s1, mult, x)
