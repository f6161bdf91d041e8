"""Pallas TPU kernels: fused sparse gather/scatter on the packed plane.

These are the RandK/TopK compression hot spots of LT-ADMM-CC once the
parameters live on a packed ``[N]`` plane (``core/packing.py``): compress
is "pick k of N values", decompress is "scatter k values back into an
N-zeros plane with a gain".  Two index regimes, two kernel families:

* **affine** (RandK ``sampler="block"``, stride 1, and
  ``sampler="stride"``): the k indices are ``(off + j * stride) % n``.
  ``affine_gather``/``affine_scatter`` take each message's
  ``(off, stride)`` as scalar-prefetch (SMEM) operands and walk the
  index set in-kernel, so no index array exists in HBM.  A v5e vector
  unit cannot load from arbitrary VMEM addresses, so each element is
  one dynamic-row load plus a lane rotate; the message row stays
  resident in VMEM.
* **arbitrary indices** (RandK ``sampler="uniform"``, TopK):
  ``gather``/``scatter`` index per element (``x_ref[idx]``), which the
  TPU compiler refuses ("Cannot do int indexing on TPU").  They run in
  interpret mode only; on a TPU those compressors resolve ``impl=auto``
  to jnp (``core.compression.COMPRESSORS``).

All kernels validate bit-exactly against ``ref.py`` — the index
derivation stays seed-synchronized with ``core.compression``, so the
kernel path changes zero math, only op count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import prng, resolve_interpret

BLOCK = 1024  # elements per VMEM tile (multiple of 128 lanes)
LANES = 128
TILE_ROWS = 8  # affine_gather output tile: (8, 128) f32
# VMEM a resident affine-kernel row may take (v5e has 128 MiB)
VMEM_BUDGET = 96 * 2**20


# ---------------------------------------------------------------------------
# Arbitrary-index gather / scatter
# ---------------------------------------------------------------------------


def _gather_kernel(idx_ref, x_ref, out_ref):
    out_ref[...] = x_ref[idx_ref[...]]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather(x_pad, idx_pad, *, interpret=None):
    """out[j] = x_pad[idx_pad[j]] — grid over index tiles, x resident.

    ``idx_pad`` length must be a BLOCK multiple (pad with 0 and slice the
    result); every index must be in range.
    """
    interpret = resolve_interpret(interpret)
    (k,), (n,) = idx_pad.shape, x_pad.shape
    assert k % BLOCK == 0, k
    return pl.pallas_call(
        _gather_kernel,
        grid=(k // BLOCK,),
        in_specs=[
            pl.BlockSpec((BLOCK,), lambda i: (i,)),
            pl.BlockSpec((n,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((BLOCK,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((k,), x_pad.dtype),
        interpret=interpret,
        name="sparse_gather",
    )(idx_pad, x_pad)


def _scatter_kernel(idx_ref, v_ref, gain_ref, out_ref):
    zeros = jnp.zeros(out_ref.shape, out_ref.dtype)
    out_ref[...] = zeros.at[idx_ref[...]].set(gain_ref[0] * v_ref[...])


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def scatter(values, idx, gain, *, n, interpret=None):
    """out = zeros(n); out[idx[j]] = gain * values[j] (unique indices).

    Single grid step: the whole plane is materialized in one scatter —
    right-sized for message planes that fit VMEM; the cyclic variant
    below is the tiled path.
    """
    interpret = resolve_interpret(interpret)
    (k,) = idx.shape
    gain = jnp.reshape(jnp.asarray(gain, values.dtype), (1,))
    return pl.pallas_call(
        _scatter_kernel,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((k,), lambda i: (0,)),
            pl.BlockSpec((k,), lambda i: (0,)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((n,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((n,), values.dtype),
        interpret=interpret,
        name="sparse_scatter",
    )(idx, values, gain)


# ---------------------------------------------------------------------------
# Affine-index gather / scatter (RandK block and stride samplers)
# ---------------------------------------------------------------------------


def _vmem_limit(n, *row_bytes):
    """Scoped-VMEM request for double-buffered resident rows; a message
    too large for VMEM (or for int32 index walks) fails here, before
    compiling."""
    assert n <= prng.MAX_N, n
    need = 2 * sum(row_bytes) + 2**20
    if need > VMEM_BUDGET:
        raise ValueError(
            f"affine RandK kernel needs {need} B of VMEM for one message "
            f"(budget {VMEM_BUDGET} B); use impl=jnp for this plane width"
        )
    return max(need, 16 * 2**20)


def _rotate_to(row, src_lane, dst_lane):
    """Move lane ``src_lane`` of a (1, 128) row to lane ``dst_lane``."""
    return pltpu.roll(row, (dst_lane - src_lane) % LANES, 1)


def _step(idx, stride, n):
    idx = idx + stride
    return jnp.where(idx >= n, idx - n, idx)


def _affine_gather_kernel(off_ref, stride_ref, x_ref, o_ref, *, n):
    m, t = pl.program_id(0), pl.program_id(1)
    off, stride = off_ref[m], stride_ref[m]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    j0 = t * (TILE_ROWS * LANES)
    idx0 = (off + prng.mulmod(j0, stride, n)) % np.int32(n)

    def row(r, idx):
        def elem(c, carry):
            idx, acc = carry
            src = x_ref[pl.ds(idx // LANES, 1), :]
            val = _rotate_to(src, idx % LANES, c)
            return _step(idx, stride, n), jnp.where(lane == c, val, acc)

        idx, acc = jax.lax.fori_loop(
            0, LANES, elem, (idx, jnp.zeros((1, LANES), o_ref.dtype))
        )
        o_ref[pl.ds(r, 1), :] = acc
        return idx

    jax.lax.fori_loop(0, TILE_ROWS, row, idx0)


@functools.partial(
    jax.jit, static_argnames=("n", "out_rows", "interpret")
)
def affine_gather(off, stride, x, *, n, out_rows, interpret=None):
    """``out[m, j] = x[m, (off[m] + j * stride[m]) % n]`` for ``j <
    out_rows * 128``.

    ``x [M, R, 128]`` holds each message (true length ``n``, zero-padded)
    as rows of 128 lanes; ``off``/``stride`` [M] int32 with ``0 <= off,
    stride < n``; ``out_rows`` is a multiple of 8.  Indices are reduced
    mod the TRUE n, so padding is never sampled.  Returns ``[M,
    out_rows, 128]``.
    """
    interpret = resolve_interpret(interpret)
    m, rows, _ = x.shape
    assert out_rows % TILE_ROWS == 0, out_rows
    itemsize = jnp.dtype(x.dtype).itemsize
    return pl.pallas_call(
        functools.partial(_affine_gather_kernel, n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(m, out_rows // TILE_ROWS),
            in_specs=[
                pl.BlockSpec((None, rows, LANES), lambda m_, t, *_: (m_, 0, 0))
            ],
            out_specs=pl.BlockSpec(
                (None, TILE_ROWS, LANES), lambda m_, t, *_: (m_, t, 0)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((m, out_rows, LANES), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(
                n, rows * LANES * itemsize, TILE_ROWS * LANES * itemsize
            )
        ),
        interpret=interpret,
        name="affine_gather",
    )(off, stride, x)


def _affine_scatter_kernel(off_ref, stride_ref, v_ref, o_ref, *, n, k,
                           gain):
    m = pl.program_id(0)
    stride = stride_ref[m]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def elem(j, idx):
        src = v_ref[pl.ds(j // LANES, 1), :]
        val = _rotate_to(src, j % LANES, idx % LANES)
        val = (gain * val.astype(jnp.float32)).astype(o_ref.dtype)
        dst = pl.ds(idx // LANES, 1)
        o_ref[dst, :] = jnp.where(lane == idx % LANES, val, o_ref[dst, :])
        return _step(idx, stride, n)

    jax.lax.fori_loop(0, k, elem, off_ref[m])


@functools.partial(
    jax.jit, static_argnames=("n", "k", "gain", "out_rows", "interpret")
)
def affine_scatter(off, stride, v, *, n, k, gain, out_rows,
                   interpret=None):
    """Inverse of ``affine_gather``: ``out[m] = zeros`` with ``out[m,
    (off[m] + j * stride[m]) % n] = gain * v[m, j]`` for ``j < k``.

    ``v [M, Rk, 128]`` holds the k values per message (pad lanes are
    never read); returns ``[M, out_rows, 128]``, rows of 128 lanes
    covering at least n elements (one grid step per message).
    """
    interpret = resolve_interpret(interpret)
    m, rows, _ = v.shape
    itemsize = jnp.dtype(v.dtype).itemsize
    return pl.pallas_call(
        functools.partial(
            _affine_scatter_kernel, n=n, k=k, gain=np.float32(gain)
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(m,),
            in_specs=[
                pl.BlockSpec((None, rows, LANES), lambda m_, *_: (m_, 0, 0))
            ],
            out_specs=pl.BlockSpec(
                (None, out_rows, LANES), lambda m_, *_: (m_, 0, 0)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((m, out_rows, LANES), v.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(
                n, rows * LANES * itemsize, out_rows * LANES * itemsize
            )
        ),
        interpret=interpret,
        name="affine_scatter",
    )(off, stride, v)
