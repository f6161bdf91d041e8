"""Pallas TPU kernel: Mamba2 SSD chunked scan.

One pallas_call runs the ENTIRE scan: grid = (batch, heads, n_chunks) with
the chunk axis innermost-sequential, so the recurrent state h [d_state,
head_dim] lives in VMEM scratch across chunk iterations of a fixed (b, head)
— the cross-chunk recurrence never round-trips HBM.  Within a chunk the
intra-chunk term is the (CBᵀ ∘ L) X masked matmul (MXU work), matching the
SSD formulation of Mamba2.

Inputs are head-major and dt-prefolded (x already scaled by dt, alog = dt·A):
    x    [B, NH, T, HD]    alog [B, NH, T]
    bmat [B, NH, T, DS]    cmat [B, NH, T, DS]
Outputs: y [B, NH, T, HD], h_final [B, NH, DS, HD].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

DEFAULT_CHUNK = 128


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, h_ref):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)  # [q, hd]
    al = a_ref[0, 0].astype(jnp.float32)  # [q]
    bm = b_ref[0, 0].astype(jnp.float32)  # [q, ds]
    cm = c_ref[0, 0].astype(jnp.float32)  # [q, ds]
    q = x.shape[0]

    cum = jnp.cumsum(al)  # [q]
    # intra-chunk: (C Bᵀ ∘ L) X, L[t,s] = exp(cum_t - cum_s) for s <= t
    ldiff = cum[:, None] - cum[None, :]
    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    )
    lfac = jnp.where(tri, jnp.exp(ldiff), 0.0)
    cb = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [q, q]
    y = jax.lax.dot_general(
        cb * lfac, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [q, hd]

    # inter-chunk: y += exp(cum_t) * C_t · h_in
    h_in = h_ref[...]  # [ds, hd]
    y = y + jnp.exp(cum)[:, None] * jax.lax.dot_general(
        cm, h_in, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: h = exp(cum_Q) h + Σ_s exp(cum_Q - cum_s) B_s ⊗ x_s
    decay_out = jnp.exp(cum[-1] - cum)  # [q]
    bw = bm * decay_out[:, None]  # [q, ds]
    h_new = jnp.exp(cum[-1]) * h_in + jax.lax.dot_general(
        bw, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [ds, hd]
    h_ref[...] = h_new

    @pl.when(ic == nc - 1)
    def _emit_state():
        hout_ref[0, 0] = h_new.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, alog, bmat, cmat, *, chunk=DEFAULT_CHUNK, interpret=None):
    """Head-major SSD scan.  T % chunk == 0.  ``interpret=None``:
    compiled on TPU, interpret mode elsewhere."""
    interpret = resolve_interpret(interpret)
    b, nh, t, hd = x.shape
    ds = bmat.shape[-1]
    chunk = min(chunk, t)
    assert t % chunk == 0, (t, chunk)
    nc = t // chunk
    y, h_final = pl.pallas_call(
        _ssd_kernel,
        grid=(b, nh, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, hd), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk), lambda bi, hi, ci: (bi, hi, ci)),
            pl.BlockSpec((1, 1, chunk, ds), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, ds), lambda bi, hi, ci: (bi, hi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, hd), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, ds, hd), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nh, t, hd), x.dtype),
            jax.ShapeDtypeStruct((b, nh, ds, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((ds, hd), jnp.float32)],
        interpret=interpret,
    )(x, alog, bmat, cmat)
    return y, h_final
