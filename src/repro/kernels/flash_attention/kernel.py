"""Pallas TPU kernel: blockwise causal flash attention with GQA and
optional sliding window.

Schedule (TPU-adapted: VMEM-resident accumulators, MXU-shaped tiles):
  grid = (batch, q_heads, n_q_blocks, n_kv_blocks); the kv-block axis is the
  innermost sequential dimension, so the (acc, m, l) scratch carries the
  online-softmax state across kv blocks for a fixed (b, h, iq).  K/V blocks
  for query head h come from kv head h // group via the BlockSpec index map —
  GQA without materializing repeated heads.  Block shapes default to
  (128, head_dim): MXU-aligned (128 lanes) and small enough that
  q + k + v + acc tiles fit VMEM comfortably (4 x 128 x 128 x 4B = 256 KiB).

Layout: q [B, H, T, Dh]; k, v [B, KH, S, Dh]; out [B, H, T, Dh].
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

DEFAULT_Q_BLOCK = 128
DEFAULT_KV_BLOCK = 128
_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  causal, window, scale, kv_len):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)
    qb = q_ref.shape[-2]
    kb = k_ref.shape[-2]

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # [qb, dh]
    k = k_ref[0, 0].astype(jnp.float32)  # [kb, dh]
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # [qb, kb]

    rows = iq * qb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 0)
    cols = ik * kb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 1)
    mask = cols < kv_len
    if causal:
        mask &= rows >= cols
    if window is not None:
        mask &= (rows - cols) < window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[...]  # [qb, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)  # [qb, 1]
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_block", "kv_block", "interpret"),
)
def flash_attention(q, k, v, *, causal=True, window=None,
                    q_block=DEFAULT_Q_BLOCK, kv_block=DEFAULT_KV_BLOCK,
                    interpret=None):
    """q [B,H,T,Dh]; k,v [B,KH,S,Dh] -> [B,H,T,Dh].  T % q_block == 0;
    S is padded to kv_block internally (masked).  ``interpret=None``:
    compiled on TPU, interpret mode elsewhere."""
    interpret = resolve_interpret(interpret)
    b, h, t, dh = q.shape
    kh, s_len = k.shape[1], k.shape[2]
    g = h // kh
    q_block = min(q_block, t)
    assert t % q_block == 0, (t, q_block)
    pad_s = (-s_len) % kv_block
    if pad_s:
        zpad = jnp.zeros((b, kh, pad_s, dh), k.dtype)
        k = jnp.concatenate([k, zpad], axis=2)
        v = jnp.concatenate([v, zpad], axis=2)
    nq = t // q_block
    nk = k.shape[2] // kv_block
    scale = 1.0 / math.sqrt(dh)

    kernel = functools.partial(
        _flash_kernel, causal=causal, window=window, scale=scale,
        kv_len=s_len,
    )
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, q_block, dh),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, kv_block, dh),
                         lambda bi, hi, qi, ki, g=g: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, kv_block, dh),
                         lambda bi, hi, qi, ki, g=g: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_block, dh),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, t, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block, dh), jnp.float32),
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
