"""Attention layers: GQA (with qk-norm / QKV-bias / sliding-window options)
and DeepSeek-style MLA (multi-head latent attention).

Both expose:
    *_specs(cfg)                               parameter ParamSpec tree
    *_forward(params, cfg, x, positions)       full-sequence (train/prefill)
    *_init_cache(cfg, batch, cache_len)        decode cache (zeros)
    *_prefill_cache(...)                       cache from a full forward
    *_decode(params, cfg, cache, x, pos)       one-token decode

Sliding-window decode uses a ring-buffer cache of length ``window`` with an
absolute-position side array (slots with pos_id < 0 are invalid), which is
what lets full-attention architectures run the 500k-token long-context shape
with O(window) memory.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.models.common import ParamSpec, apply_rope, rmsnorm

_NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    sliding_window: int | None = None  # None = full causal
    causal: bool = True  # False for encoder self-attention
    # §Perf: mesh axis to shard the QUERY SEQUENCE over during attention —
    # the fix for head counts that do not divide the TP axis (e.g. qwen2's
    # 12 heads on a 16-way axis), where head sharding is impossible and the
    # default is 16x replicated attention compute.  Requires an ambient
    # mesh (jax.set_mesh) at lowering time.
    seq_shard_axis: str | None = None


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def gqa_specs(cfg: AttnConfig):
    d, h, kh, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    specs = {
        "wq": ParamSpec((d, h, dh), ("embed", "heads", "head")),
        "wk": ParamSpec((d, kh, dh), ("embed", "kv_heads", "head")),
        "wv": ParamSpec((d, kh, dh), ("embed", "kv_heads", "head")),
        "wo": ParamSpec((h, dh, d), ("heads", "head", "embed")),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec((h, dh), ("heads", "head"), init="zeros")
        specs["bk"] = ParamSpec((kh, dh), ("kv_heads", "head"), init="zeros")
        specs["bv"] = ParamSpec((kh, dh), ("kv_heads", "head"), init="zeros")
    if cfg.qk_norm:
        specs["q_norm"] = ParamSpec((dh,), ("head",), init="ones")
        specs["k_norm"] = ParamSpec((dh,), ("head",), init="ones")
    return specs


def _project_qkv(params, cfg: AttnConfig, x, positions):
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
    k = jnp.einsum("btd,dhk->bthk", x, params["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    if cfg.qk_norm:
        q = rmsnorm({"scale": params["q_norm"]}, q)
        k = rmsnorm({"scale": params["k_norm"]}, k)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def sdpa(q, k, v, mask, use_flash: bool = False):
    """Grouped scaled-dot-product attention.

    q [B,T,H,Dh]; k,v [B,S,KH,Dh]; mask broadcastable to [B,1,1,T,S] or None.
    When ``use_flash`` and shapes allow, dispatches to the Pallas flash
    kernel (repro.kernels.flash_attention.ops).
    """
    if use_flash:
        from repro.kernels.flash_attention import ops as flash_ops

        if flash_ops.supported(q, k, v, mask):
            # mask is None here: plain full (non-causal) attention
            return flash_ops.flash_attention(q, k, v, causal=False)
    b, t, h, dh = q.shape
    kh = k.shape[2]
    g = h // kh
    qg = q.reshape(b, t, kh, g, dh)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg, k).astype(jnp.float32)
    scores = scores / math.sqrt(dh)
    if mask is not None:
        scores = jnp.where(mask, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v)
    return out.reshape(b, t, h, dh)


def sdpa_blockwise(q, k, v, *, causal=True, window=None,
                   q_block=512, kv_block=1024, q_offset=0, vary_axes=()):
    """Flash-structured attention at the XLA level: online softmax over KV
    blocks inside a scan over Q blocks — O(block²) live memory instead of
    O(T·S).  This is the default for long sequences so the dry-run memory
    analysis reflects a production attention implementation; the Pallas
    kernel (repro.kernels.flash_attention) is the TPU-native version of the
    same schedule.

    For sliding-window attention only ceil(window/kv_block)+1 KV blocks per
    Q block are touched (linear total cost); full-causal scans all KV blocks
    and masks (the triangular-waste elimination is a §Perf item).
    """
    b, t, h, dh = q.shape
    s = k.shape[1]
    kh = k.shape[2]
    dv = v.shape[-1]
    g = h // kh
    q_block = min(q_block, t)
    kv_block = min(kv_block, s)
    assert t % q_block == 0 and s % kv_block == 0, (t, s, q_block, kv_block)
    nq, nk = t // q_block, s // kv_block
    scale = 1.0 / math.sqrt(dh)

    qb = q.reshape(b, nq, q_block, kh, g, dh)
    kb = k.reshape(b, nk, kv_block, kh, dh)
    vb = v.reshape(b, nk, kv_block, kh, dv)

    if window is not None:
        # only blocks within the window of the diagonal contribute
        n_rel = -(-window // kv_block) + 1  # ceil + diagonal block
        rel_range = range(min(n_rel, nk))
    else:
        rel_range = None

    def q_chunk(iq, qc):
        # qc [b, q_block, kh, g, dh]
        acc0 = jnp.zeros((b, q_block, kh, g, dv), jnp.float32)
        m0 = jnp.full((b, q_block, kh, g), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, q_block, kh, g), jnp.float32)
        if vary_axes:  # under shard_map: carries vary with the manual axis
            acc0, m0, l0 = (jax.lax.pcast(t_, tuple(vary_axes), to="varying")
                            for t_ in (acc0, m0, l0))
        qpos = q_offset + iq * q_block + jnp.arange(q_block)

        def kv_step(carry, ik, valid):
            acc, m, l = carry
            kc = jax.lax.dynamic_index_in_dim(kb, ik, 1, keepdims=False)
            vc = jax.lax.dynamic_index_in_dim(vb, ik, 1, keepdims=False)
            kpos = ik * kv_block + jnp.arange(kv_block)
            sc = jnp.einsum("bqkgd,bskd->bqkgs", qc, kc) * scale
            sc = sc.astype(jnp.float32)
            msk = jnp.ones((q_block, kv_block), bool)
            if causal:
                msk &= qpos[:, None] >= kpos[None, :]
            if window is not None:
                msk &= (qpos[:, None] - kpos[None, :]) < window
            msk &= valid
            sc = jnp.where(msk[None, :, None, None, :], sc, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
            p = jnp.exp(sc - m_new[..., None])
            p = jnp.where(msk[None, :, None, None, :], p, 0.0)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bqkgs,bskd->bqkgd", p.astype(vc.dtype), vc
            ).astype(jnp.float32)
            return (acc, m_new, l_new)

        if rel_range is not None:
            carry = (acc0, m0, l0)
            for j in rel_range:  # static, short loop over window blocks
                carry = kv_step(
                    carry, jnp.maximum(iq - j, 0), iq - j >= 0
                )
            acc, m, l = carry
        else:
            (acc, m, l), _ = jax.lax.scan(
                lambda c, ik: (kv_step(c, ik, True), None),
                (acc0, m0, l0),
                jnp.arange(nk),
            )
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.astype(q.dtype)

    outs = jax.lax.map(
        lambda iq: q_chunk(iq, jax.lax.dynamic_index_in_dim(
            qb, iq, 1, keepdims=False)),
        jnp.arange(nq),
    )  # [nq, b, q_block, kh, g, dh]
    out = jnp.moveaxis(outs, 0, 1).reshape(b, t, h, dv)
    return out


def causal_mask(t, s, window=None, offset=0):
    """[1,1,1,t,s] boolean mask.  offset = (absolute pos of q_0) - (of k_0)."""
    qi = jnp.arange(t)[:, None] + offset
    ki = jnp.arange(s)[None, :]
    m = qi >= ki
    if window is not None:
        m &= (qi - ki) < window
    return m[None, None, None]


BLOCKWISE_THRESHOLD = 2048  # switch to flash-structured attention above this


def _seq_sharded_blockwise(q, k, v, *, causal, window, axis):
    """Sequence-parallel attention: shard the query T dim over ``axis``
    (K/V replicated across it), each shard runs blockwise attention locally
    with a global causal offset.  No collectives inside attention; the
    surrounding einsums re-shard the output lazily."""
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    n = mesh.shape[axis]
    t = q.shape[1]
    if t % n:
        return sdpa_blockwise(q, k, v, causal=causal, window=window)
    t_local = t // n

    def local(q_l, k_r, v_r):
        idx = jax.lax.axis_index(axis)
        return sdpa_blockwise(
            q_l, k_r, v_r, causal=causal, window=window,
            q_offset=idx * t_local, vary_axes=(axis,),
        )

    return jax.shard_map(
        local,
        in_specs=(P(None, axis), P(), P()),
        out_specs=P(None, axis),
        axis_names={axis},
    )(q, k, v)


def gqa_forward(params, cfg: AttnConfig, x, positions, *,
                kv=None, kv_positions=None, use_flash=False, impl="auto"):
    """Full-sequence attention.  ``kv`` overrides k/v source (cross-attn).

    impl: "dense" | "blockwise" | "auto" (blockwise when T is long).
    """
    if kv is None:
        q, k, v = _project_qkv(params, cfg, x, positions)
        causal = cfg.causal
    else:
        # cross-attention: q from x, k/v from encoder output
        q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
        if cfg.qkv_bias:
            q = q + params["bq"]
        k = jnp.einsum("bsd,dhk->bshk", kv, params["wk"])
        v = jnp.einsum("bsd,dhk->bshk", kv, params["wv"])
        if cfg.qkv_bias:
            k, v = k + params["bk"], v + params["bv"]
        causal = False
    if use_flash and kv is None:
        from repro.kernels.flash_attention import ops as flash_ops

        if flash_ops.supported(q, k, v, None):
            out = flash_ops.flash_attention(
                q, k, v, causal=causal, window=cfg.sliding_window
            )
            return jnp.einsum("bthk,hkd->btd", out, params["wo"])
    blockwise = impl == "blockwise" or (
        impl == "auto" and max(q.shape[1], k.shape[1]) > BLOCKWISE_THRESHOLD
    )
    if cfg.seq_shard_axis is not None and kv is None and blockwise:
        out = _seq_sharded_blockwise(
            q, k, v, causal=causal, window=cfg.sliding_window,
            axis=cfg.seq_shard_axis,
        )
    elif blockwise:
        out = sdpa_blockwise(
            q, k, v, causal=causal, window=cfg.sliding_window
        )
    else:
        mask = (
            causal_mask(q.shape[1], k.shape[1], cfg.sliding_window)
            if causal
            else None
        )
        out = sdpa(q, k, v, mask, use_flash=use_flash)
    y = jnp.einsum("bthk,hkd->btd", out, params["wo"])
    return y


# ---------------------------------------------------------------------------
# Decode cache (full-length or sliding-window ring buffer)
# ---------------------------------------------------------------------------


def gqa_cache_len(cfg: AttnConfig, max_len: int) -> int:
    if cfg.sliding_window is not None:
        return min(cfg.sliding_window, max_len)
    return max_len


def gqa_init_cache(cfg: AttnConfig, batch: int, max_len: int, dtype):
    s = gqa_cache_len(cfg, max_len)
    kh, dh = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, s, kh, dh), dtype),
        "v": jnp.zeros((batch, s, kh, dh), dtype),
        "pos_ids": jnp.full((s,), -1, jnp.int32),
    }


def gqa_decode(params, cfg: AttnConfig, cache, x, pos):
    """One-token decode.  x [B,1,d]; pos scalar int32 (position of x)."""
    positions = pos[None, None] if pos.ndim == 0 else pos
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
    k = jnp.einsum("btd,dhk->bthk", x, params["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, params["wv"])
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    if cfg.qk_norm:
        q = rmsnorm({"scale": params["q_norm"]}, q)
        k = rmsnorm({"scale": params["k_norm"]}, k)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    s = cache["k"].shape[1]
    slot = (pos % s).astype(jnp.int32)  # == pos for full-length caches
    ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
    pos_ids = cache["pos_ids"].at[slot].set(pos.astype(jnp.int32))

    valid = (pos_ids >= 0) & (pos_ids <= pos)
    mask = valid[None, None, None, None, :]
    out = sdpa(q, ck, cv, mask)
    y = jnp.einsum("bthk,hkd->btd", out, params["wo"])
    return y, {"k": ck, "v": cv, "pos_ids": pos_ids}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2): low-rank latent KV cache
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    d_model: int
    n_heads: int
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    sliding_window: int | None = None


def mla_specs(cfg: MLAConfig):
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    return {
        "wq": ParamSpec((d, h, qk), ("embed", "heads", "head")),
        "w_dkv": ParamSpec((d, r), ("embed", None)),
        "kv_norm": ParamSpec((r,), (None,), init="ones"),
        "w_uk": ParamSpec((r, h, cfg.qk_nope_dim), (None, "heads", "head")),
        "w_uv": ParamSpec((r, h, cfg.v_head_dim), (None, "heads", "head")),
        "w_kr": ParamSpec((d, cfg.qk_rope_dim), ("embed", None)),
        "wo": ParamSpec((h, cfg.v_head_dim, d), ("heads", "head", "embed")),
    }


def _mla_common(params, cfg: MLAConfig, x, positions):
    c = jnp.einsum("btd,dr->btr", x, params["w_dkv"])
    c = rmsnorm({"scale": params["kv_norm"]}, c)
    k_rope = jnp.einsum("btd,de->bte", x, params["w_kr"])[:, :, None, :]
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)  # [B,T,1,rope]
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
    q_nope = q[..., : cfg.qk_nope_dim]
    q_rope = apply_rope(q[..., cfg.qk_nope_dim:], positions, cfg.rope_theta)
    return c, k_rope, q_nope, q_rope


def mla_forward(params, cfg: MLAConfig, x, positions, use_flash=False):
    del use_flash  # reference path; MLA flash variant not implemented
    c, k_rope, q_nope, q_rope = _mla_common(params, cfg, x, positions)
    k_nope = jnp.einsum("btr,rhk->bthk", c, params["w_uk"])
    v = jnp.einsum("btr,rhk->bthk", c, params["w_uv"])
    t = x.shape[1]
    if t > BLOCKWISE_THRESHOLD:
        # fold the shared rope-key into per-head keys; blockwise attention
        # (scale handled internally via the combined head dim)
        h = cfg.n_heads
        k_eff = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, k_rope.shape[:2] + (h,) +
                                      k_rope.shape[3:])], axis=-1
        )
        q_eff = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = sdpa_blockwise(
            q_eff, k_eff, v, causal=True, window=cfg.sliding_window
        )
        return jnp.einsum("bthk,hkd->btd", out, params["wo"])
    scale = 1.0 / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    scores = (
        jnp.einsum("bthk,bshk->bhts", q_nope, k_nope)
        + jnp.einsum("bthk,bsek->bhts", q_rope, k_rope)
    ).astype(jnp.float32) * scale
    mask = causal_mask(x.shape[1], x.shape[1], cfg.sliding_window)[:, :, 0]
    scores = jnp.where(mask, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhts,bshk->bthk", probs, v)
    return jnp.einsum("bthk,hkd->btd", out, params["wo"])


def mla_cache_len(cfg: MLAConfig, max_len: int) -> int:
    if cfg.sliding_window is not None:
        return min(cfg.sliding_window, max_len)
    return max_len


def mla_init_cache(cfg: MLAConfig, batch: int, max_len: int, dtype):
    s = mla_cache_len(cfg, max_len)
    return {
        "c": jnp.zeros((batch, s, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, s, cfg.qk_rope_dim), dtype),
        "pos_ids": jnp.full((s,), -1, jnp.int32),
    }


def mla_decode(params, cfg: MLAConfig, cache, x, pos):
    """Absorbed-matmul decode: scores computed against the latent cache
    directly (q_nope absorbed through w_uk; output through w_uv), so the
    per-step FLOPs and cache traffic scale with kv_lora_rank, not heads."""
    positions = pos[None, None]
    c, k_rope, q_nope, q_rope = _mla_common(params, cfg, x, positions)
    s = cache["c"].shape[1]
    slot = (pos % s).astype(jnp.int32)  # == pos for full-length caches
    cc = jax.lax.dynamic_update_slice(cache["c"], c, (0, slot, 0))
    ckr = jax.lax.dynamic_update_slice(
        cache["k_rope"], k_rope[:, :, 0, :], (0, slot, 0)
    )
    pos_ids = cache["pos_ids"].at[slot].set(pos.astype(jnp.int32))

    q_lat = jnp.einsum("bthk,rhk->bthr", q_nope, params["w_uk"])
    scale = 1.0 / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    scores = (
        jnp.einsum("bthr,bsr->bhts", q_lat, cc)
        + jnp.einsum("bthk,bsk->bhts", q_rope, ckr)
    ).astype(jnp.float32) * scale
    valid = (pos_ids >= 0) & (pos_ids <= pos)
    scores = jnp.where(valid[None, None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out_lat = jnp.einsum("bhts,bsr->bthr", probs, cc)
    out = jnp.einsum("bthr,rhk->bthk", out_lat, params["w_uv"])
    y = jnp.einsum("bthk,hkd->btd", out, params["wo"])
    return y, {"c": cc, "k_rope": ckr, "pos_ids": pos_ids}
