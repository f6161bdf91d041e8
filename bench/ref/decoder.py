"""Plain reference of a dense decoder-only language model.

The published block of Qwen3 and OLMo written out in ``jax.numpy``:
pre-norm grouped-query attention with rotary positions (optionally with
an RMS norm on each query and key head), a SwiGLU feed-forward block,
a final norm and an unembedding tied to the token embedding.  The norm
is RMSNorm with a learned scale (Qwen3) or LayerNorm without parameters
(OLMo).  No kernels, no remat, no scan: one Python loop over the layers.

``dtype`` is the precision the matrix products run in.  The benchmark's
reference runs it in float32 under ``jax.default_matmul_precision
("highest")``; the control runs it in bfloat16.  Norms, rotary angles,
softmax and the loss are taken in float32 and cast back to ``dtype``.

Weights are drawn from the seed in the order and with the scales the
system under test documents for its parameter specs: one key per leaf,
leaves in sorted-path order, normal draws scaled by the fan-in of the
first non-layer axis (``0.02`` for the embedding), norm scales at one.

Counts (``bench/flops.py`` reads them): ``param_count`` is every weight
of the packed plane; model FLOPs follow the PaLM convention (Chowdhery
et al. 2022, appendix B): ``6 * P + 12 * L * d_q * T`` per token, where
``P`` counts every matrix-product parameter, the tied unembedding
included once, ``d_q`` is the query width (heads times head size) and
``T`` the sequence length.  Recomputation (remat) is not counted.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _attn_params(c):
    d, h, kh, dh = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    return d * h * dh * 2 + d * kh * dh * 2  # wq, wo, wk, wv


def matmul_params(c) -> int:
    """Parameters that enter a matrix product, tied unembedding once."""
    per_layer = _attn_params(c) + 3 * c["hidden_size"] * c["intermediate_size"]
    return c["num_hidden_layers"] * per_layer + c["vocab_size"] * c["hidden_size"]


def param_count(c) -> int:
    """Every parameter of the model: matrix weights and norm scales."""
    n = matmul_params(c)
    if c["norm"] == "rms":
        n += (2 * c["num_hidden_layers"] + 1) * c["hidden_size"]
    if c["qk_norm"]:
        n += 2 * c["num_hidden_layers"] * c["head_dim"]
    return n


def flops_per_token(c, seq_len) -> int:
    d_q = c["num_attention_heads"] * c["head_dim"]
    return 6 * matmul_params(c) + 12 * c["num_hidden_layers"] * d_q * seq_len


def leaf_specs(cfg):
    """``{path: (shape, init, fan_in)}`` of every weight, paths joined
    by ``/`` and sorted the way a flattened nested dict is."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kh, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    n_layers, vocab = cfg["num_hidden_layers"], cfg["vocab_size"]
    rms = cfg["norm"] == "rms"
    blk = "units/0_attn/"
    specs = {"embed/embedding": ((vocab, d), "embed", None)}
    if rms:
        specs["final_norm/scale"] = ((d,), "ones", None)
        specs[blk + "ln1/scale"] = ((n_layers, d), "ones", None)
        specs[blk + "ln2/scale"] = ((n_layers, d), "ones", None)
    if cfg["qk_norm"]:
        specs[blk + "attn/q_norm"] = ((n_layers, dh), "ones", None)
        specs[blk + "attn/k_norm"] = ((n_layers, dh), "ones", None)
    specs[blk + "attn/wq"] = ((n_layers, d, h, dh), "normal", d)
    specs[blk + "attn/wk"] = ((n_layers, d, kh, dh), "normal", d)
    specs[blk + "attn/wv"] = ((n_layers, d, kh, dh), "normal", d)
    specs[blk + "attn/wo"] = ((n_layers, h, dh, d), "normal", h)
    specs[blk + "ffn/wg"] = ((n_layers, d, f), "normal", d)
    specs[blk + "ffn/wu"] = ((n_layers, d, f), "normal", d)
    specs[blk + "ffn/wd"] = ((n_layers, f, d), "normal", f)
    return {k: specs[k] for k in sorted(specs, key=lambda p: p.split("/"))}


def init_weights(cfg, seed):
    """``{path: float32 array}`` drawn from ``jax.random.key(seed)``."""
    specs = leaf_specs(cfg)
    keys = jax.random.split(jax.random.key(seed), len(specs))
    out = {}
    for k, (path, (shape, init, fan_in)) in zip(keys, specs.items()):
        if init == "ones":
            out[path] = jnp.ones(shape, jnp.float32)
        elif init == "embed":
            out[path] = jax.random.normal(k, shape) * 0.02
        else:
            out[path] = jax.random.normal(k, shape) * (1.0 / math.sqrt(fan_in))
    return out


def _norm(cfg, x, scale, dtype):
    xf = x.astype(jnp.float32)
    if cfg["norm"] == "rms":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        return (xf * jax.lax.rsqrt(var + cfg["rms_norm_eps"])
                * scale.astype(jnp.float32)).astype(dtype)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + cfg["layer_norm_eps"])).astype(dtype)


def _rms_head(x, scale, eps, dtype):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(dtype)


def _rope(x, theta, dtype):
    """Rotate-half rotary embedding over the last axis; x [B, T, H, Dh]."""
    t, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv  # [T, Dh/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(dtype)


def _attention(cfg, w, layer, x, dtype):
    h, kh, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    blk = "units/0_attn/attn/"
    q = jnp.einsum("btd,dhk->bthk", x, w[blk + "wq"][layer])
    k = jnp.einsum("btd,dhk->bthk", x, w[blk + "wk"][layer])
    v = jnp.einsum("btd,dhk->bthk", x, w[blk + "wv"][layer])
    if cfg["qk_norm"]:
        q = _rms_head(q, w[blk + "q_norm"][layer], cfg["rms_norm_eps"], dtype)
        k = _rms_head(k, w[blk + "k_norm"][layer], cfg["rms_norm_eps"], dtype)
    q = _rope(q, cfg["rope_theta"], dtype)
    k = _rope(k, cfg["rope_theta"], dtype)
    b, t = x.shape[0], x.shape[1]
    # query head i reads key/value head i // (h // kh)
    qg = q.reshape(b, t, kh, h // kh, dh)
    s = jnp.einsum("btkgd,bskd->bkgts", qg, k).astype(jnp.float32)
    s = s / math.sqrt(dh)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(dtype)
    o = jnp.einsum("bkgts,bskd->btkgd", p, v).reshape(b, t, h, dh)
    return jnp.einsum("bthk,hkd->btd", o, w[blk + "wo"][layer])


def _ffn(w, layer, x):
    blk = "units/0_attn/ffn/"
    g = jax.nn.silu(jnp.einsum("btd,df->btf", x, w[blk + "wg"][layer]))
    u = jnp.einsum("btd,df->btf", x, w[blk + "wu"][layer])
    return jnp.einsum("btf,fd->btd", g * u, w[blk + "wd"][layer])


def loss(cfg, weights, tokens, dtype=jnp.float32):
    """Mean next-token cross-entropy of ``tokens [B, T + 1]``."""
    w = {k: v.astype(dtype) for k, v in weights.items()}
    emb = w["embed/embedding"]
    x = jnp.take(emb, tokens[:, :-1], axis=0)
    rms = cfg["norm"] == "rms"
    blk = "units/0_attn/"
    for layer in range(cfg["num_hidden_layers"]):
        s1 = w[blk + "ln1/scale"][layer] if rms else None
        x = x + _attention(cfg, w, layer, _norm(cfg, x, s1, dtype), dtype)
        s2 = w[blk + "ln2/scale"][layer] if rms else None
        x = x + _ffn(w, layer, _norm(cfg, x, s2, dtype))
    x = _norm(cfg, x, w["final_norm/scale"] if rms else None, dtype)
    logits = jnp.einsum("btd,vd->btv", x, emb).astype(jnp.float32)
    labels = tokens[:, 1:]
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) - gold)
