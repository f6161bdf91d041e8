"""Decoder-only language model assembler.

A model is a stack of *units*; each unit is a short pattern of blocks (e.g.
``("attn",)`` for dense models, ``("mamba",)*6`` for Zamba2 with a shared
attention block appended per unit, ``("mlstm",)*5 + ("slstm",)`` for xLSTM).
Unit parameters are stacked along a leading axis and the stack is executed
with ``lax.scan`` (+ optional remat) so the lowered HLO stays one-unit-sized
regardless of depth — essential for compiling the 104B config.

Block kinds:
    attn    pre-norm GQA attention + SwiGLU FFN (or parallel block)
    moe     pre-norm GQA attention + MoE FFN (+ shared experts)
    mla     pre-norm MLA attention + MoE FFN
    mla_dense  pre-norm MLA attention + dense FFN (DeepSeek first-k-dense)
    mamba   pre-norm Mamba2 (SSD) block
    mlstm / slstm   xLSTM blocks (no separate FFN)

``shared_attn`` (Zamba2): one attention+FFN block whose parameters are shared
across all its invocations (applied after every unit).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models import mamba as mamba_lib
from repro.models import moe as moe_lib
from repro.models import xlstm as xlstm_lib
from repro.models.common import (
    ParamSpec,
    embed,
    embedding_specs,
    make_norm,
    softmax_xent,
    softmax_xent_streamed,
    unembed,
    unembed_head,
    unembed_head_specs,
)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    vocab: int
    pattern: tuple = ("attn",)  # repeating unit of block kinds
    d_ff: int = 0  # dense FFN hidden size
    attn: Any = None  # AttnConfig
    mla: Any = None  # MLAConfig
    moe: Any = None  # MoEConfig
    ssm: Any = None  # SSMConfig
    lstm: Any = None  # XLSTMConfig
    norm: str = "rms"
    parallel_block: bool = False  # command-r style fused attn+ffn residual
    shared_attn: bool = False  # Zamba2 shared block after each unit
    first_dense: int = 0  # DeepSeek: leading dense layers (unstacked)
    d_ff_first: int = 0  # their FFN width
    tie_embeddings: bool = True
    ffn_bias: bool = False
    dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: str = "full"  # full | dots (dots_with_no_batch_dims)
    use_flash: bool = False
    # >0: streamed fused unembed+xent over this many vocab chunks (never
    # materializes [B,T,V] logits) — §Perf optimization, tied embeddings only
    xent_chunks: int = 0
    # inputs_via_embeds: VLM / audio stubs feed embeddings, not token ids
    inputs_via_embeds: bool = False

    @property
    def n_units(self) -> int:
        n = (self.n_layers - self.first_dense) // len(self.pattern)
        assert n * len(self.pattern) + self.first_dense == self.n_layers, (
            "n_layers must be first_dense + k * len(pattern)",
            self.n_layers,
            self.pattern,
        )
        return n


# ---------------------------------------------------------------------------
# Block specs / forward / decode
# ---------------------------------------------------------------------------


def _ffn_specs(d, d_ff):
    return {
        "wg": ParamSpec((d, d_ff), ("embed", "ffn")),
        "wu": ParamSpec((d, d_ff), ("embed", "ffn")),
        "wd": ParamSpec((d_ff, d), ("ffn", "embed")),
    }


def _ffn(params, x):
    h = jax.nn.silu(jnp.einsum("btd,df->btf", x, params["wg"]))
    h = h * jnp.einsum("btd,df->btf", x, params["wu"])
    return jnp.einsum("btf,fd->btd", h, params["wd"])


def block_specs(cfg: ModelConfig, kind: str):
    d = cfg.d_model
    norm_specs, _ = make_norm(cfg.norm, d)
    if kind in ("attn", "shared_attn"):
        specs = {
            "ln1": dict(norm_specs),
            "attn": attn_lib.gqa_specs(cfg.attn),
        }
        if not cfg.parallel_block:
            specs["ln2"] = dict(norm_specs)
        specs["ffn"] = _ffn_specs(d, cfg.d_ff)
        return specs
    if kind == "moe":
        return {
            "ln1": dict(norm_specs),
            "attn": attn_lib.gqa_specs(cfg.attn),
            "ln2": dict(norm_specs),
            "moe": moe_lib.moe_specs(cfg.moe),
        }
    if kind == "mla":
        return {
            "ln1": dict(norm_specs),
            "attn": attn_lib.mla_specs(cfg.mla),
            "ln2": dict(norm_specs),
            "moe": moe_lib.moe_specs(cfg.moe),
        }
    if kind == "mla_dense":
        return {
            "ln1": dict(norm_specs),
            "attn": attn_lib.mla_specs(cfg.mla),
            "ln2": dict(norm_specs),
            "ffn": _ffn_specs(d, cfg.d_ff_first),
        }
    if kind == "mamba":
        return {"ln": dict(norm_specs), "mamba": mamba_lib.mamba_specs(cfg.ssm)}
    if kind == "mlstm":
        return {"ln": dict(norm_specs), "cell": xlstm_lib.mlstm_specs(cfg.lstm)}
    if kind == "slstm":
        return {"ln": dict(norm_specs), "cell": xlstm_lib.slstm_specs(cfg.lstm)}
    raise ValueError(kind)


def block_forward(params, cfg: ModelConfig, kind: str, x, positions):
    """Full-sequence block application.  Returns (y, aux_loss)."""
    _, norm = make_norm(cfg.norm, cfg.d_model)
    aux = jnp.zeros((), jnp.float32)
    if kind in ("attn", "shared_attn"):
        h = norm(params.get("ln1", {}), x)
        a = attn_lib.gqa_forward(
            params["attn"], cfg.attn, h, positions, use_flash=cfg.use_flash
        )
        if cfg.parallel_block:
            return x + a + _ffn(params["ffn"], h), aux
        x = x + a
        h = norm(params.get("ln2", {}), x)
        return x + _ffn(params["ffn"], h), aux
    if kind == "moe":
        h = norm(params.get("ln1", {}), x)
        x = x + attn_lib.gqa_forward(
            params["attn"], cfg.attn, h, positions, use_flash=cfg.use_flash
        )
        h = norm(params.get("ln2", {}), x)
        y, aux = moe_lib.moe_forward(params["moe"], cfg.moe, h)
        return x + y, aux
    if kind == "mla":
        h = norm(params.get("ln1", {}), x)
        x = x + attn_lib.mla_forward(params["attn"], cfg.mla, h, positions)
        h = norm(params.get("ln2", {}), x)
        y, aux = moe_lib.moe_forward(params["moe"], cfg.moe, h)
        return x + y, aux
    if kind == "mla_dense":
        h = norm(params.get("ln1", {}), x)
        x = x + attn_lib.mla_forward(params["attn"], cfg.mla, h, positions)
        h = norm(params.get("ln2", {}), x)
        return x + _ffn(params["ffn"], h), aux
    if kind == "mamba":
        h = norm(params.get("ln", {}), x)
        return x + mamba_lib.mamba_forward(params["mamba"], cfg.ssm, h), aux
    if kind == "mlstm":
        h = norm(params.get("ln", {}), x)
        return x + xlstm_lib.mlstm_forward(params["cell"], cfg.lstm, h), aux
    if kind == "slstm":
        h = norm(params.get("ln", {}), x)
        return x + xlstm_lib.slstm_forward(params["cell"], cfg.lstm, h), aux
    raise ValueError(kind)


def block_init_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int):
    if kind in ("attn", "shared_attn", "moe"):
        return attn_lib.gqa_init_cache(cfg.attn, batch, max_len, cfg.dtype)
    if kind in ("mla", "mla_dense"):
        return attn_lib.mla_init_cache(cfg.mla, batch, max_len, cfg.dtype)
    if kind == "mamba":
        return mamba_lib.mamba_init_cache(cfg.ssm, batch, cfg.dtype)
    if kind == "mlstm":
        return xlstm_lib.mlstm_init_cache(cfg.lstm, batch, cfg.dtype)
    if kind == "slstm":
        return xlstm_lib.slstm_init_cache(cfg.lstm, batch, cfg.dtype)
    raise ValueError(kind)


def block_decode(params, cfg: ModelConfig, kind: str, cache, x, pos):
    _, norm = make_norm(cfg.norm, cfg.d_model)
    if kind in ("attn", "shared_attn"):
        h = norm(params.get("ln1", {}), x)
        a, cache = attn_lib.gqa_decode(params["attn"], cfg.attn, cache, h, pos)
        if cfg.parallel_block:
            return x + a + _ffn(params["ffn"], h), cache
        x = x + a
        h = norm(params.get("ln2", {}), x)
        return x + _ffn(params["ffn"], h), cache
    if kind == "moe":
        h = norm(params.get("ln1", {}), x)
        a, cache = attn_lib.gqa_decode(params["attn"], cfg.attn, cache, h, pos)
        x = x + a
        h = norm(params.get("ln2", {}), x)
        y, _ = moe_lib.moe_forward(params["moe"], cfg.moe, h)
        return x + y, cache
    if kind in ("mla", "mla_dense"):
        h = norm(params.get("ln1", {}), x)
        a, cache = attn_lib.mla_decode(params["attn"], cfg.mla, cache, h, pos)
        x = x + a
        h = norm(params.get("ln2", {}), x)
        if kind == "mla":
            y, _ = moe_lib.moe_forward(params["moe"], cfg.moe, h)
        else:
            y = _ffn(params["ffn"], h)
        return x + y, cache
    if kind == "mamba":
        h = norm(params.get("ln", {}), x)
        y, cache = mamba_lib.mamba_decode(params["mamba"], cfg.ssm, cache, h, pos)
        return x + y, cache
    if kind == "mlstm":
        h = norm(params.get("ln", {}), x)
        y, cache = xlstm_lib.mlstm_decode(params["cell"], cfg.lstm, cache, h, pos)
        return x + y, cache
    if kind == "slstm":
        h = norm(params.get("ln", {}), x)
        y, cache = xlstm_lib.slstm_decode(params["cell"], cfg.lstm, cache, h, pos)
        return x + y, cache
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Whole-model specs / init / forward / decode
# ---------------------------------------------------------------------------


def _stack_specs(specs, n):
    """Prepend a stacking axis of size n to every ParamSpec."""
    return jax.tree.map(
        lambda s: ParamSpec(
            (n,) + s.shape, ("layers",) + s.axes, init=s.init, scale=s.scale
        ),
        specs,
        is_leaf=lambda s: isinstance(s, ParamSpec),
    )


def model_specs(cfg: ModelConfig):
    unit = {
        f"{i}_{kind}": block_specs(cfg, kind)
        for i, kind in enumerate(cfg.pattern)
    }
    specs = {
        "embed": embedding_specs(cfg.vocab, cfg.d_model),
        "units": _stack_specs(unit, cfg.n_units),
        "final_norm": make_norm(cfg.norm, cfg.d_model)[0],
    }
    if cfg.first_dense:
        specs["first"] = _stack_specs(
            block_specs(cfg, "mla_dense" if cfg.mla else "attn"),
            cfg.first_dense,
        )
    if cfg.shared_attn:
        specs["shared"] = block_specs(cfg, "shared_attn")
    if not cfg.tie_embeddings:
        specs["unembed"] = unembed_head_specs(cfg.vocab, cfg.d_model)
    return specs


def _unit_forward(cfg: ModelConfig, unit_params, shared_params, x, positions):
    aux = jnp.zeros((), jnp.float32)
    for i, kind in enumerate(cfg.pattern):
        x, a = block_forward(unit_params[f"{i}_{kind}"], cfg, kind, x, positions)
        aux = aux + a
    if cfg.shared_attn:
        x, a = block_forward(shared_params, cfg, "shared_attn", x, positions)
        aux = aux + a
    return x, aux


def compute_params(params, cfg: ModelConfig):
    """Floating-point params cast to the compute dtype ``cfg.dtype``: the
    trainer keeps f32 master weights (gradients flow back through the
    cast in f32) while the layers run in ``cfg.dtype``."""
    return jax.tree.map(
        lambda p: p.astype(cfg.dtype)
        if jnp.issubdtype(p.dtype, jnp.floating) else p,
        params,
    )


def forward(params, cfg: ModelConfig, tokens=None, embeds=None,
            positions=None, return_hidden=False):
    """Train / prefill forward.  Returns (logits | hidden, aux_loss)."""
    params = compute_params(params, cfg)
    if embeds is None:
        x = embed(params["embed"], tokens).astype(cfg.dtype)
    else:
        x = embeds.astype(cfg.dtype)
    b, t = x.shape[0], x.shape[1]
    if positions is None:
        positions = jnp.arange(t)[None, :]
    aux_total = jnp.zeros((), jnp.float32)

    if cfg.first_dense:
        kind = "mla_dense" if cfg.mla else "attn"

        def first_body(carry, p):
            xx, aux = carry
            xx, a = block_forward(p, cfg, kind, xx, positions)
            return (xx, aux + a), None

        (x, aux_total), _ = jax.lax.scan(
            first_body, (x, aux_total), params["first"]
        )

    shared = params.get("shared")

    def unit_body(carry, unit_p):
        xx, aux = carry
        xx, a = _unit_forward(cfg, unit_p, shared, xx, positions)
        return (xx, aux + a), None

    if cfg.remat:
        policy = (
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            if cfg.remat_policy == "dots"
            else None
        )
        body = jax.checkpoint(unit_body, policy=policy)
    else:
        body = unit_body
    (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), params["units"])

    _, norm = make_norm(cfg.norm, cfg.d_model)
    x = norm(params["final_norm"], x)
    if return_hidden:
        return x, aux_total
    if cfg.tie_embeddings:
        logits = unembed(params["embed"], x)
    else:
        logits = unembed_head(params["unembed"], x)
    return logits, aux_total


def loss_fn(params, cfg: ModelConfig, batch):
    """batch: {"tokens": [B,T]} or {"embeds": [B,T,d], "labels": [B,T]}."""
    params = compute_params(params, cfg)
    if cfg.xent_chunks and cfg.tie_embeddings:
        if "embeds" in batch:
            x, aux = forward(params, cfg, embeds=batch["embeds"],
                             return_hidden=True)
            labels = batch["labels"]
        else:
            x, aux = forward(params, cfg, tokens=batch["tokens"][:, :-1],
                             return_hidden=True)
            labels = batch["tokens"][:, 1:]
        loss = softmax_xent_streamed(
            x, params["embed"]["embedding"], labels, cfg.xent_chunks
        )
        return loss + aux
    if "embeds" in batch:
        logits, aux = forward(params, cfg, embeds=batch["embeds"])
        labels = batch["labels"]
        loss = softmax_xent(logits, labels)
    else:
        tokens = batch["tokens"]
        logits, aux = forward(params, cfg, tokens=tokens[:, :-1])
        loss = softmax_xent(logits, tokens[:, 1:])
    return loss + aux


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    def stack(tree_fn, n):
        trees = [tree_fn() for _ in range(n)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)

    unit_cache = {
        f"{i}_{kind}": block_init_cache(cfg, kind, batch, max_len)
        for i, kind in enumerate(cfg.pattern)
    }
    cache = {
        "units": jax.tree.map(
            lambda x: jnp.broadcast_to(
                x[None], (cfg.n_units,) + x.shape
            ).copy(),
            unit_cache,
        ),
        "shared": (
            stack(
                lambda: block_init_cache(cfg, "shared_attn", batch, max_len),
                cfg.n_units,
            )
            if cfg.shared_attn
            else None
        ),
    }
    if cfg.first_dense:
        kind = "mla_dense" if cfg.mla else "attn"
        cache["first"] = stack(
            lambda: block_init_cache(cfg, kind, batch, max_len),
            cfg.first_dense,
        )
    return cache


def decode_step(params, cfg: ModelConfig, cache, token=None, embed_in=None,
                pos=None):
    """One-token decode.  token [B] int32 or embed_in [B,1,d]; pos scalar."""
    if embed_in is None:
        x = embed(params["embed"], token[:, None]).astype(cfg.dtype)
    else:
        x = embed_in.astype(cfg.dtype)

    if cfg.first_dense:
        kind = "mla_dense" if cfg.mla else "attn"

        def first_body(xx, pc):
            p, c = pc
            xx, c = block_decode(p, cfg, kind, c, xx, pos)
            return xx, c

        x, new_first = jax.lax.scan(
            first_body, x, (params["first"], cache["first"])
        )

    shared = params.get("shared")

    def unit_body(xx, pc):
        unit_p, c, shared_c = pc
        for i, kind in enumerate(cfg.pattern):
            key = f"{i}_{kind}"
            xx, ck = block_decode(unit_p[key], cfg, kind, c[key], xx, pos)
            c = {**c, key: ck}
        if cfg.shared_attn:
            xx, shared_c = block_decode(
                shared, cfg, "shared_attn", shared_c, xx, pos
            )
        return xx, (c, shared_c)

    x, (new_units, new_shared) = jax.lax.scan(
        unit_body, x, (params["units"], cache["units"], cache["shared"])
    )

    _, norm = make_norm(cfg.norm, cfg.d_model)
    x = norm(params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = unembed(params["embed"], x)
    else:
        logits = unembed_head(params["unembed"], x)
    new_cache = {"units": new_units, "shared": new_shared}
    if cfg.first_dense:
        new_cache["first"] = new_first
    return logits, new_cache
