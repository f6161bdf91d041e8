"""Compression operators C : R^n -> R^n (paper §II-B, Assumption 3).

A compressor here is *payload-typed*: ``compress`` returns the wire
representation (what actually moves over ICI in a ``collective-permute``) and
``decompress`` reconstructs the dense tensor.  This is essential for the
roofline to be honest — if we permuted the decompressed dense tensor the HLO
collective bytes would not shrink at all.  Payloads are ``Payload`` pytrees:
named wire leaves (``payload["q"]``, ``payload["v"]``, ...) whose byte count
(``payload.wire_bytes``) is derivable from the payload itself.

Implemented compressors (each registered in ``COMPRESSORS`` via a
``CompressorEntry``, mirroring ``core.solver.SOLVERS``):

* ``BBitQuantizer`` — the paper's C1: unbiased stochastic b-bit quantizer with
  per-tensor inf-norm scale.  b bits per element = 1 sign bit + (b-1)
  magnitude bits, i.e. s = 2^(b-1) - 1 levels; wire format int8 (b == 8) or
  two 4-bit values packed per uint8 byte (b == 4).
* ``RandK`` — the paper's C2, TPU-adapted: the index subset is derived from a
  PRNG key shared by sender and receiver (per edge and round), so **only the
  k values** are transmitted — no indices on the wire.  Three samplers:
  ``uniform`` (exact rand-k, O(n log n) sort — paper-scale problems),
  ``block`` (uniformly-shifted cyclic block — O(k), unbiased, transformer
  scale) and ``stride`` (seeded affine set ``(off + j*stride) % n`` with the
  stride drawn from a static coprime table — unbiased, duplicate-free, and
  derivable *inside* a Pallas kernel from the counter PRNG).
* ``TopK`` — biased magnitude top-k (beyond-paper comparison; relies on error
  feedback for convergence; violates Assumption 3's unbiasedness).
* ``Identity`` — no compression (recovers LT-ADMM of ref. [14]).

All compressors are unbiased with E||C(x)-x||^2 <= p ||x||^2 except TopK;
``variance_p`` reports the constant p per leaf (used in tests and napkin
math).

**Backend selection** is a first-class parameter: every compressor takes
``impl={auto,jnp,pallas}`` (``"qbit:bits=8,impl=pallas"``), resolved
centrally through ``resolve_impl`` — ``auto`` means compiled Pallas on
a TPU where the compressor's kernels compile there (the static
``CompressorEntry.tpu_kernels`` rule: qbit, randk block/stride) and plain
jnp everywhere else; topk and randk's uniform sampler need an
arbitrary-index gather the TPU compiler refuses, so ``impl=pallas`` on a
TPU raises for them instead of falling back.  The legacy
``kernel=true``/``false`` spec param still parses (DeprecationWarning) and
maps to ``impl=pallas``/``jnp``.  RandK/TopK keep their seed-synchronized
index derivation on the leaf path, so their Pallas leaf path is
bit-identical; the quantizer's stochastic-rounding stream differs (still
unbiased).

**Fused plane path**: on the packed plane (``core.packing``) the per-round
compress of all ``[A, S, N]`` messages goes through ``plane_compress`` /
``plane_decompress``.  With ``impl=pallas`` and a plane-capable compressor
(qbit; randk block/stride) that is ONE fused Pallas launch for the whole
plane: stochastic-rounding bits are drawn in-kernel from the counter PRNG
(``kernels.prng``) and RandK index sets are walked in-kernel from a
per-message (offset, stride), all seeded by (round key, sender,
receiver), so no random stream or index array is ever materialized in HBM —
only the round seed is shared, exactly like the wire format.  Any other
configuration falls back to the vmapped per-message ``compress_tree`` path,
bit-identical to the tree solvers.
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from collections.abc import Callable, Mapping
from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.kernels import prng

IMPLS = ("auto", "jnp", "pallas")


def resolve_impl(impl: str, tpu_kernels: bool = True) -> str:
    """``auto`` -> backend choice: ``pallas`` (compiled) on a TPU when
    the compressor has kernels the TPU compiler accepts
    (``tpu_kernels``, the static rule in its ``CompressorEntry``), else
    ``jnp``.  Explicit ``jnp`` always wins; explicit ``pallas`` runs the
    kernels in interpret mode off-TPU and fails loudly on a TPU when
    there are no such kernels."""
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    from repro.kernels import resolve_interpret

    on_tpu = not resolve_interpret(None)
    if impl == "auto":
        # off-TPU, interpret-mode Pallas is a correctness tool, not a
        # fast path — auto stays on jnp
        return "pallas" if on_tpu and tpu_kernels else "jnp"
    if impl == "pallas" and on_tpu and not tpu_kernels:
        raise ValueError(
            "impl=pallas: this compressor configuration has no kernel the "
            "TPU compiler accepts (arbitrary-index gather/scatter); use "
            "impl=auto or impl=jnp"
        )
    return impl


def _flat(x):
    return jnp.reshape(x, (-1,))


def _leaf_nbytes(leaf) -> int:
    shape = getattr(leaf, "shape", ())
    dtype = getattr(leaf, "dtype", jnp.float32)
    return math.prod(shape) * jnp.dtype(dtype).itemsize


@jax.tree_util.register_pytree_with_keys_class
class Payload(Mapping):
    """Typed wire representation of one compressed message.

    A pytree node with NAMED leaves — ``payload["q"]``, ``payload["v"]``,
    ... — that vmaps/scans/permutes like the plain dict it replaces, plus
    ``wire_bytes``: the byte count of the leaves as stored, derivable
    from the payload itself (per message when leaves are unbatched; the
    whole batch when they carry lead dims).  Compressors' ``wire_bytes``
    *methods* remain the shape-only accounting used by the cost model.
    """

    __slots__ = ("_leaves",)

    def __init__(self, **leaves):
        # canonical (sorted) key order: flatten/unflatten roundtrips and
        # equality are insensitive to construction order
        self._leaves = dict(sorted(leaves.items()))

    def __getitem__(self, k):
        return self._leaves[k]

    def __iter__(self):
        return iter(self._leaves)

    def __len__(self):
        return len(self._leaves)

    def __repr__(self):
        inner = ", ".join(f"{k}={v!r}" for k, v in sorted(self._leaves.items()))
        return f"Payload({inner})"

    @property
    def wire_bytes(self) -> int:
        return sum(_leaf_nbytes(v) for v in self._leaves.values())

    def tree_flatten_with_keys(self):
        items = sorted(self._leaves.items())
        return (
            tuple((jax.tree_util.DictKey(k), v) for k, v in items),
            tuple(k for k, _ in items),
        )

    @classmethod
    def tree_unflatten(cls, keys, leaves):
        return cls(**dict(zip(keys, leaves)))


@runtime_checkable
class Compressor(Protocol):
    """What every registered compressor implements (leaf granularity).

    ``compress(key, x) -> Payload`` / ``decompress(key, payload, like)``
    are the seed-synchronized wire codec; ``variance_p``/``wire_bytes``
    are the Assumption-3 constant and the cost model's byte accounting.
    Plane-capable compressors additionally provide ``compress_plane`` /
    ``decompress_plane`` (see ``plane_compress``).
    """

    name: str
    unbiased: bool
    impl: str

    def compress(self, key, x) -> Payload: ...

    def decompress(self, key, payload, like) -> jax.Array: ...

    def variance_p(self, shape) -> float: ...

    def wire_bytes(self, shape, dtype) -> int: ...


def resolved_impl(comp) -> str:
    """The backend ``comp`` runs on here: ``resolve_impl`` under its
    registered TPU rule."""
    return resolve_impl(
        comp.impl, COMPRESSORS[comp.name].tpu_kernels(comp)
    )


def _check_impl(impl: str):
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")


# ---------------------------------------------------------------------------
# Leaf-level compressors
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Identity:
    # ``impl`` is explicitly allowlisted (validated, then ignored — there
    # is nothing to fuse) so backend selection works uniformly across
    # every compressor spec; any OTHER param is a spec error.
    impl: str = "auto"
    name: str = "identity"
    unbiased: bool = True

    def __post_init__(self):
        _check_impl(self.impl)

    def compress(self, key, x) -> Payload:
        del key
        return Payload(v=x)

    def decompress(self, key, payload, like) -> jax.Array:
        del key, like
        return payload["v"]

    def variance_p(self, shape) -> float:
        del shape
        return 1.0  # Assumption 3 constant (p >= 1; equality = lossless)

    def wire_bytes(self, shape, dtype) -> int:
        return math.prod(shape) * jnp.dtype(dtype).itemsize


@dataclasses.dataclass(frozen=True)
class BBitQuantizer:
    """Paper's C1 with s = 2^(b-1) - 1 magnitude levels (b bits incl. sign).

    C(x) = (||x||_inf / s) * sign(x) ∘ floor(s |x| / ||x||_inf + kappa),
    kappa ~ U[0,1)^n  =>  E[C(x)] = x  (unbiased for any s >= 1).

    ``impl=pallas`` (spec: ``qbit:bits=8,impl=pallas``; ``auto`` resolves
    to it on TPU) routes through the fused Pallas pipeline in
    ``repro.kernels.quantize`` — on the packed plane the whole ``[A,S,N]``
    compress is ONE launch with in-kernel counter-PRNG rounding bits.
    Same quantizer family and wire format; the stochastic-rounding stream
    differs from the jnp path (counter-PRNG bits vs
    ``jax.random.uniform``), so the Pallas path is unbiased and
    contractive but not bit-identical.
    """

    bits: int = 8
    impl: str = "auto"
    name: str = "qbit"
    unbiased: bool = True

    def __post_init__(self):
        _check_impl(self.impl)
        if self.bits not in (4, 8):
            raise ValueError(
                f"wire packing implemented for bits in (4, 8), got {self.bits}"
            )

    @property
    def levels(self) -> int:
        return 2 ** (self.bits - 1) - 1

    def _pallas(self) -> bool:
        return resolved_impl(self) == "pallas"

    def compress(self, key, x) -> Payload:
        if self._pallas():
            from repro.kernels.quantize import ops as qops

            return Payload(**qops.quantize_tensor(key, x, bits=self.bits))
        xf = _flat(x).astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(xf)), jnp.finfo(jnp.float32).tiny)
        kappa = jax.random.uniform(key, xf.shape)
        q = jnp.floor(self.levels * jnp.abs(xf) / scale + kappa)
        # |q| <= levels: |x|/scale <= 1 and kappa < 1 bound the floor
        q = jnp.sign(xf) * q
        q = q.astype(jnp.int8)
        if self.bits == 4:
            q = _pack4(q)
        return Payload(q=q, scale=scale)

    def decompress(self, key, payload, like) -> jax.Array:
        del key
        if self._pallas():
            from repro.kernels.quantize import ops as qops

            return qops.dequantize_tensor(
                payload, like.shape, dtype=like.dtype, bits=self.bits
            )
        q = payload["q"]
        n = math.prod(like.shape)
        if self.bits == 4:
            q = _unpack4(q, n)
        xf = payload["scale"] * q.astype(jnp.float32) / self.levels
        return jnp.reshape(xf, like.shape).astype(like.dtype)

    # -- fused plane path (one Pallas launch for all [A, S, N] messages) --

    def plane_ready(self) -> bool:
        return True

    def compress_plane(self, seed, sids, rids, x) -> Payload:
        from repro.kernels.quantize import ops as qops

        q, scale = qops.quantize_plane(seed, sids, rids, x, bits=self.bits)
        return Payload(q=q, scale=scale)

    def decompress_plane(self, seed, sids, rids, payload, like) -> jax.Array:
        del seed, sids, rids
        from repro.kernels.quantize import ops as qops

        n = math.prod(like.shape)
        out = qops.dequantize_plane(
            payload["q"], payload["scale"], n=n, bits=self.bits
        )
        return out.reshape(out.shape[:-1] + like.shape).astype(like.dtype)

    def variance_p(self, shape) -> float:
        # E||C(x)-x||^2 <= (n / (4 s^2)) * (||x||_inf^2 / ||x||^2) * ||x||^2
        # worst case ||x||_inf^2 * n / (4 s^2) <= n/(4 s^2) ||x||^2; p = 1 + n/(4 s^2)
        n = 1
        for d in shape:
            n *= d
        return 1.0 + n / (4.0 * self.levels**2)

    def wire_bytes(self, shape, dtype) -> int:
        del dtype
        n = 1
        for d in shape:
            n *= d
        return (n * self.bits + 7) // 8 + 4  # packed ints + f32 scale


def _pack4(q_int8):
    """Pack signed 4-bit values ([-7, 7]) two per byte (offset-8 nibbles)."""
    q = q_int8.astype(jnp.int32) + 8  # [1, 15]
    if q.shape[0] % 2:
        q = jnp.concatenate([q, jnp.full((1,), 8, q.dtype)])
    hi, lo = q[0::2], q[1::2]
    return ((hi << 4) | lo).astype(jnp.uint8)


def _unpack4(packed, n):
    p = packed.astype(jnp.int32)
    hi = (p >> 4) & 0xF
    lo = p & 0xF
    q = jnp.stack([hi, lo], axis=1).reshape(-1)[:n]
    return (q - 8).astype(jnp.int8)


@dataclasses.dataclass(frozen=True)
class RandK:
    """Paper's C2, seed-synchronized so indices never hit the wire.

    fraction: k = max(1, round(fraction * n)) per leaf.
    sampler:  "uniform" — exact uniform k-subset (permutation-based);
              "block"   — cyclic contiguous block at a uniform random offset
                          (each coordinate still has inclusion prob. k/n, so
                          C stays unbiased; O(k) instead of O(n log n));
              "stride"  — seeded affine set (off + j*stride) % n, stride
                          from a static table coprime to n: same O(k) and
                          unbiasedness as block (inclusion prob. k/n for
                          any fixed coprime stride), but decorrelated
                          coordinates AND derivable inside a Pallas kernel
                          by the counter PRNG (the fused plane path).
    """

    fraction: float = 0.25
    sampler: str = "uniform"
    impl: str = "auto"
    name: str = "randk"
    unbiased: bool = True

    def __post_init__(self):
        _check_impl(self.impl)
        if self.sampler not in ("uniform", "block", "stride"):
            raise ValueError(
                "sampler must be one of ('uniform', 'block', 'stride'), "
                f"got {self.sampler!r}"
            )

    def _pallas(self) -> bool:
        return resolved_impl(self) == "pallas"

    def _k(self, n: int) -> int:
        return max(1, int(round(self.fraction * n)))

    def _offset(self, key, n: int):
        return jax.random.randint(key, (), 0, n)

    def _affine(self, key, n: int):
        """``(off [1], stride [1])`` of the block/stride index set."""
        if self.sampler == "block":
            return (jnp.reshape(self._offset(key, n), (1,)),
                    jnp.ones((1,), jnp.int32))
        off, stride = prng.affine_params(
            prng.key_seed(key), n, prng.coprime_strides(n)
        )
        return jnp.reshape(off, (1,)), jnp.reshape(stride, (1,))

    def _indices(self, key, n: int):
        k = self._k(n)
        if self.sampler == "uniform":
            perm = jax.random.permutation(key, n)
            return perm[:k]
        if self.sampler == "stride":
            return prng.affine_indices(
                prng.key_seed(key), n, k, prng.coprime_strides(n)
            )
        return (self._offset(key, n) + jnp.arange(k)) % n

    def compress(self, key, x) -> Payload:
        xf = _flat(x)
        n = xf.shape[0]
        if self._pallas():
            from repro.kernels.sparse_gather import ops as sg

            if self.sampler == "uniform":
                return Payload(v=sg.sparse_gather(xf, self._indices(key, n)))
            off, stride = self._affine(key, n)
            return Payload(v=sg.randk_gather(
                xf[None], off, stride, self._k(n)
            )[0])
        return Payload(v=jnp.take(xf, self._indices(key, n), axis=0))

    def decompress(self, key, payload, like) -> jax.Array:
        n = math.prod(like.shape)
        k = self._k(n)
        if self._pallas():
            from repro.kernels.sparse_gather import ops as sg

            if self.sampler == "uniform":
                out = sg.sparse_scatter(
                    payload["v"], self._indices(key, n), n, gain=n / k
                )
            else:
                off, stride = self._affine(key, n)
                out = sg.randk_scatter(
                    payload["v"][None], off, stride, n, n / k
                )[0]
            return jnp.reshape(out, like.shape).astype(like.dtype)
        idx = self._indices(key, n)
        out = jnp.zeros((n,), payload["v"].dtype)
        out = out.at[idx].set((n / k) * payload["v"])
        return jnp.reshape(out, like.shape).astype(like.dtype)

    # -- fused plane path: index sets derived in-kernel, never in HBM --

    def _strides(self, n: int) -> tuple:
        return (1,) if self.sampler == "block" else prng.coprime_strides(n)

    def plane_ready(self) -> bool:
        # "uniform" needs a per-message O(n log n) permutation — no
        # in-kernel derivation; it falls back to the vmapped path.
        return self.sampler in ("block", "stride")

    def compress_plane(self, seed, sids, rids, x) -> Payload:
        from repro.kernels.sparse_gather import ops as sg

        n = x.shape[-1]
        return Payload(v=sg.randk_gather_plane(
            seed, sids, rids, x, k=self._k(n), strides=self._strides(n)
        ))

    def decompress_plane(self, seed, sids, rids, payload, like) -> jax.Array:
        from repro.kernels.sparse_gather import ops as sg

        n = math.prod(like.shape)
        k = self._k(n)
        out = sg.randk_scatter_plane(
            seed, sids, rids, payload["v"], n=n, gain=n / k,
            strides=self._strides(n),
        )
        return out.reshape(out.shape[:-1] + like.shape).astype(like.dtype)

    def variance_p(self, shape) -> float:
        n = 1
        for d in shape:
            n *= d
        return n / self._k(n)

    def wire_bytes(self, shape, dtype) -> int:
        n = 1
        for d in shape:
            n *= d
        return self._k(n) * jnp.dtype(dtype).itemsize


@dataclasses.dataclass(frozen=True)
class TopK:
    """Biased magnitude top-k (needs indices on the wire: values + int32 idx)."""

    fraction: float = 0.25
    impl: str = "auto"
    name: str = "topk"
    unbiased: bool = False

    def __post_init__(self):
        _check_impl(self.impl)

    def _pallas(self) -> bool:
        return resolved_impl(self) == "pallas"

    def _k(self, n: int) -> int:
        return max(1, int(round(self.fraction * n)))

    def compress(self, key, x) -> Payload:
        del key
        xf = _flat(x)
        k = self._k(xf.shape[0])
        v, idx = jax.lax.top_k(jnp.abs(xf), k)
        del v
        if self._pallas():
            from repro.kernels.sparse_gather import ops as sg

            return Payload(v=sg.sparse_gather(xf, idx),
                           idx=idx.astype(jnp.int32))
        return Payload(v=jnp.take(xf, idx), idx=idx.astype(jnp.int32))

    def decompress(self, key, payload, like) -> jax.Array:
        del key
        n = math.prod(like.shape)
        if self._pallas():
            from repro.kernels.sparse_gather import ops as sg

            out = sg.sparse_scatter(payload["v"], payload["idx"], n)
            return jnp.reshape(out, like.shape).astype(like.dtype)
        out = jnp.zeros((n,), payload["v"].dtype)
        out = out.at[payload["idx"]].set(payload["v"])
        return jnp.reshape(out, like.shape).astype(like.dtype)

    def variance_p(self, shape) -> float:
        n = 1
        for d in shape:
            n *= d
        return float(n) / self._k(n)  # loose; TopK is biased anyway

    def wire_bytes(self, shape, dtype) -> int:
        n = 1
        for d in shape:
            n *= d
        return self._k(n) * (jnp.dtype(dtype).itemsize + 4)


# ---------------------------------------------------------------------------
# Tree-level wrappers: compress every leaf with a per-leaf folded key
# ---------------------------------------------------------------------------


def compress_tree(comp, key, tree) -> Payload:
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    payloads = [comp.compress(k, x) for k, x in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, payloads)


def decompress_tree(comp, key, payload_tree, like_tree):
    likes, treedef = jax.tree.flatten(like_tree)
    keys = jax.random.split(key, len(likes))
    # payload_tree has Payload nodes at leaf positions of like_tree
    payloads = treedef.flatten_up_to(payload_tree)
    outs = [
        comp.decompress(k, p, jax.ShapeDtypeStruct(x.shape, x.dtype))
        for k, p, x in zip(keys, payloads, likes)
    ]
    return jax.tree.unflatten(treedef, outs)


def tree_wire_bytes(comp, tree) -> int:
    return sum(
        comp.wire_bytes(x.shape, x.dtype) for x in jax.tree.leaves(tree)
    )


# ---------------------------------------------------------------------------
# Plane-level helpers: whole-round [.., N] message batches
# ---------------------------------------------------------------------------


def _use_fused(comp) -> bool:
    ready = getattr(comp, "plane_ready", None)
    return ready is not None and ready() and resolved_impl(comp) == "pallas"


def _vmap_n(fn, nd: int):
    for _ in range(nd):
        fn = jax.vmap(fn)
    return fn


def _per_agent_shard(fn, exchange, seed, *planes):
    """``fn(seed, *planes)`` — inside a shard_map over the agent axis when
    ``exchange`` is bound to a mesh.  The compiler cannot partition a
    Pallas kernel, and every message is independent, so each device runs
    the fused kernels on its own agents' rows (``planes`` all lead with
    the agent dim; the seed pair is replicated)."""
    if exchange is None or exchange.axis is None:
        return fn(seed, *planes)
    from jax.sharding import PartitionSpec as P

    return jax.shard_map(
        fn, mesh=exchange.mesh,
        in_specs=(P(),) + (P(exchange.axis),) * len(planes),
        out_specs=P(exchange.axis),
        check_vma=False,  # pallas_call outputs carry no varying-axes type
    )(seed, *planes)


def plane_compress(comp, keyfn, base_key, senders, receivers, delta, like,
                   exchange=None):
    """Compress every message of a batched plane ``delta [..., N]`` and
    return ``(payload_tree, reconstruction)`` (the reconstruction feeds
    error feedback — both endpoints must see the SAME decompress).

    Fused route (``impl=pallas`` + plane-capable compressor): ONE Pallas
    launch for the whole plane (per device, when ``exchange`` is bound to
    a mesh), per-message randomness derived from ``(key_seed(base_key),
    sender, receiver)`` — ``receivers=None`` marks one-to-all broadcast
    messages.  Otherwise: the exact vmapped per-message
    ``compress_tree(comp, keyfn(ids...), ...)`` path the tree solvers
    use, bit-identical to pre-plane behavior.
    """
    if _use_fused(comp):
        def fused(seed, senders, delta, *receivers):
            rids = receivers[0] if receivers else None
            p = comp.compress_plane(seed, senders, rids, delta)
            return p, comp.decompress_plane(seed, senders, rids, p, like)

        extra = () if receivers is None else (receivers,)
        return _per_agent_shard(fused, exchange, prng.key_seed(base_key),
                                senders, delta, *extra)
    nd = delta.ndim - 1

    if receivers is None:
        def one(s, d):
            kk = keyfn(s)
            p = compress_tree(comp, kk, d)
            return p, decompress_tree(comp, kk, p, like)

        return _vmap_n(one, nd)(senders, delta)

    def one(s, r, d):
        kk = keyfn(s, r)
        p = compress_tree(comp, kk, d)
        return p, decompress_tree(comp, kk, p, like)

    return _vmap_n(one, nd)(senders, receivers, delta)


def plane_decompress(comp, keyfn, base_key, senders, receivers, payload,
                     like, nd: int, exchange=None):
    """Receiver-side reconstruction of a batched payload plane —
    re-derives the SAME per-message randomness as ``plane_compress`` (the
    seeded wire format: only ``base_key`` round state is shared).  ``nd``
    is the number of batch dims on the payload leaves."""
    if _use_fused(comp):
        def fused(seed, senders, payload, *receivers):
            rids = receivers[0] if receivers else None
            return comp.decompress_plane(seed, senders, rids, payload, like)

        extra = () if receivers is None else (receivers,)
        return _per_agent_shard(fused, exchange, prng.key_seed(base_key),
                                senders, payload, *extra)

    if receivers is None:
        def one(s, p):
            return decompress_tree(comp, keyfn(s), p, like)

        return _vmap_n(one, nd)(senders, payload)

    def one(s, r, p):
        return decompress_tree(comp, keyfn(s, r), p, like)

    return _vmap_n(one, nd)(senders, receivers, payload)


# ---------------------------------------------------------------------------
# Sealed payloads: additive checksum + round tag (fault detection)
# ---------------------------------------------------------------------------

# wire overhead of a sealed message: crc + tag, one uint32 each
SEAL_BYTES = 8

_SEAL_KEYS = ("crc", "tag")
_UINT_OF_WIDTH = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}


def _u32_view(leaf):
    """Bit-exact uint32 view of a leaf (narrow dtypes widen losslessly)."""
    udt = _UINT_OF_WIDTH[jnp.dtype(leaf.dtype).itemsize]
    return jax.lax.bitcast_convert_type(leaf, udt).astype(jnp.uint32)


def payload_checksum(payload, nd: int):
    """Additive mod-2^32 checksum over the data leaves of a payload whose
    leaves carry ``nd`` lead (message-batch) dims — shape ``[lead]``.

    Additive (not a CRC polynomial) on purpose: any single bit flip in
    any leaf perturbs the sum by a nonzero power of two, and *linearity*
    lets fault injection rewind a round tag checksum-consistently — a
    stale message stays crc-valid and is rejected by the tag check
    alone, keeping staleness and corruption distinguishable on the wire.
    """
    tot = None
    for k in payload:
        if k in _SEAL_KEYS:
            continue
        v = _u32_view(payload[k])
        s = jnp.sum(v.reshape(v.shape[:nd] + (-1,)), axis=-1,
                    dtype=jnp.uint32)
        tot = s if tot is None else tot + s
    return tot


def seal_plane(payload, tag, nd: int):
    """Add ``crc``/``tag`` uint32 leaves (``crc = checksum + tag``) to a
    batched payload; ``tag`` is the round index (traced ok)."""
    csum = payload_checksum(payload, nd)
    tag_arr = jnp.broadcast_to(jnp.asarray(tag).astype(jnp.uint32),
                               csum.shape)
    return Payload(**dict(payload), crc=csum + tag_arr, tag=tag_arr)


def verify_plane_kinds(payload, expected_tag):
    """Strip the seal and verdict each message with the failure KIND
    split out: ``(data_payload, ok, crc_ok, tag_ok)``, all verdicts
    [lead-shaped] bool.  ``crc_ok`` fails on dropped/corrupted payloads
    (checksum mismatch); ``tag_ok`` fails on wrong-round delivery — a
    stale replay is checksum-consistent by construction and rejected by
    the tag alone, which is what keeps the two observable as distinct
    counters in the telemetry plane.  ``ok = crc_ok & tag_ok``."""
    crc, tag = payload["crc"], payload["tag"]
    data = Payload(**{k: v for k, v in payload.items()
                      if k not in _SEAL_KEYS})
    want = jnp.asarray(expected_tag).astype(jnp.uint32)
    crc_ok = payload_checksum(data, crc.ndim) + tag == crc
    tag_ok = tag == want
    return data, crc_ok & tag_ok, crc_ok, tag_ok


def verify_plane(payload, expected_tag):
    """Strip the seal and verdict each message: ``(data_payload, ok)``
    with ``ok`` [lead-shaped] True iff the checksum holds AND the round
    tag matches ``expected_tag``.  Failed messages downgrade their edge
    to dark (async-ADMM hold) — callers gate on ``ok``, never on the
    possibly-poisoned data."""
    data, ok, _, _ = verify_plane_kinds(payload, expected_tag)
    return data, ok


# ---------------------------------------------------------------------------
# Registry + spec parsing (mirrors core.solver's SOLVERS entries)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CompressorEntry:
    """One registered compressor: class + the spec params it accepts
    (``get_compressor`` validates against ``params`` BEFORE construction,
    so misspellings fail with the valid names, not a TypeError)."""

    name: str
    cls: type
    params: frozenset
    doc: str = ""
    # static rule: does this configuration have Pallas kernels the TPU
    # compiler accepts?  ``impl=auto`` picks them on a TPU exactly when
    # it holds; explicit ``impl=pallas`` on a TPU fails when it does not
    tpu_kernels: Callable[[Compressor], bool] = lambda comp: False


def _entry(cls, doc: str, tpu_kernels=lambda comp: False) -> CompressorEntry:
    name = cls.__dataclass_fields__["name"].default
    params = frozenset(
        f.name
        for f in dataclasses.fields(cls)
        if f.init and f.name not in ("name", "unbiased")
    )
    return CompressorEntry(name=name, cls=cls, params=params, doc=doc,
                           tpu_kernels=tpu_kernels)


COMPRESSORS: dict[str, CompressorEntry] = {
    e.name: e
    for e in (
        _entry(Identity, "no compression (exact LT-ADMM)"),
        _entry(BBitQuantizer, "unbiased stochastic b-bit quantizer (C1)",
               tpu_kernels=lambda comp: True),
        # uniform needs an arbitrary-index gather, which does not lower
        _entry(RandK, "seed-synchronized rand-k, zero index bytes (C2)",
               tpu_kernels=lambda comp: comp.sampler in ("block", "stride")),
        _entry(TopK, "biased magnitude top-k (values + indices, needs EF)"),
    )
}


def compressor_entry(name: str) -> CompressorEntry:
    try:
        return COMPRESSORS[name]
    except KeyError:
        raise ValueError(
            f"unknown compressor {name!r}; choose from "
            f"{sorted(COMPRESSORS)}"
        ) from None


def coerce_param(v):
    """Spec-string value -> python scalar: int, then float, then bool
    literal, else the string itself (e.g. ``sampler=block``)."""
    if not isinstance(v, str):
        return v
    for cast in (int, float):
        try:
            return cast(v)
        except ValueError:
            pass
    if v.lower() in ("true", "false"):
        return v.lower() == "true"
    return v


def _parse_spec(spec: str):
    """``name[:k=v,...]`` -> (entry, params) with unknown/misspelled
    params rejected up front (naming the valid ones) and the legacy
    ``kernel=`` param mapped onto ``impl=``.  Returns ``shim_used`` so
    ``get_compressor`` can warn exactly when the deprecated form ran."""
    name, _, rest = spec.partition(":")
    entry = compressor_entry(name)
    params = {}
    for item in rest.replace("|", ",").split(","):
        if not item:
            continue
        k, eq, v = item.partition("=")
        if not eq:
            raise ValueError(
                f"malformed compressor param {item!r} in spec {spec!r} "
                f"(expected k=v)"
            )
        params[k.strip()] = coerce_param(v.strip())
    return entry, params


def _apply_kernel_shim(params: dict) -> bool:
    if "kernel" not in params:
        return False
    flag = params.pop("kernel")
    if not isinstance(flag, bool):
        raise ValueError(f"kernel= expects true/false, got {flag!r}")
    params.setdefault("impl", "pallas" if flag else "jnp")
    return True


def _construct(entry: CompressorEntry, params: dict):
    unknown = sorted(set(params) - entry.params)
    if unknown:
        raise ValueError(
            f"compressor {entry.name!r} got unknown param(s) {unknown}; "
            f"valid params: {sorted(entry.params)}"
        )
    try:
        return entry.cls(**params)
    except TypeError as e:
        raise ValueError(
            f"bad params for compressor {entry.name!r}: {e}"
        ) from None


def validate_spec(spec: str) -> None:
    """Parse-time validation of a compressor spec (used by the solver
    grammar so ``make_solver("ltadmm:compressor=qbit:bit=4", ...)`` fails
    up front, naming qbit's valid params).  Raises exactly what
    ``get_compressor`` would; never warns."""
    entry, params = _parse_spec(spec)
    _apply_kernel_shim(params)
    _construct(entry, params)


def get_compressor(spec: str, **kw) -> Compressor:
    """Compressor from a spec string: ``name[:k=v,...]``.

    ``get_compressor("qbit:bits=4")``,
    ``get_compressor("randk:fraction=0.25,sampler=block")``.  When the
    spec is nested inside an outer comma grammar (solver specs), ``|``
    is accepted in place of ``,``.  Explicit keyword arguments are the
    legacy construction path (``get_compressor("qbit", bits=4)``) and
    override spec params on collision.  The deprecated ``kernel=true``
    param maps to ``impl=pallas`` (``false`` -> ``impl=jnp``) with a
    DeprecationWarning.
    """
    entry, params = _parse_spec(spec)
    params.update(kw)
    if _apply_kernel_shim(params):
        warnings.warn(
            "compressor param kernel= is deprecated; use "
            "impl={auto,jnp,pallas} (kernel=true -> impl=pallas, "
            "kernel=false -> impl=jnp)",
            DeprecationWarning,
            stacklevel=2,
        )
    return _construct(entry, params)
