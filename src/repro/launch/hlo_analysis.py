"""Roofline-term extraction from compiled (post-SPMD) HLO text.

``compiled.cost_analysis()`` counts a ``while`` body exactly once, so for
scan-over-layers programs (all of ours) it underestimates by ~n_layers.
This module re-derives the three roofline inputs by walking the optimized
per-device HLO with loop trip-count multipliers:

* ``dot_flops``        — 2 x prod(result_shape) x contracted_size for every
                         dot/convolution, x multiplier.  (Elementwise FLOPs
                         are ignored — matmuls dominate every model here.)
* ``memory_bytes``     — per top-level op: operand bytes + result bytes
                         (fusions are XLA's HBM-traffic units, so counting
                         their boundaries approximates HBM traffic).
* ``collective_bytes`` — operand/result bytes of all-gather / all-reduce /
                         reduce-scatter / all-to-all / collective-permute,
                         x multiplier.

All shapes in compiled.as_text() are per-device (post-partitioning), so the
terms are per-chip — exactly what the roofline formula needs.

``named_scopes`` maps each top-level instruction of a compiled module to
the ``jax.named_scope`` its ``op_name`` metadata carries (the LT-ADMM
round's phases, ``launch/train.py``'s ``round_scopes``).
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z]+\d*)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^)]*\)|[a-z]+\d*\[[\d,]*\]\S*)\s+"
    r"([\w\-]+)\(",
)
_CALLED_RE = re.compile(r"(?:body|to_apply|calls|condition)=%?([\w.\-]+)")
_TRIP_RE = re.compile(r'known_trip_count\\?":\{\\?"n\\?":\\?"(\d+)')

COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def _shape_bytes(type_str: str) -> float:
    total = 0.0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclasses.dataclass
class Op:
    name: str
    kind: str
    result_type: str
    line: str


@dataclasses.dataclass
class Computation:
    name: str
    ops: list
    symbols: dict  # op name -> result type string


_HEADER_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{")


def _computation_lines(hlo_text: str):
    """-> ({computation name: its instruction lines}, ENTRY's name)."""
    comps, entry, current = {}, None, None
    for line in hlo_text.splitlines():
        stripped = line.strip()
        header = _HEADER_RE.match(stripped)
        if header and not line.startswith(" " * 2):
            current = comps[header.group(1)] = []
            if stripped.startswith("ENTRY"):
                entry = header.group(1)
            continue
        if current is None:
            continue
        if stripped == "}":
            current = None
            continue
        current.append(line)
    return comps, entry


def parse_computations(hlo_text: str) -> dict:
    lines, entry = _computation_lines(hlo_text)
    comps = {}
    for cname, body in lines.items():
        comps[cname] = current = Computation(cname, [], {})
        for line in body:
            m = _OP_RE.match(line)
            if m:
                name, rtype, kind = m.groups()
                current.ops.append(Op(name, kind, rtype, line.strip()))
                current.symbols[name] = rtype
    if entry is not None:
        comps["__entry__"] = comps[entry]
    return comps


_NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
# kinds whose lines name called computations: a fusion's computation is
# fused into it, the others' run as top-level code
_CONTROL_RE = re.compile(r"\s(fusion|while|conditional|call)\(")
_SUBCOMP_RE = re.compile(
    r"\b(?:body|condition|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_FUSED_RE = re.compile(r"\bcalls=%?([\w.\-]+)")


def named_scopes(hlo_text: str, prefix: str) -> dict:
    """Instruction name -> its ``jax.named_scope`` that starts with
    ``prefix`` (the first such component of its ``op_name``), for every
    top-level instruction of a compiled module: those of ENTRY and of
    the computations it runs through while, conditional and call — the
    ops a device trace shows.  A fusion whose own line carries no
    metadata takes the scope of its fused computation's root.
    Instructions under no such scope are left out."""
    comps, entry = _computation_lines(hlo_text)
    scope_re = re.compile(r"(?<![\w.])" + re.escape(prefix) + r"\w+")

    def scope(line):
        m = _OP_NAME_RE.search(line)
        s = scope_re.search(m.group(1)) if m else None
        return s.group(0) if s else None

    def root(cname):
        return next((ln for ln in comps.get(cname, ())
                     if ln.lstrip().startswith("ROOT")), "")

    out, todo, seen = {}, [entry], set()
    while todo:
        cname = todo.pop()
        if cname in seen or cname not in comps:
            continue
        seen.add(cname)
        for line in comps[cname]:
            name = _NAME_RE.match(line)
            if not name:
                continue
            kind = _CONTROL_RE.search(line)
            kind = kind.group(1) if kind else None
            if kind in ("while", "conditional", "call"):
                todo += _SUBCOMP_RE.findall(line)
                for branches in _BRANCHES_RE.findall(line):
                    todo += [b.strip().lstrip("%")
                             for b in branches.split(",")]
            s = scope(line)
            if kind == "fusion" and "metadata=" not in line:
                fused = _FUSED_RE.search(line)
                s = scope(root(fused.group(1))) if fused else None
            if s:
                out[name.group(1)] = s
    return out


def _multipliers(comps: dict) -> dict:
    """computation name -> execution-count multiplier (trip-count aware)."""
    entry = comps.get("__entry__")
    mult = defaultdict(float)
    if entry is None:
        return mult
    mult[entry.name] = 1.0
    # iterate to fixpoint (call graph is a DAG)
    for _ in range(64):
        changed = False
        for cname, comp in comps.items():
            if cname == "__entry__" or mult[cname] == 0:
                continue
            base = mult[cname]
            for op in comp.ops:
                called = _CALLED_RE.findall(op.line)
                if not called:
                    continue
                trip = 1.0
                if op.kind == "while":
                    tm = _TRIP_RE.search(op.line)
                    trip = float(tm.group(1)) if tm else 1.0
                for cal in called:
                    if cal in comps:
                        new = base * trip
                        if new > mult[cal]:
                            mult[cal] = new
                            changed = True
        if not changed:
            break
    return mult


def _operand_names(line: str) -> list:
    # operands inside the top-level parens of op(...)
    m = re.search(r"\w\(([^)]*)\)", line)
    if not m:
        return []
    return re.findall(r"%([\w.\-]+)", m.group(1))


def _dot_flops(op: Op, symbols: dict) -> float:
    # element count of result:
    elems = 0
    for dtype, dims in _SHAPE_RE.findall(op.result_type):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        elems += n
    # contracted size from lhs shape and contracting dims
    cm = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.line)
    operands = _operand_names(op.line)
    if not cm or not operands:
        return 2.0 * elems  # fallback
    lhs_type = symbols.get(operands[0], "")
    sm = _SHAPE_RE.search(lhs_type)
    if not sm:
        return 2.0 * elems
    lhs_dims = [int(d) for d in sm.group(2).split(",") if d]
    contracted = 1
    for ci in cm.group(1).split(","):
        if ci != "" and int(ci) < len(lhs_dims):
            contracted *= lhs_dims[int(ci)]
    return 2.0 * elems * contracted


@dataclasses.dataclass
class HLOStats:
    dot_flops: float = 0.0
    memory_bytes: float = 0.0  # v1: operand+result per op (upper bound —
    # fan-out counted once per consumer)
    memory_bytes_w2: float = 0.0  # v2: result bytes x 2 (write + one read;
    # tighter HBM-traffic estimate, used for the roofline memory term)
    collective_bytes: float = 0.0
    collective_counts: dict = dataclasses.field(default_factory=dict)

    def as_dict(self):
        return {
            "dot_flops": self.dot_flops,
            "memory_bytes": self.memory_bytes,
            "memory_bytes_w2": self.memory_bytes_w2,
            "collective_bytes": self.collective_bytes,
            "collective_counts": dict(self.collective_counts),
        }


def analyze(hlo_text: str) -> HLOStats:
    comps = parse_computations(hlo_text)
    mult = _multipliers(comps)
    stats = HLOStats(collective_counts=defaultdict(float))
    for cname, comp in comps.items():
        if cname == "__entry__":
            continue
        m = mult.get(cname, 0.0)
        if m == 0.0:
            continue
        for op in comp.ops:
            if op.kind in ("dot", "convolution"):
                stats.dot_flops += m * _dot_flops(op, comp.symbols)
            if op.kind in COLLECTIVES or any(
                op.kind.startswith(c) for c in COLLECTIVES
            ):
                moved = _shape_bytes(op.result_type)
                stats.collective_bytes += m * moved
                key = op.kind
                stats.collective_counts[key] = (
                    stats.collective_counts.get(key, 0.0) + m
                )
            # memory traffic proxy: result + operand bytes of real ops
            if op.kind not in ("parameter", "constant", "tuple",
                               "get-tuple-element", "bitcast"):
                rbytes = _shape_bytes(op.result_type)
                opbytes = sum(
                    _shape_bytes(comp.symbols.get(o, ""))
                    for o in _operand_names(op.line)
                )
                stats.memory_bytes += m * (rbytes + opbytes)
                stats.memory_bytes_w2 += m * 2.0 * rbytes
    stats.collective_counts = dict(stats.collective_counts)
    return stats


# ---------------------------------------------------------------------------
# Roofline terms (TPU v5e-class constants; per-chip)
# ---------------------------------------------------------------------------

PEAK_FLOPS = 197e12  # bf16
HBM_BW = 819e9  # bytes/s
ICI_BW = 50e9  # bytes/s/link


def roofline_terms(stats: HLOStats) -> dict:
    t_comp = stats.dot_flops / PEAK_FLOPS
    t_mem = (stats.memory_bytes_w2 or stats.memory_bytes) / HBM_BW
    t_coll = stats.collective_bytes / ICI_BW
    dominant = max(
        ("compute", t_comp), ("memory", t_mem), ("collective", t_coll),
        key=lambda kv: kv[1],
    )[0]
    return {
        "t_compute_s": t_comp,
        "t_memory_s": t_mem,
        "t_collective_s": t_coll,
        "dominant": dominant,
    }
