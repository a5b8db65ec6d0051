"""Post-SPMD HLO analysis: loop-corrected collective & dot-FLOP accounting.

XLA's HloCostAnalysis visits a while body once (verified empirically in
EXPERIMENTS.md §Dry-run notes), so scanned-layer programs under-report by
~num_layers.  This parser walks the optimized HLO module text, recovers
while trip counts from their condition computations, propagates a
multiplier down the call graph (while/fusion/call), and accumulates:

  * collective result-bytes per op kind (all-reduce, all-gather,
    reduce-scatter, all-to-all, collective-permute, incl. -start forms)
  * dot FLOPs (2 · result_elems · contracted_size)

Both are *per-device* quantities in SPMD modules: shapes in the
partitioned module are already per-partition.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^)]*\)|\w+\[[\d,]*\]\S*)\s+([\w\-]+)\("
)
_CALL_RE = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_WHILE_ATTR_RE = re.compile(r"(body|condition)=%?([\w.\-]+)")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_elems(type_str: str) -> int:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return 0
    dims = m.group(2)
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n


@dataclasses.dataclass
class Op:
    name: str
    kind: str
    type_str: str
    line: str


@dataclasses.dataclass
class Computation:
    name: str
    ops: list[Op]
    is_entry: bool


def parse_module(hlo_text: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    for line in hlo_text.splitlines():
        stripped = line.strip()
        header = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s*(\([^{]*\))?\s*->.*\{", stripped)
        if header and not stripped.startswith("//") and "=" not in stripped.split("(")[0]:
            cur = Computation(name=header.group(2), ops=[], is_entry=bool(header.group(1)))
            comps[cur.name] = cur
            continue
        if stripped == "}":
            cur = None
            continue
        if cur is None:
            continue
        m = _OP_RE.match(line)
        if m:
            cur.ops.append(Op(name=m.group(1), kind=m.group(3), type_str=m.group(2), line=line))
    return comps


def _while_trip_count(cond: Computation) -> int:
    """Canonical lowered loops compare the induction var with a constant."""
    consts = []
    for op in cond.ops:
        if op.kind == "constant":
            mm = re.search(r"constant\((-?\d+)\)", op.line)
            if mm:
                consts.append(int(mm.group(1)))
    pos = [c for c in consts if 0 < c <= 10_000_000]
    return max(pos) if pos else 1


def _multipliers(comps: dict[str, Computation]) -> dict[str, float]:
    entry = next((c for c in comps.values() if c.is_entry), None)
    mult: dict[str, float] = defaultdict(float)
    if entry is None:
        return {name: 1.0 for name in comps}
    mult[entry.name] = 1.0
    stack = [entry.name]
    seen_edges = set()
    while stack:
        name = stack.pop()
        comp = comps.get(name)
        if comp is None:
            continue
        m = mult[name]
        for op in comp.ops:
            if op.kind == "while":
                attrs = dict(_WHILE_ATTR_RE.findall(op.line))
                cond_name = attrs.get("condition")
                body_name = attrs.get("body")
                trip = _while_trip_count(comps[cond_name]) if cond_name in comps else 1
                for child in (cond_name, body_name):
                    if child and (name, child) not in seen_edges:
                        seen_edges.add((name, child))
                        mult[child] += m * trip
                        stack.append(child)
            else:
                for child in _CALL_RE.findall(op.line):
                    if child in comps and (name, child, op.name) not in seen_edges:
                        seen_edges.add((name, child, op.name))
                        mult[child] += m
                        stack.append(child)
    return dict(mult)


def _dot_flops(op: Op, symbols: dict[str, str]) -> float:
    """2 * result_elems * contracted_size (per partition).

    Operands are printed by name only in optimized HLO; their types come
    from the computation's symbol table (parameters + prior ops)."""
    res_elems = _shape_elems(op.type_str)
    call = op.line.split(op.kind + "(", 1)[-1]
    mops = re.match(r"\s*%?([\w.\-]+)", call)
    lhs_dims: list[int] = []
    if mops and mops.group(1) in symbols:
        sh = _SHAPE_RE.search(symbols[mops.group(1)])
        if sh and sh.group(2):
            lhs_dims = [int(d) for d in sh.group(2).split(",")]
    else:  # fall back to inline-typed operand, if present
        sh = _SHAPE_RE.search(call)
        if sh and sh.group(2):
            lhs_dims = [int(d) for d in sh.group(2).split(",")]
    mdims = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.line)
    contracted = 1
    if mdims and mdims.group(1):
        for d in mdims.group(1).split(","):
            contracted *= lhs_dims[int(d)] if int(d) < len(lhs_dims) else 1
    return 2.0 * res_elems * contracted


@dataclasses.dataclass
class HLOStats:
    collective_bytes: dict[str, float]       # kind -> loop-corrected bytes/device
    collective_bytes_static: dict[str, float]  # without loop correction
    collective_count: dict[str, int]
    dot_flops: float                          # loop-corrected, per device
    dot_flops_static: float
    while_trips: list[int]

    @property
    def total_collective_bytes(self) -> float:
        return sum(self.collective_bytes.values())


def analyze(hlo_text: str) -> HLOStats:
    comps = parse_module(hlo_text)
    mult = _multipliers(comps)
    coll: dict[str, float] = defaultdict(float)
    coll_static: dict[str, float] = defaultdict(float)
    count: dict[str, int] = defaultdict(int)
    dflops = 0.0
    dflops_static = 0.0
    trips = []
    for comp in comps.values():
        m = mult.get(comp.name, 0.0)
        if m == 0.0:
            continue  # unreachable (dead computation)
        symbols = {op.name: op.type_str for op in comp.ops}
        for op in comp.ops:
            kind = op.kind
            base = kind.replace("-start", "")
            if kind.endswith("-done"):
                continue
            if base in _COLLECTIVES:
                b = _shape_bytes(op.type_str)
                coll[base] += b * m
                coll_static[base] += b
                count[base] += 1
            elif kind == "dot":
                f = _dot_flops(op, symbols)
                dflops += f * m
                dflops_static += f
            elif kind == "while":
                attrs = dict(_WHILE_ATTR_RE.findall(op.line))
                cn = attrs.get("condition")
                if cn in comps:
                    trips.append(_while_trip_count(comps[cn]))
    return HLOStats(
        collective_bytes=dict(coll),
        collective_bytes_static=dict(coll_static),
        collective_count=dict(count),
        dot_flops=dflops,
        dot_flops_static=dflops_static,
        while_trips=trips,
    )


def span_attrs(stats: HLOStats, **extra) -> dict:
    """Flatten an HLOStats into span meta (obs/trace.py): scalar totals
    plus per-kind collective bytes, so a compiled program's span in the
    profile carries its communication/compute footprint."""
    attrs = dict(
        dot_flops=stats.dot_flops,
        collective_bytes=stats.total_collective_bytes,
        collective_launches=sum(stats.collective_count.values()),
        while_trips=sum(stats.while_trips),
    )
    for kind, b in sorted(stats.collective_bytes.items()):
        attrs[f"collective_bytes.{kind}"] = b
    attrs.update(extra)
    return attrs
