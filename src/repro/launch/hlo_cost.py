"""Trip-count-aware HLO cost analysis.

``compiled.cost_analysis()`` counts a ``while`` (lax.scan) body ONCE
(verified in tests/test_roofline.py), which undercounts scanned-layer
models by the layer count.  This module parses the post-SPMD HLO text,
builds the computation call graph, extracts while-loop trip counts from
their condition computations, and accumulates:

  * flops: dot/convolution ops (2*out_elems*contracted; x4 for complex)
  * bytes: every op's operands + output (XLA's 'bytes accessed' convention)
  * collective bytes/counts by kind (all-reduce doubled: RS+AG equivalent)

each weighted by the product of enclosing while trip counts.

The parser is deliberately conservative: computations reachable only as
``fusion``/``to_apply`` subroutines are not double-counted (their cost is
attributed at the call site via the fusion op's operands/outputs).
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Optional

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16,
}

_COMP_HEADER = re.compile(
    r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$")
_OP_LINE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.*?)\s*([\w\-]+)\(")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
_OPERANDS = re.compile(r"%([\w\.\-]+)")
_ATTR_CALL = re.compile(
    r"(body|condition|to_apply|calls)=\s*(?:\{([^}]*)\}|%?([\w\.\-]+))")
_CONTRACT = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_TRIP_CONST = re.compile(r"constant\((\d+)\)")

COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute")


def _shape_info(type_str: str):
    """[(dtype, elems, bytes)] for possibly-tuple type strings."""
    out = []
    for dtype, dims in _SHAPE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        out.append((dtype, n, n * _DTYPE_BYTES[dtype]))
    return out


def _total_bytes(type_str: str) -> int:
    return sum(b for _, _, b in _shape_info(type_str))


@dataclasses.dataclass
class Op:
    name: str
    kind: str
    type_str: str
    line: str
    operand_str: str    # text inside the op's argument parens


@dataclasses.dataclass
class Computation:
    name: str
    ops: list
    shapes: dict            # op name -> type string


def parse_computations(hlo: str) -> dict:
    comps: dict = {}
    cur: Optional[Computation] = None
    for line in hlo.splitlines():
        stripped = line.strip()
        m = _COMP_HEADER.match(stripped) if (
            stripped.endswith("{") and "->" in stripped
            and "=" not in stripped.split("(")[0]) else None
        if m:
            cur = Computation(m.group(1), [], {})
            comps[cur.name] = cur
            continue
        if cur is None:
            continue
        if stripped == "}":
            cur = None
            continue
        om = _OP_LINE.match(line)
        if om:
            name, type_str, kind = om.group(1), om.group(2), om.group(3)
            rest = line[om.end():]
            depth = 1
            end = 0
            for i, ch in enumerate(rest):
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        end = i
                        break
            operand_str = rest[:end]
            cur.ops.append(Op(name, kind, type_str, line, operand_str))
            cur.shapes[name] = type_str
    return comps


def _callees(op: Op) -> dict:
    """attr -> [computation names] referenced by this op."""
    out = {}
    for m in _ATTR_CALL.finditer(op.line):
        attr = m.group(1)
        names = []
        if m.group(2) is not None:
            names = [n.strip().lstrip("%") for n in m.group(2).split(",")]
        elif m.group(3):
            names = [m.group(3)]
        out.setdefault(attr, []).extend(names)
    return out


def _trip_count(cond: Computation) -> int:
    """Largest integer constant in the condition computation (scan emits
    ``compare(iter, constant(N), LT)``); 1 if none found."""
    best = 1
    for op in cond.ops:
        for c in _TRIP_CONST.findall(op.line):
            best = max(best, int(c))
    return best


def _dot_flops(op: Op, comp: Computation) -> float:
    info = _shape_info(op.type_str)
    if not info:
        return 0.0
    dtype, out_elems, _ = info[0]
    factor = 8.0 if dtype.startswith("c") else 2.0
    # contracted size from the lhs operand's shape
    cm = _CONTRACT.search(op.line)
    operand_names = _OPERANDS.findall(op.operand_str)
    contracted = 1
    if cm and operand_names:
        lhs_type = comp.shapes.get(operand_names[0], "")
        lhs_info = _shape_info(lhs_type)
        if lhs_info:
            dims_str = [d for d in cm.group(1).split(",") if d]
            lhs_dims = _SHAPE.search(lhs_type)
            if lhs_dims and lhs_dims.group(2):
                sizes = [int(x) for x in lhs_dims.group(2).split(",") if x]
                for d in dims_str:
                    di = int(d)
                    if di < len(sizes):
                        contracted *= sizes[di]
    return factor * out_elems * contracted


_VIEW_OPS = frozenset({"parameter", "constant", "tuple", "get-tuple-element",
                       "bitcast", "after-all", "add-dependency", "domain",
                       "opt-barrier", "partition-id", "replica-id",
                       # control ops: their data movement is inside the
                       # bodies (carries are aliased in place)
                       "while", "conditional", "call"})


def _fusion_operand_bytes(op: Op, comp: Computation, comps: dict) -> int:
    """Operand bytes of a fusion, with dynamic-slice/gather-consumed
    parameters counted at their *slice* size (a scan body reading one layer
    of a stacked weight must not be charged the whole stack per
    iteration)."""
    callees = _callees(op)
    called = None
    for cn in callees.get("calls", []):
        called = comps.get(cn)
    full_total = 0
    operand_names = _OPERANDS.findall(op.operand_str)
    if called is None:
        for name in operand_names:
            if name in comp.shapes:
                full_total += _total_bytes(comp.shapes[name])
        return full_total
    # param index -> bytes actually read
    param_sizes: dict = {}
    for inner in called.ops:
        if inner.kind == "parameter":
            param_sizes[inner.name] = _total_bytes(inner.type_str)
    sliced: dict = {}
    for inner in called.ops:
        if inner.kind in ("dynamic-slice", "gather", "slice"):
            srcs = _OPERANDS.findall(inner.operand_str)
            if srcs and srcs[0] in param_sizes:
                sliced[srcs[0]] = sliced.get(srcs[0], 0) \
                    + _total_bytes(inner.type_str)
    total = 0
    for pname, size in param_sizes.items():
        total += min(sliced.get(pname, size), size)
    return total


def _op_bytes(op: Op, comp: Computation, comps: Optional[dict] = None) -> int:
    if op.kind in _VIEW_OPS:
        return 0
    if op.kind == "copy":
        return 2 * _total_bytes(op.type_str)
    if op.kind == "fusion" and comps is not None:
        return _total_bytes(op.type_str) \
            + _fusion_operand_bytes(op, comp, comps)
    total = _total_bytes(op.type_str)
    for name in _OPERANDS.findall(op.operand_str):
        if name in comp.shapes:
            total += _total_bytes(comp.shapes[name])
    return total


@dataclasses.dataclass
class HloCost:
    flops: float = 0.0
    bytes: float = 0.0
    collective_bytes: float = 0.0
    collectives: dict = dataclasses.field(default_factory=dict)

    def add_collective(self, kind: str, count: float, nbytes: float):
        e = self.collectives.setdefault(kind, {"count": 0, "bytes": 0})
        e["count"] += count
        e["bytes"] += nbytes
        self.collective_bytes += nbytes


def analyze(hlo: str) -> HloCost:
    comps = parse_computations(hlo)
    # find the entry: computation named like the module entry — use the one
    # not referenced by anyone
    referenced = set()
    for comp in comps.values():
        for op in comp.ops:
            for names in _callees(op).values():
                referenced.update(names)
    entries = [c for c in comps if c not in referenced]
    cost = HloCost()
    seen_async: set = set()

    def visit(cname: str, mult: float):
        comp = comps.get(cname)
        if comp is None:
            return
        for op in comp.ops:
            kind = op.kind
            base = kind.replace("-start", "").replace("-done", "")
            if base in COLLECTIVE_KINDS:
                if kind.endswith("-done") or op.name in seen_async:
                    continue
                b = _total_bytes(op.type_str)
                if kind.endswith("-start"):
                    # start ops produce (in, out[, scratch]) tuples: halve
                    b = b // 2
                if base == "all-reduce":
                    b *= 2
                cost.add_collective(base, mult, b * mult)
            elif kind in ("dot", "convolution"):
                cost.flops += mult * _dot_flops(op, comp)
            callees = _callees(op)
            if kind == "while":
                trips = 1
                for cn in callees.get("condition", []):
                    if cn in comps:
                        trips = max(trips, _trip_count(comps[cn]))
                for bn in callees.get("body", []):
                    visit(bn, mult * trips)
                for cn in callees.get("condition", []):
                    visit(cn, mult * (trips + 1))
            elif kind in ("call", "async-start", "custom-call"):
                for group in ("calls", "to_apply"):
                    for cn in callees.get(group, []):
                        visit(cn, mult)
            # bytes: every op's operands + output (XLA convention)
            cost.bytes += mult * _op_bytes(op, comp, comps)
        return

    for e in entries:
        visit(e, 1.0)
    return cost
