"""Trip-count-aware HLO cost analysis.

``compiled.cost_analysis()`` counts a ``while`` body ONCE, so any model using
``lax.scan`` over layers (i.e., every serious JAX LLM) is undercounted by the
layer count. This module parses the post-SPMD HLO text and computes:

* **flops** — dot/conv FLOPs (2·M·N·K·batch), multiplied by the execution
  count of the enclosing computation (while bodies × trip count, nested scans
  multiply). Elementwise FLOPs are excluded (<2% for matmul-dominated LLM
  steps) — noted in EXPERIMENTS.md.
* **bytes** — naive HBM traffic: Σ over executed ops of (operand + result
  bytes), fusions counted as single ops (their internals live in registers),
  bookkeeping ops (tuple/gte/parameter/constant/bitcast) skipped.
* **collective_bytes** — result-shape bytes of all-gather / all-reduce /
  reduce-scatter / all-to-all / collective-permute × execution count.

Operand shapes are resolved through a per-computation symbol table (compiled
HLO does not inline operand shapes). Validated against XLA's own
cost_analysis on scan-free programs (tests/test_hlo_cost.py).
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1, "f8e4m3fn": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
    "pred": 1, "c64": 8, "c128": 16,
}
_SHAPE_RE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")
_COMP_HDR_RE = re.compile(r"^(ENTRY\s+)?%?([\w\.\-_]+)\s*\(.*\)\s*->\s*.+\{\s*$")
_OP_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-_]+)\s*=\s*(\(?[^=]*?)\s*([a-z][a-z0-9\-]*)\((.*)$")
_BODY_RE = re.compile(r"body=%?([\w\.\-_]+)")
_COND_RE = re.compile(r"condition=%?([\w\.\-_]+)")
_CALLS_RE = re.compile(r"calls=%?([\w\.\-_]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_CONST_RE = re.compile(r"constant\((\d+)\)")
_OPERAND_RE = re.compile(r"%([\w\.\-_]+)")

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")

_SKIP_BYTES_OPS = {
    "tuple", "get-tuple-element", "parameter", "constant", "bitcast", "bitcast-convert",
    "after-all", "partition-id", "replica-id", "opt-barrier", "copy", "copy-start",
    "copy-done", "iota",
    # control flow: the body computations carry the traffic, not the op itself
    "while", "conditional",
}


def _dims(s: str) -> List[int]:
    return [int(x) for x in s.split(",") if x] if s else []


def _shape_elems(m) -> int:
    n = 1
    for d in _dims(m.group(2)):
        n *= d
    return n


def _shape_bytes(m) -> int:
    return _shape_elems(m) * _DTYPE_BYTES[m.group(1)]


_COMMENT_RE = re.compile(r"/\*.*?\*/")


def _strip_meta(line: str) -> str:
    line = _COMMENT_RE.sub("", line)
    i = line.find("metadata=")
    return line[:i] if i >= 0 else line


@dataclasses.dataclass
class Op:
    name: str
    opcode: str
    result_part: str  # text of result shape(s)
    args_part: str  # text after the opening paren (operands + attrs), metadata-stripped

    def result_bytes(self) -> int:
        return sum(_shape_bytes(m) for m in _SHAPE_RE.finditer(self.result_part))

    def result_elems(self) -> int:
        ms = list(_SHAPE_RE.finditer(self.result_part))
        return _shape_elems(ms[0]) if ms else 0


@dataclasses.dataclass
class Computation:
    name: str
    is_entry: bool
    ops: List[Op] = dataclasses.field(default_factory=list)
    shapes: Dict[str, str] = dataclasses.field(default_factory=dict)  # op name -> result_part
    int_constants: List[int] = dataclasses.field(default_factory=list)

    def operand_names(self, op: Op) -> List[str]:
        # operands live before the first top-level ')'; attribute comp refs
        # (body=/calls=) come after — a close enough split for cost purposes.
        cut = op.args_part.split(")")[0]
        return _OPERAND_RE.findall(cut)

    def operand_bytes(self, op: Op) -> int:
        total = 0
        for name in self.operand_names(op):
            part = self.shapes.get(name)
            if part:
                total += sum(_shape_bytes(m) for m in _SHAPE_RE.finditer(part))
        return total

    def operand_shape_dims(self, op: Op, index: int) -> List[int]:
        names = self.operand_names(op)
        if index >= len(names):
            return []
        part = self.shapes.get(names[index], "")
        ms = list(_SHAPE_RE.finditer(part))
        return _dims(ms[0].group(2)) if ms else []


def parse_hlo(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    current: Optional[Computation] = None
    for raw in text.splitlines():
        line = raw.rstrip()
        stripped = line.strip()
        if stripped.endswith("{") and "->" in stripped:
            m = _COMP_HDR_RE.match(stripped)
            if m:
                current = Computation(m.group(2), is_entry=bool(m.group(1)))
                comps[current.name] = current
                continue
        if stripped == "}":
            current = None
            continue
        if current is None:
            continue
        m = _OP_RE.match(_strip_meta(line))
        if m:
            op = Op(m.group(1), m.group(3), m.group(2), m.group(4))
            current.ops.append(op)
            current.shapes[op.name] = op.result_part
            if op.opcode == "constant":
                cm = re.match(r"(\d+)\)", op.args_part)
                if cm:
                    current.int_constants.append(int(cm.group(1)))
    return comps


def _trip_count(comps: Dict[str, Computation], cond: Computation) -> int:
    """lax.scan conditions compare the induction var LT a constant. The
    constant is materialized as a `constant` op in the condition region (the
    compare itself may be wrapped in a fusion)."""
    consts = list(cond.int_constants)
    for op in cond.ops:
        if op.opcode == "fusion":
            m = _CALLS_RE.search(op.args_part)
            if m and m.group(1) in comps:
                consts.extend(comps[m.group(1)].int_constants)
    return max(consts) if consts else 1


def _fusion_slice_bytes(comps: Dict[str, Computation], op: Op) -> Optional[int]:
    """Dynamic-slice / dynamic-update-slice fusions touch only the SLICE, not
    the whole stacked operand (scan weights are (L, ...) but each iteration
    reads one layer). Counting full operands would overcount by ×L."""
    m = _CALLS_RE.search(op.args_part)
    if not m or m.group(1) not in comps:
        return None
    inner = comps[m.group(1)]
    total = 0
    found = False
    for iop in inner.ops:
        if iop.opcode == "dynamic-slice":
            total += 2 * iop.result_bytes()  # read slice + write result
            found = True
        elif iop.opcode == "dynamic-update-slice":
            names = inner.operand_names(iop)
            upd = inner.shapes.get(names[1], "") if len(names) > 1 else ""
            ub = sum(_shape_bytes(sm) for sm in _SHAPE_RE.finditer(upd))
            total += 2 * ub  # read update + write slice in place
            found = True
    return total if found else None


def _dot_flops(comp: Computation, op: Op) -> int:
    lhs = comp.operand_shape_dims(op, 0)
    cm = _CONTRACT_RE.search(op.args_part)
    contract = _dims(cm.group(1)) if cm else []
    k = 1
    for d in contract:
        if d < len(lhs):
            k *= lhs[d]
    return 2 * op.result_elems() * k


def normalize_cost_analysis(cost) -> Dict[str, float]:
    """``Compiled.cost_analysis()`` returns a dict, or ``None`` where the
    backend has no analysis — always yield a dict."""
    return cost or {}


@dataclasses.dataclass
class HloCost:
    flops: float
    bytes: float
    collective_bytes: Dict[str, float]
    collective_total: float
    collective_count: float
    while_trips: Dict[str, int]


def analyze_hlo(text: str) -> HloCost:
    comps = parse_hlo(text)
    entry = next((n for n, c in comps.items() if c.is_entry), None)
    if entry is None:
        entry = next((n for n in comps if "main" in n), next(iter(comps)))

    fusion_comps = set()
    for comp in comps.values():
        for op in comp.ops:
            if op.opcode == "fusion":
                m = _CALLS_RE.search(op.args_part)
                if m:
                    fusion_comps.add(m.group(1))

    exec_count: Dict[str, float] = defaultdict(float)
    while_trips: Dict[str, int] = {}

    def visit(name: str, count: float, depth=0):
        if name not in comps or count <= 0 or depth > 64:
            return
        exec_count[name] += count
        for op in comps[name].ops:
            if op.opcode == "while":
                b = _BODY_RE.search(op.args_part)
                c = _COND_RE.search(op.args_part)
                trips = _trip_count(comps, comps[c.group(1)]) if c and c.group(1) in comps else 1
                if b:
                    while_trips[b.group(1)] = trips
                    visit(b.group(1), count * trips, depth + 1)
                if c:
                    visit(c.group(1), count * trips, depth + 1)
            elif op.opcode == "fusion":
                m = _CALLS_RE.search(op.args_part)
                if m:
                    visit(m.group(1), count, depth + 1)
            elif op.opcode == "call":
                m = re.search(r"to_apply=%?([\w\.\-_]+)", op.args_part)
                if m:
                    visit(m.group(1), count, depth + 1)

    visit(entry, 1.0)

    flops = 0.0
    nbytes = 0.0
    coll = {k: 0.0 for k in COLLECTIVE_OPS}
    coll_count = 0.0
    for name, comp in comps.items():
        count = exec_count.get(name, 0.0)
        if count <= 0:
            continue
        in_fusion = name in fusion_comps
        for op in comp.ops:
            if op.opcode in ("dot", "convolution"):
                flops += count * _dot_flops(comp, op)
            if in_fusion:
                continue
            base = op.opcode.replace("-start", "").replace("-done", "")
            if base in COLLECTIVE_OPS:
                if not op.opcode.endswith("-done"):
                    b = op.result_bytes()
                    # XLA:CPU promotes bf16 reductions/dots to f32 (TPU does
                    # both natively in bf16) — count promoted collectives at
                    # their true width: 'promoted' reducers, or operands that
                    # are just convert(bf16->f32) fusions.
                    if "promoted" in op.args_part:
                        b //= 2
                    elif "f32[" in op.result_part:
                        names = comp.operand_names(op)
                        if names and "convert" in names[0]:
                            b //= 2
                    coll[base] += count * b
                    coll_count += count
                continue
            if op.opcode in _SKIP_BYTES_OPS or op.opcode.endswith("-done"):
                continue
            if op.opcode == "fusion":
                sliced = _fusion_slice_bytes(comps, op)
                if sliced is not None:
                    nbytes += count * sliced
                    continue
            if op.opcode in ("dynamic-slice",):
                nbytes += count * 2 * op.result_bytes()
                continue
            nbytes += count * (op.result_bytes() + comp.operand_bytes(op))

    return HloCost(
        flops=flops,
        bytes=nbytes,
        collective_bytes=coll,
        collective_total=sum(coll.values()),
        collective_count=coll_count,
        while_trips=while_trips,
    )
