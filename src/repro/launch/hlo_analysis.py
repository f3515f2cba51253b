"""Post-compile HLO analysis: FLOPs, HBM-byte and collective-byte accounting
with **while-loop trip-count multipliers**.

``compiled.cost_analysis()`` visits each while body ONCE (verified
empirically: a 10-iteration scan of matmuls reports 1 matmul of FLOPs), so a
scanned-by-depth model would be under-counted by its layer count.  This
module re-derives the three roofline terms from ``compiled.as_text()``:

* computations are parsed into ops (name, opcode, output shape, operands),
* every ``while`` op contributes ``trip_count x`` to its body/condition
  (trip count recovered from the loop-condition constant; jax scans lower to
  canonical 0..N loops),
* ``fusion``/``call``/``to_apply``/branch computations inherit their caller's
  multiplier,
* FLOPs are counted from ``dot`` ops (2*M*N*K from the dot dimension
  numbers), which dominate for transformer workloads,
* bytes = sum over *top-level* ops of (operand + output bytes) — the text is
  post-fusion, so a fusion counts once with its true inputs/outputs,
* collective bytes are summed per opcode over {all-reduce, all-gather,
  reduce-scatter, all-to-all, collective-permute} using operand sizes; the
  TPU's async pairs count once, at ``<collective>-start``.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# type group is lazy-any: tuple types may contain /*index=N*/ comments
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(.*?)\s([\w\-]+)\((.*)$")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\((.*?)\)\s*->")
_CALL_RE = re.compile(
    r"(?:calls=|condition=|body=|to_apply=)%?([\w\.\-]+)")
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def _async_payload_bytes(type_str: str, operand_bytes: int) -> int:
    """Payload of a ``<collective>-start``: its output tuple repeats the
    operands (collective-permute, all-gather) ahead of the results and ends
    in ``u32[]`` context scalars, which carry no data."""
    out = sum(shape_bytes(f"{dt}[{dims}]")
              for dt, dims in _SHAPE_RE.findall(type_str)
              if (dt, dims) != ("u32", ""))
    return max(operand_bytes, out - operand_bytes)


def shape_bytes(type_str: str) -> int:
    """Bytes of a shape string like 'bf16[2,4]{1,0}' or '(f32[2], s32[])'."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    # scalar like 'f32[]' — regex [\d,]* matches empty dims
    return total


@dataclasses.dataclass
class HloOp:
    name: str
    opcode: str
    out_type: str
    rest: str            # text after the opening paren of operands
    operands: List[str]


@dataclasses.dataclass
class Computation:
    name: str
    ops: Dict[str, HloOp]
    params: Dict[str, str]        # param name -> type string
    order: List[str]


def parse_hlo(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for line in text.splitlines():
        stripped = line.strip()
        if not stripped:
            continue
        if stripped.endswith("{") and ("->" in stripped):
            m = _COMP_RE.match(stripped)
            if m:
                name = m.group(1)
                params: Dict[str, str] = {}
                for p in m.group(2).split(","):
                    p = p.strip()
                    if ":" in p:
                        pname, ptype = p.split(":", 1)
                        params[pname.strip().lstrip("%")] = ptype.strip()
                cur = Computation(name, {}, params, [])
                comps[name] = cur
                continue
        if stripped == "}":
            continue
        if cur is None:
            continue
        m = _OP_RE.match(line)
        if m:
            name, out_type, opcode, rest = m.groups()
            # operand section: up to matching close paren (approximate: split
            # at '), ' attr boundary)
            op_section = rest.split("), ")[0]
            operands = _OPERAND_RE.findall(op_section)
            op = HloOp(name, opcode, out_type, rest, operands)
            cur.ops[name] = op
            cur.order.append(name)
    return comps


def _operand_type(comp: Computation, comps, opname: str) -> str:
    if opname in comp.ops:
        return comp.ops[opname].out_type
    if opname in comp.params:
        return comp.params[opname]
    return ""


def _trip_count(cond: Computation, comps) -> int:
    """Recover N from a canonical 0..N while condition (best effort)."""
    consts: List[int] = []

    def scan_comp(c: Computation, depth=0):
        if depth > 3:
            return
        for op in c.ops.values():
            if op.opcode == "constant":
                m = re.search(r"constant\((\d+)\)", "constant(" + op.rest)
                if m:
                    consts.append(int(m.group(1)))
            for callee in _CALL_RE.findall(op.rest):
                if callee in comps:
                    scan_comp(comps[callee], depth + 1)

    scan_comp(cond)
    return max(consts) if consts else 1


def _multipliers(comps: Dict[str, Computation], entry: str) -> Dict[str, float]:
    mult: Dict[str, float] = defaultdict(float)
    mult[entry] = 1.0
    # topological-ish: repeatedly propagate (call graph is a DAG; few passes)
    work = [entry]
    seen_edges = set()
    while work:
        cname = work.pop()
        comp = comps.get(cname)
        if comp is None:
            continue
        m = mult[cname]
        for op in comp.ops.values():
            callees = _CALL_RE.findall(op.rest)
            branches = _BRANCH_RE.findall(op.rest)
            for b in branches:
                callees += _OPERAND_RE.findall(b)
            if op.opcode == "while":
                cm = re.search(r"condition=%?([\w\.\-]+)", op.rest)
                bm = re.search(r"body=%?([\w\.\-]+)", op.rest)
                trip = 1
                if cm and cm.group(1) in comps:
                    trip = _trip_count(comps[cm.group(1)], comps)
                for target, k in ((bm, trip), (cm, trip + 1)):
                    if target and target.group(1) in comps:
                        t = target.group(1)
                        edge = (cname, t, op.name)
                        if edge not in seen_edges:
                            seen_edges.add(edge)
                            mult[t] += m * k
                            work.append(t)
            else:
                for t in callees:
                    if t in comps:
                        edge = (cname, t, op.name)
                        if edge not in seen_edges:
                            seen_edges.add(edge)
                            mult[t] += m
                            work.append(t)
    return dict(mult)


def _dot_flops(comp: Computation, comps, op: HloOp) -> float:
    out_elems = 1
    m = _SHAPE_RE.search(op.out_type)
    if m:
        for d in m.group(2).split(","):
            if d:
                out_elems *= int(d)
    # contracted dims from lhs
    lhs_type = _operand_type(comp, comps, op.operands[0]) if op.operands else ""
    mshape = _SHAPE_RE.search(lhs_type)
    cdims = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.rest)
    k = 1
    if mshape and cdims:
        dims = [int(d) for d in mshape.group(2).split(",") if d]
        for ci in cdims.group(1).split(","):
            if ci and int(ci) < len(dims):
                k *= dims[int(ci)]
    return 2.0 * out_elems * k


_SKIP_BYTES_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "iota",
}


@dataclasses.dataclass
class HloStats:
    flops: float = 0.0
    bytes_accessed: float = 0.0   # every op's I/O x trip count (upper bound)
    bytes_dot: float = 0.0        # dot operand/output traffic x trip count
    bytes_entry: float = 0.0      # entry-level op I/O (optimizer, copies)
    collective_bytes: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {c: 0.0 for c in COLLECTIVES})
    collective_count: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {c: 0.0 for c in COLLECTIVES})
    dot_count: float = 0.0
    while_trips: List[int] = dataclasses.field(default_factory=list)

    @property
    def total_collective_bytes(self) -> float:
        return sum(self.collective_bytes.values())

    @property
    def bytes_hbm_est(self) -> float:
        """HBM-traffic estimate: dot streams (weights/activations feeding the
        MXU must come from HBM each visit — remat recompute included via trip
        multipliers) + entry-level elementwise passes (optimizer, copies).
        ``bytes_accessed`` is kept as the pessimistic bound: it also charges
        every intra-loop elementwise op, which on TPU stays fused in VMEM."""
        return self.bytes_dot + self.bytes_entry


def analyze(text: str) -> HloStats:
    comps = parse_hlo(text)
    entry = None
    for line in text.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_RE.match(line.strip())
            if m:
                entry = m.group(1)
                break
    if entry is None:
        # fall back: last computation
        entry = list(comps)[-1] if comps else ""
    mult = _multipliers(comps, entry)
    stats = HloStats()
    for cname, comp in comps.items():
        m = mult.get(cname, 0.0)
        if m == 0.0:
            continue
        is_entry = cname == entry
        for op in comp.ops.values():
            if op.opcode == "dot":
                stats.flops += m * _dot_flops(comp, comps, op)
                stats.dot_count += m
                stats.bytes_dot += m * (
                    shape_bytes(op.out_type) + sum(
                        shape_bytes(_operand_type(comp, comps, o))
                        for o in op.operands))
            if op.opcode == "while":
                cm = re.search(r"condition=%?([\w\.\-]+)", op.rest)
                if cm and cm.group(1) in comps:
                    stats.while_trips.append(_trip_count(comps[cm.group(1)], comps))
            if op.opcode in _SKIP_BYTES_OPS:
                continue
            ob = shape_bytes(op.out_type)
            ib = sum(
                shape_bytes(_operand_type(comp, comps, o)) for o in op.operands
            )
            # fusions already fold their internals; count I/O once
            stats.bytes_accessed += m * (ob + ib)
            if is_entry:
                stats.bytes_entry += m * (ob + ib)
            if op.opcode in COLLECTIVES:
                stats.collective_bytes[op.opcode] += m * max(ib, ob)
                stats.collective_count[op.opcode] += m
            elif op.opcode.endswith("-start") and op.opcode[:-6] in COLLECTIVES:
                coll = op.opcode[:-6]
                stats.collective_bytes[coll] += m * _async_payload_bytes(
                    op.out_type, ib)
                stats.collective_count[coll] += m
    return stats
