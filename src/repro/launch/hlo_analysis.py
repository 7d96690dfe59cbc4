"""Post-SPMD HLO cost model with while-loop trip-count accounting.

``compiled.cost_analysis()`` counts while bodies ONCE (verified in
tests/test_roofline.py), which under-counts scanned-layer models by the
scan length.  This module re-derives the three roofline terms from the
compiled per-device HLO text:

* **flops**: every ``dot`` = 2 * prod(result dims) * prod(lhs contracting
  dims), multiplied by the computation's execution count (whiles multiply
  by their trip count, parsed from the loop condition's s32 constant;
  nested whiles cascade).  Elementwise flops are ignored (dots dominate).
* **bytes**: per top-level instruction, operand bytes (reads) + result
  bytes (write), skipping pure plumbing ops (tuple/gte/parameter/constant/
  bitcast) — a fusion-aware HBM-traffic estimate since fused subgraphs
  appear as single instructions.
* **collective bytes**: result-shape bytes per collective op (x2 for
  all-reduce: ring send+recv), with the same multipliers.

All approximations are documented in EXPERIMENTS.md §Roofline.
"""
from __future__ import annotations

import dataclasses
import math
import re
from collections import defaultdict

__all__ = ["analyze_hlo", "compiled_cost_dict", "HloCost"]


def compiled_cost_dict(compiled) -> dict:
    """``compiled.cost_analysis()`` as a dict (empty when the backend
    reports nothing)."""
    return compiled.cost_analysis() or {}

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z]\d*[a-z0-9]*)\[([0-9,]*)\]")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->\s*.*\{$")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")
_SKIP_BYTES_OPS = {
    "tuple", "get-tuple-element", "parameter", "constant", "bitcast",
    "after-all", "while", "conditional", "call",
}


def _shape_dims(shape_str: str) -> list[tuple[str, list[int]]]:
    out = []
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        dd = [int(x) for x in dims.split(",") if x] if dims else []
        out.append((dt, dd))
    return out


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _shape_dims(shape_str):
        total += math.prod(dims) * _DTYPE_BYTES[dt] if dims else _DTYPE_BYTES[dt]
    return total


def _opcode_of(rhs: str) -> tuple[str, str]:
    """(type_str, opcode) from an instruction RHS."""
    s = rhs.strip()
    if s.startswith("("):  # tuple type: find matching paren
        depth = 0
        for i, ch in enumerate(s):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    type_str = s[: i + 1]
                    rest = s[i + 1 :]
                    break
        else:
            return s, ""
    else:
        m = re.match(r"^([\w\[\],{}:*\/]+)\s+(.*)$", s)
        if not m:
            return s, ""
        type_str, rest = m.group(1), m.group(2)
    op = re.match(r"\s*([\w\-]+)\(", rest)
    return type_str, (op.group(1) if op else "")


@dataclasses.dataclass
class HloCost:
    flops: float
    bytes: float
    collective_bytes: float
    collective_by_type: dict
    dot_count: int
    while_trips: dict


def analyze_hlo(hlo_text: str) -> HloCost:
    # ---- split into computations
    comps: dict[str, list[str]] = {}
    sigs: dict[str, str] = {}
    cur = None
    entry = None
    for raw in hlo_text.splitlines():
        line = raw.rstrip()
        hdr = _COMP_HDR_RE.match(line.strip())
        if hdr and line.strip().endswith("{"):
            cur = hdr.group(1)
            comps[cur] = []
            sigs[cur] = line
            if line.strip().startswith("ENTRY"):
                entry = cur
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is not None:
            comps[cur].append(line)
    if entry is None:
        entry = max(comps, key=lambda c: len(comps[c])) if comps else None

    # ---- per-computation symbol tables + parsed instructions
    parsed: dict[str, list[tuple[str, str, str, str]]] = {}
    symtab: dict[str, dict[str, str]] = defaultdict(dict)
    for cname, lines in comps.items():
        # parameters from the signature line
        for pm in re.finditer(r"(\w[\w.\-]*):\s*([^,()]+(?:\([^)]*\))?)", sigs[cname]):
            symtab[cname][pm.group(1)] = pm.group(2)
        out = []
        for line in lines:
            m = _INSTR_RE.match(line)
            if not m:
                continue
            name, rhs = m.group(1), m.group(2)
            type_str, opcode = _opcode_of(rhs)
            symtab[cname][name] = type_str
            out.append((name, type_str, opcode, rhs))
        parsed[cname] = out

    # ---- while trip counts: max s32 constant in the condition computation
    def cond_trip(cond_name: str) -> int:
        best = 1
        for _, _, opcode, rhs in parsed.get(cond_name, []):
            if opcode == "constant":
                m = re.search(r"constant\((\d+)\)", rhs)
                if m:
                    best = max(best, int(m.group(1)))
        return best

    # ---- multipliers via DFS over the call graph
    mult: dict[str, float] = defaultdict(float)
    mult[entry] = 1.0
    order = [entry]
    seen = {entry}
    while_trips: dict[str, int] = {}
    i = 0
    while i < len(order):
        cname = order[i]
        i += 1
        m_here = mult[cname]
        for _, _, opcode, rhs in parsed.get(cname, []):
            children: list[tuple[str, float]] = []
            if opcode == "while":
                mb = re.search(r"body=%?([\w.\-]+)", rhs)
                mc = re.search(r"condition=%?([\w.\-]+)", rhs)
                trip = cond_trip(mc.group(1)) if mc else 1
                if mb:
                    while_trips[mb.group(1)] = trip
                    children.append((mb.group(1), m_here * trip))
                if mc:
                    children.append((mc.group(1), m_here * trip))
            elif opcode in ("call", "fusion", "reduce", "map", "scatter", "sort", "reduce-window", "custom-call", "conditional"):
                for mm in re.finditer(r"(?:to_apply|calls)=%?([\w.\-]+)", rhs):
                    children.append((mm.group(1), m_here))
                for mm in re.finditer(r"(?:branch_computations)=\{([^}]*)\}", rhs):
                    for b in _OPERAND_RE.findall(mm.group(1)):
                        children.append((b, m_here))
            for child, cm in children:
                if child in comps:
                    mult[child] += cm
                    if child not in seen:
                        seen.add(child)
                        order.append(child)

    # ---- classify inner computations whose IO is accounted by their caller
    # (fusion bodies, map/reduce/scatter/sort wrappers).  call/while/
    # conditional regions contain real top-level code and stay counted.
    inline_comps: set[str] = set()
    for cname, instrs in parsed.items():
        for _, _, opcode, rhs in instrs:
            if opcode in ("call", "while", "conditional"):
                continue
            for mm in re.finditer(r"(?:calls|to_apply)=%?([\w.\-]+)", rhs):
                inline_comps.add(mm.group(1))

    def _operands(rhs: str) -> list[str]:
        arg_str = rhs.split("(", 1)[1] if "(" in rhs else ""
        arg_str = arg_str.split("),", 1)[0]
        return _OPERAND_RE.findall(arg_str)

    def _fusion_read_bytes(fcomp: str, operand_shapes: list[str]) -> float:
        """Effective reads of a fused computation: a parameter consumed only
        through dynamic-slice reads just the slices, else the full operand."""
        instrs = parsed.get(fcomp, [])
        # parameter name -> operand index
        param_idx: dict[str, int] = {}
        for name, _, opcode, rhs in instrs:
            if opcode == "parameter":
                m = re.search(r"parameter\((\d+)\)", rhs)
                if m:
                    param_idx[name] = int(m.group(1))
        reads = 0.0
        for pname, idx in param_idx.items():
            if idx >= len(operand_shapes):
                continue
            full = _shape_bytes(operand_shapes[idx])
            slice_bytes = 0.0
            only_ds = True
            used = False
            for name, t, opcode, rhs in instrs:
                if opcode == "parameter":
                    continue
                ops = _OPERAND_RE.findall(rhs)
                if pname in ops:
                    used = True
                    if opcode == "dynamic-slice" and ops and ops[0] == pname:
                        slice_bytes += _shape_bytes(t)
                    elif opcode == "dynamic-update-slice" and ops and ops[0] == pname:
                        upd = ops[1] if len(ops) > 1 else None
                        # in-place: reads/writes only the update extent
                        slice_bytes += 0.0
                    else:
                        only_ds = False
            if not used:
                continue
            reads += slice_bytes if only_ds else full
        return reads

    flops = 0.0
    bytes_total = 0.0
    coll_bytes = 0.0
    coll_by_type: dict[str, float] = defaultdict(float)
    dot_count = 0

    for cname, instrs in parsed.items():
        m_here = mult.get(cname, 0.0)
        if m_here == 0.0 or cname in inline_comps:
            continue
        tab = symtab[cname]
        for name, type_str, opcode, rhs in instrs:
            if opcode == "dot":
                ops = _operands(rhs)
                lhs_shape = tab.get(ops[0], "") if ops else ""
                lc = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", rhs)
                contract = 1
                if lc and lhs_shape:
                    dims = _shape_dims(lhs_shape)
                    if dims:
                        _, dd = dims[0]
                        for idx in (int(x) for x in lc.group(1).split(",") if x):
                            if idx < len(dd):
                                contract *= dd[idx]
                result_elems = 0
                for dt, dd in _shape_dims(type_str):
                    result_elems += math.prod(dd) if dd else 1
                flops += m_here * 2.0 * result_elems * contract
                dot_count += 1
            for ck in _COLLECTIVES:
                if opcode == ck or opcode.startswith(ck + "-"):
                    b = _shape_bytes(type_str)
                    factor = 2.0 if ck == "all-reduce" else 1.0
                    coll_bytes += m_here * b * factor
                    coll_by_type[ck] += m_here * b * factor
                    break
            if opcode in _SKIP_BYTES_OPS or not opcode:
                continue
            b_out = _shape_bytes(type_str)
            if opcode == "dynamic-slice":
                bytes_total += m_here * 2 * b_out
                continue
            if opcode == "dynamic-update-slice":
                ops = _operands(rhs)
                upd = tab.get(ops[1], "") if len(ops) > 1 else ""
                bytes_total += m_here * 2 * _shape_bytes(upd)
                continue
            if opcode == "fusion":
                mcall = re.search(r"calls=%?([\w.\-]+)", rhs)
                if mcall:
                    op_shapes = [tab.get(o, "") for o in _operands(rhs)]
                    bytes_total += m_here * (b_out + _fusion_read_bytes(mcall.group(1), op_shapes))
                    continue
            b_in = 0
            for opn in _operands(rhs):
                if opn in tab:
                    b_in += _shape_bytes(tab[opn])
            bytes_total += m_here * (b_out + b_in)

    return HloCost(
        flops=flops,
        bytes=bytes_total,
        collective_bytes=coll_bytes,
        collective_by_type=dict(coll_by_type),
        dot_count=dot_count,
        while_trips=while_trips,
    )
