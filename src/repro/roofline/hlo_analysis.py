"""Call-graph HLO analysis with while-trip-count multiplication.

XLA's ``HloCostAnalysis`` (what ``compiled.cost_analysis()`` reports) counts
every instruction ONCE — a ``lax.scan`` over 126 layers reports one layer's
FLOPs.  For the roofline we need trip-weighted totals, so this module parses
the optimized HLO text into its computation call graph and accumulates

  * dot FLOPs                (2 * prod(result_dims) * contract_size)
  * bytes accessed           (operand + result sizes per instruction,
                              HloCostAnalysis-style: fusion boundaries only)
  * collective wire bytes    (ring factors per op, as roofline/analysis.py)

multiplying every computation's totals by the product of enclosing while-loop
trip counts (``backend_config={"known_trip_count":{"n":...}}`` on the while
instruction, falling back to the loop condition's comparison constant).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

from repro.compat import cost_analysis as normalized_cost_analysis
from repro.roofline.analysis import _DTYPE_BYTES, _wire_factor

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(.*)$")
_OPCODE_RE = re.compile(r"^((?:\([^=]*\)|[a-z0-9\[\],\{\} ])*?)"
                        r"([a-z][a-z0-9\-]*)\(")
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")
_TRIP_RE = re.compile(r"\"known_trip_count\":\{\"n\":\"(\d+)\"\}")
_CONST_S32_RE = re.compile(r"=\s*s32\[\]\s*constant\((\d+)\)")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_GROUPS_SHAPE_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{(.*?)\}")

# ops HloCostAnalysis treats as free (no bytes); while/conditional bodies do
# the work, the wrapper op moves nothing itself
_FREE_OPS = {"tuple", "get-tuple-element", "parameter", "bitcast",
             "constant", "after-all", "partition-id", "replica-id",
             "while", "conditional"}
_COMMENT_RE = re.compile(r"/\*.*?\*/")


def _shape_bytes_of(seg: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(seg):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _first_dims(seg: str) -> List[int]:
    m = _SHAPE_RE.search(seg)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",")] if m.group(2) else []


def _group_size(line: str) -> int:
    m = _GROUPS_SHAPE_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        first = m.group(1).split("}", 1)[0].replace("{", "")
        return max(len([x for x in first.split(",") if x.strip()]), 1)
    return 1


@dataclasses.dataclass
class Instr:
    name: str
    opcode: str
    result_seg: str  # text between '=' and the opcode (shapes of the result)
    body: str  # full text after '='
    operands: List[str]
    attrs: str  # text after the operand parens


@dataclasses.dataclass
class Comp:
    instrs: List[Instr] = dataclasses.field(default_factory=list)
    shapes: Dict[str, int] = dataclasses.field(default_factory=dict)
    dims: Dict[str, List[int]] = dataclasses.field(default_factory=dict)
    max_const: int = 1


def _parse_instr(name: str, body: str) -> Optional[Instr]:
    body = _COMMENT_RE.sub("", body)
    m = _OPCODE_RE.match(body)
    if not m:
        return None
    result_seg, opcode = m.group(1), m.group(2)
    rest = body[m.end():]
    # operands: %refs up to the closing paren of the op (operands contain no
    # parens, so cut at the first ')')
    op_seg, _, attrs = rest.partition(")")
    operands = _OPERAND_RE.findall(op_seg)
    return Instr(name, opcode, result_seg, body, operands, attrs)


def parse(hlo: str) -> Tuple[Dict[str, Comp], Optional[str]]:
    comps: Dict[str, Comp] = {}
    cur: Optional[Comp] = None
    entry = None
    for raw in hlo.splitlines():
        line = raw.rstrip()
        s = line.strip()
        if s.endswith("{") and ("->" in s) and ("=" not in s.split("(")[0]):
            name = s.split("(")[0].replace("ENTRY", "").strip().lstrip("%")
            cur = comps.setdefault(name, Comp())
            if s.startswith("ENTRY"):
                entry = name
            continue
        if s == "}":
            cur = None
            continue
        if cur is None:
            continue
        im = _INSTR_RE.match(line)
        if not im:
            continue
        ins = _parse_instr(im.group(1), im.group(2))
        if ins is None:
            continue
        cur.instrs.append(ins)
        cur.shapes[ins.name] = _shape_bytes_of(ins.result_seg)
        cur.dims[ins.name] = _first_dims(ins.result_seg)
        cm = _CONST_S32_RE.search(ins.body)
        if cm:
            cur.max_const = max(cur.max_const, int(cm.group(1)))
    return comps, entry


_ZERO = ("flops", "bytes", "wire_bytes",
         *(f"n_{c}" for c in _COLLECTIVES))


def analyze(hlo: str) -> Dict[str, float]:
    comps, entry = parse(hlo)
    memo: Dict[Tuple[str, bool], Dict[str, float]] = {}

    def _fusion_inplace_correction(ins: Instr, comp: Comp, b: float) -> float:
        """A fusion whose root is a dynamic-update-slice of a same-shape
        operand is executed in place on TPU (buffer aliasing): the full
        buffer is neither read nor written, only the updated slice is.
        Replace the (2 x full-buffer) boundary bytes with (2 x slice)."""
        m = re.search(r"calls=%?([\w\.\-]+)", ins.attrs)
        callee = comps.get(m.group(1)) if m else None
        if callee is None:
            return b
        full_dims = comp.dims.get(ins.name, [])
        full_bytes = comp.shapes.get(ins.name, 0)
        if not full_dims:
            return b
        for ci in callee.instrs:
            if ci.opcode == "dynamic-update-slice" and \
                    callee.dims.get(ci.name, []) == full_dims:
                upd = (callee.shapes.get(ci.operands[1], 0)
                       if len(ci.operands) > 1 else 0)
                # drop result write + the aliased same-dims operand read
                aliased_in = max(
                    (comp.shapes.get(o, 0) for o in ins.operands
                     if comp.dims.get(o, []) == full_dims), default=0)
                corrected = b - full_bytes - aliased_in + 2 * upd
                return max(corrected, 0.0)
        return b

    def local_and_edges(comp: Comp):
        acc = {k: 0.0 for k in _ZERO}
        edges: List[Tuple[str, float, bool]] = []  # (callee, mult, is_fusion)
        for ins in comp.instrs:
            if ins.opcode not in _FREE_OPS:
                # slice-like ops touch only the slice, not the full buffer
                # (XLA updates in place); HloCostAnalysis does the same.
                if ins.opcode == "dynamic-update-slice":
                    upd = (comp.shapes.get(ins.operands[1], 0)
                           if len(ins.operands) > 1 else 0)
                    b = 2 * upd
                elif ins.opcode == "scatter":
                    upd = (comp.shapes.get(ins.operands[2], 0)
                           if len(ins.operands) > 2 else 0)
                    idx = (comp.shapes.get(ins.operands[1], 0)
                           if len(ins.operands) > 1 else 0)
                    b = 2 * upd + idx
                elif ins.opcode in ("dynamic-slice", "gather"):
                    b = 2 * comp.shapes.get(ins.name, 0)
                    if ins.opcode == "gather" and len(ins.operands) > 1:
                        b += comp.shapes.get(ins.operands[1], 0)
                else:
                    b = comp.shapes.get(ins.name, 0)
                    for o in ins.operands:
                        b += comp.shapes.get(o, 0)
                    if ins.opcode == "fusion":
                        b = _fusion_inplace_correction(ins, comp, b)
                acc["bytes"] += b
            if ins.opcode == "dot":
                out = 1
                for d in comp.dims.get(ins.name, []):
                    out *= d
                lhs_dims = comp.dims.get(ins.operands[0], []) \
                    if ins.operands else []
                m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}",
                              ins.attrs)
                contract = 1
                if m and m.group(1):
                    for i in m.group(1).split(","):
                        ii = int(i)
                        if ii < len(lhs_dims):
                            contract *= lhs_dims[ii]
                acc["flops"] += 2.0 * out * contract
            elif ins.opcode.rstrip("-start").rstrip("-done") in _COLLECTIVES \
                    or any(ins.opcode == c or ins.opcode == c + "-start"
                           for c in _COLLECTIVES):
                base = next(c for c in _COLLECTIVES
                            if ins.opcode.startswith(c))
                if not ins.opcode.endswith("-done"):
                    n = _group_size(ins.attrs)
                    b = comp.shapes.get(ins.name, 0)
                    acc["wire_bytes"] += b * _wire_factor(base, n)
                    acc[f"n_{base}"] += 1
            if ins.opcode == "while":
                mt = _TRIP_RE.search(ins.attrs)
                mb = re.search(r"body=%?([\w\.\-]+)", ins.attrs)
                mc = re.search(r"condition=%?([\w\.\-]+)", ins.attrs)
                trip = float(mt.group(1)) if mt else (
                    float(comps[mc.group(1)].max_const)
                    if mc and mc.group(1) in comps else 1.0)
                if mb:
                    edges.append((mb.group(1), trip, False))
                if mc:
                    edges.append((mc.group(1), trip, False))
            elif ins.opcode in ("fusion", "call", "custom-call"):
                m = re.search(r"calls=%?([\w\.\-]+)", ins.attrs)
                if m:
                    edges.append((m.group(1), 1.0, True))
            elif ins.opcode == "conditional":
                m = re.search(r"branch_computations=\{([^}]*)\}", ins.attrs)
                if m:
                    for callee in _OPERAND_RE.findall(m.group(1)):
                        edges.append((callee, 1.0, True))
        return acc, edges

    def total(name: str, inside_fusion: bool, depth=0) -> Dict[str, float]:
        key = (name, inside_fusion)
        if key in memo:
            return memo[key]
        zero = {k: 0.0 for k in _ZERO}
        comp = comps.get(name)
        if comp is None or depth > 64:
            return zero
        memo[key] = zero  # cycle guard
        acc, edges = local_and_edges(comp)
        if inside_fusion:
            acc["bytes"] = 0.0  # fusion internals are free for bytes
        for callee, mult, is_fusion in edges:
            sub = total(callee, inside_fusion or is_fusion, depth + 1)
            for k in acc:
                acc[k] += mult * sub[k]
        memo[key] = acc
        return acc

    if entry is None:
        return {k: 0.0 for k in _ZERO}
    return total(entry, False)


def analyze_compiled(compiled) -> Dict[str, float]:
    """Trip-weighted totals for a jit-compiled executable, plus the raw
    (unweighted) XLA numbers under ``raw_flops`` / ``raw_bytes_accessed``.

    The raw numbers come through ``compat.cost_analysis`` (one flat dict).
    """
    out = analyze(compiled.as_text())
    raw = normalized_cost_analysis(compiled)
    out["raw_flops"] = float(raw.get("flops", 0.0))
    out["raw_bytes_accessed"] = float(raw.get("bytes accessed", 0.0))
    return out
