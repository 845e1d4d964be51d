"""Compiled-artifact lint: collective / aliasing audits over optimized HLO.

This is the reusable home of what ``launch/dryrun.py`` used to do with
private regexes: scan a compiled executable's HLO text for oversized
collectives (the decode-step guard against involuntary rematerialization
of a sharded table — the gather shows up as a table-sized all-gather),
check that donated buffers were actually aliased (``input_output_alias``
annotations on the module header), and find ops that copy a whole
stacked page pool or work on one layer's slice of it
(:func:`pool_copy_findings`).  Pure string parsing, no jax import — the
CI lint lane can audit saved HLO dumps without an accelerator stack.

Findings reuse :class:`repro.analysis.rules.Finding`; ``path`` carries
the caller's label (e.g. ``decode_chunk[fp]``) and ``line`` the HLO text
line of the offending instruction.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

from repro.analysis.rules import Finding

# result-shape element sizes (bytes); mirrors roofline/analysis.py without
# importing it (that module is jax-adjacent, this one must stay stdlib-only)
DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")


@dataclasses.dataclass(frozen=True)
class Collective:
    """One collective instruction: op kind, result bytes, HLO text line.

    ``bytes`` is the largest single shape in the result segment — tuple
    results of ``-start`` ops repeat the aliased operand, so a sum would
    double-count the payload."""

    op: str
    bytes: int
    line: int
    text: str


def _result_bytes(seg: str) -> int:
    """Largest shape in a result segment, in bytes."""
    biggest = 0
    for dt, dims in _SHAPE_RE.findall(seg):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        biggest = max(biggest, n * DTYPE_BYTES[dt])
    return biggest


def _collective_call_re(op: str) -> "re.Pattern":
    # HLO reads `%all-gather.5 = bf16[...]{...} all-gather(...)` — the op
    # name on the left also contains the op string, so the result shapes
    # are what sits between the `=` and the *call* (token followed by `(`).
    return re.compile(r"=\s*(.*?)\s*" + re.escape(op)
                      + r"(?:-start|-done)?\(", re.S)


def find_collectives(hlo: str,
                     ops: Sequence[str] = COLLECTIVE_OPS) -> List[Collective]:
    """Every collective call in the HLO with its result-shape bytes."""
    pats = [(op, _collective_call_re(op)) for op in ops]
    out: List[Collective] = []
    for lineno, line in enumerate(hlo.splitlines(), 1):
        for op, pat in pats:
            m = pat.search(line)
            if m:
                out.append(Collective(op, _result_bytes(m.group(1)), lineno,
                                      line.strip()))
    return out


def largest_allgather_bytes(hlo: str) -> int:
    """Max result size of any all-gather in the optimized HLO — the
    decode-step guard ``launch/dryrun.py`` records as
    ``largest_allgather_bytes``."""
    return largest_collective_bytes(hlo, "all-gather")


def largest_collective_bytes(hlo: str, op: str = "all-gather") -> int:
    return max((c.bytes for c in find_collectives(hlo, (op,))), default=0)


# module-header annotation: input_output_alias={ {0}: (2, {}, may-alias) }
_ALIAS_ENTRY_RE = re.compile(r"\{([0-9,\s]*)\}:\s*\((\d+)")


def input_output_aliases(hlo: str) -> List[Tuple[Tuple[int, ...], int]]:
    """Parsed ``input_output_alias`` entries:
    ``(output_tuple_index_path, parameter_number)`` per aliased buffer.
    Empty when the module carries no donation/aliasing."""
    # the annotation nests braces ({output index}: (...)), so take
    # everything from `input_output_alias={` to the matching close brace
    start = hlo.find("input_output_alias={")
    if start < 0:
        return []
    i = start + len("input_output_alias={")
    depth = 1
    j = i
    while j < len(hlo) and depth:
        if hlo[j] == "{":
            depth += 1
        elif hlo[j] == "}":
            depth -= 1
        j += 1
    block = hlo[i:j - 1]
    out = []
    for m in _ALIAS_ENTRY_RE.finditer(block):
        path = tuple(int(x) for x in m.group(1).split(",") if x.strip())
        out.append((path, int(m.group(2))))
    return out


def aliased_parameter_numbers(hlo: str) -> List[int]:
    return sorted({p for _, p in input_output_aliases(hlo)})


def audit_hlo(hlo: str, *, label: str,
              max_allgather_bytes: Optional[int] = None,
              max_collective_bytes: Optional[Dict[str, int]] = None,
              expect_alias_params: Sequence[int] = ()) -> List[Finding]:
    """Lint one compiled module's HLO text.

    * ``max_allgather_bytes`` — any all-gather with a result at or above
      this many bytes is a finding (``hlo-big-allgather``): the classic
      symptom of a sharded table being involuntarily rematerialized.
    * ``max_collective_bytes`` — the same cap per arbitrary collective op
      (``hlo-big-collective``).
    * ``expect_alias_params`` — parameter numbers the caller donated;
      each one missing from ``input_output_alias`` is a finding
      (``hlo-missing-alias``): the donation was requested but XLA copied.
    """
    findings: List[Finding] = []
    caps: Dict[str, int] = dict(max_collective_bytes or {})
    if max_allgather_bytes is not None:
        caps["all-gather"] = max_allgather_bytes
    if caps:
        for c in find_collectives(hlo, tuple(caps)):
            cap = caps[c.op]
            if c.bytes >= cap:
                rule = ("hlo-big-allgather" if c.op == "all-gather"
                        else "hlo-big-collective")
                findings.append(Finding(
                    label, c.line, rule,
                    f"{c.op} moves {c.bytes} bytes (cap {cap}) — a "
                    f"table/embed-sized collective in this step means a "
                    f"sharded buffer is being rematerialized"))
    if expect_alias_params:
        aliased = set(aliased_parameter_numbers(hlo))
        for p in expect_alias_params:
            if p not in aliased:
                findings.append(Finding(
                    label, 1, "hlo-missing-alias",
                    f"donated parameter {p} has no input_output_alias "
                    f"entry — XLA is copying the buffer, not updating "
                    f"in place"))
    return findings


# ops that move a whole buffer: on a stacked page pool each one costs the
# pool's full size in HBM traffic, where an in-place step needs none
POOL_COPY_OPS = ("copy", "copy-start", "copy-done", "broadcast",
                 "dynamic-update-slice")

_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*)$")


def _instr_types_and_op(line: str) -> Optional[Tuple[List[str], str]]:
    """``(["f32[2,17,8]", ...], opcode)`` of one HLO instruction line:
    every array type of its result (a tuple result lists several) and its
    opcode; None for lines that are not instructions."""
    m = _INSTR_RE.match(line)
    if not m:
        return None
    rest = m.group(1)
    if rest.startswith("("):  # tuple result: up to the matching paren
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        type_seg, rest = rest[:i + 1], rest[i + 1:]
    else:
        type_seg, _, rest = rest.partition(" ")
    op = re.match(r"\s*([\w\-]+)\(", rest)
    if not op:
        return None
    types = [f"{dt}[{dims}]" for dt, dims in _SHAPE_RE.findall(type_seg)]
    return types, op.group(1)


def _elements(dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def pool_copy_findings(hlo: str, *, label: str,
                       pools: Sequence[str]) -> List[Finding]:
    """Ops that copy a stacked page pool or touch one layer's slice of it.

    ``pools`` are the stacked pools' HLO types, e.g. ``"f32[12,513,16,12,
    64]"`` (layers first).  With the pools resident in the layer scan's
    carry and donated, the compiled step reaches them only through the
    token scatter and the page gather.  So any ``POOL_COPY_OPS`` op whose
    result is a whole stacked pool, and any op at all whose result is one
    layer's pool, is a finding (``hlo-pool-copy``): a per-layer slice, a
    write-back, or a whole-pool copy.  Results are matched by element type
    and count, so a pool reshaped first (pages of all layers merged, say)
    is still found."""
    stacked, layer = set(), set()
    for t in pools:
        dt, dims = _SHAPE_RE.fullmatch(t).groups()
        reps = int(dims.split(",")[0])
        stacked.add((dt, _elements(dims)))
        layer.add((dt, _elements(dims) // reps))
    findings: List[Finding] = []
    for lineno, line in enumerate(hlo.splitlines(), 1):
        parsed = _instr_types_and_op(line)
        if parsed is None:
            continue
        types, op = parsed
        sizes = [(t, (_SHAPE_RE.fullmatch(t).group(1),
                      _elements(_SHAPE_RE.fullmatch(t).group(2))))
                 for t in types]
        hit = next((t for t, k in sizes if k in layer), None)
        what = "works on one layer's slice of"
        if hit is None and op in POOL_COPY_OPS:
            hit = next((t for t, k in sizes if k in stacked), None)
            what = "copies or rewrites the whole stacked"
        if hit is not None:
            findings.append(Finding(
                label, lineno, "hlo-pool-copy",
                f"{op} on {hit} {what} page pool: the step should reach "
                f"the pool only through the token scatter and the page "
                f"gather"))
    return findings
