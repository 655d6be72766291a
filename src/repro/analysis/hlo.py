"""Trip-count-aware HLO text analysis.

``compiled.cost_analysis()`` visits every ``while`` body ONCE — for a
scan-over-layers model that undercounts flops/bytes/collectives by the trip
count (verified in tests). This module re-derives the three roofline inputs
from the partitioned HLO text with loop multipliers applied:

  * flops            — dot ops (2 * prod(result) * contracted), plus 1/elem
                       for elementwise math inside fusions;
  * bytes accessed   — per top-level instruction: operand + result bytes
                       (fusions opaque, views skipped) — the HBM-traffic
                       approximation HloCostAnalysis itself uses;
  * collective bytes — operand bytes of all-gather / all-reduce /
                       reduce-scatter / all-to-all / collective-permute,
                       derived from result shapes per collective semantics.

Loop trip counts are read from each while's condition computation (the
`compare(iter, constant)` pattern JAX scans produce); conditionals count
each branch once (upper bound); unknown trip counts fall back to 1 and are
flagged in the result.
"""
from __future__ import annotations

import dataclasses
import re
import warnings
from typing import Any, Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "s2": 1, "u2": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e3m4": 1,
    "f8e4m3b11fnuz": 1, "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
    "f8e8m0fnu": 1, "f4e2m1fn": 1,
    # shape-only placeholders that carry no data bytes
    "token": 0, "opaque": 0,
}

# A dtype the table does not know is counted at this width and WARNED about
# (once per dtype per process) instead of being silently dropped — an
# invariant gate built on byte accounting that quietly zeroes unknown
# dtypes is a false pass. ``HloCost.unknown_dtypes`` carries the per-dtype
# element counts so spec gates can fail hard on them.
_UNKNOWN_DTYPE_BYTES = 4
_WARNED_DTYPES: set = set()

COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute")

# dtype tokens follow the XLA grammar (pred/token/opaque/bf16/cNN plus
# [fsu]<digits><suffix> families); matching any lowercase word would pick
# up identifiers like `bufs[1]` out of op metadata and miscount them as
# unknown-dtype shapes
_SHAPE_RE = re.compile(
    r"\b(pred|token|opaque|bf16|c64|c128|[fsu][0-9][a-z0-9]*)\[([0-9,]*)\]")
_COMP_HDR_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s([\w\-]+)\((.*)$")
_REF_RE = re.compile(r"%([\w.\-]+)")
_OP_NAME_RE = re.compile(r'metadata=\{[^}]*?\bop_name="([^"]*)"')
_CONST_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*[su]\d+\[\]\s+constant\((\d+)\)")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")

# elementwise ops that cost ~1 flop/element (transcendentals cost more on
# real hardware; HloCostAnalysis also counts 1)
_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "power", "maximum", "minimum",
    "tanh", "exponential", "log", "rsqrt", "sqrt", "negate", "abs", "sign",
    "cosine", "sine", "logistic", "expm1", "log1p", "atan2", "remainder",
}


def _warn_unknown_dtype(dtype: str) -> None:
    if dtype in _WARNED_DTYPES:
        return
    _WARNED_DTYPES.add(dtype)
    warnings.warn(
        f"HLO dtype {dtype!r} missing from analysis table; counting "
        f"{_UNKNOWN_DTYPE_BYTES} bytes/element. Extend "
        "repro.analysis.hlo._DTYPE_BYTES to make byte budgets exact.",
        RuntimeWarning, stacklevel=3)


def _elem_count(dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n


def _shape_list_bytes(text: str) -> int:
    return sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(text))


def _shape_bytes(dtype: str, dims: str) -> int:
    n = _elem_count(dims)
    if dtype not in _DTYPE_BYTES:
        _warn_unknown_dtype(dtype)
        return n * _UNKNOWN_DTYPE_BYTES
    return n * _DTYPE_BYTES[dtype]


def _shape_elems(text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            _warn_unknown_dtype(dtype)
        total += _elem_count(dims)
    return total


def unknown_dtypes_in(text: str) -> Dict[str, int]:
    """dtype -> total element count for every HLO shape whose dtype the
    byte table does not know. Non-empty means every byte figure derived
    from this HLO is an estimate, not an account — spec gates fail on it
    unless explicitly allowed."""
    out: Dict[str, int] = {}
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            out[dtype] = out.get(dtype, 0) + _elem_count(dims)
    return out


@dataclasses.dataclass
class Instr:
    name: str
    result_type: str
    op: str
    operands: List[str]
    attrs: str
    line: str


@dataclasses.dataclass
class Computation:
    name: str
    instrs: List[Instr] = dataclasses.field(default_factory=list)
    by_name: Dict[str, Instr] = dataclasses.field(default_factory=dict)
    constants: Dict[str, int] = dataclasses.field(default_factory=dict)


def _split_operands_attrs(rest: str) -> Tuple[str, str]:
    """rest = everything after 'op(' — split at the matching ')'."""
    depth = 1
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return rest[:i], rest[i + 1:]
    return rest, ""


def parse_hlo(text: str) -> Tuple[Dict[str, Computation], Optional[str]]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    entry: Optional[str] = None
    for line in text.splitlines():
        hdr = _COMP_HDR_RE.match(line)
        if hdr and (" {" in line or line.rstrip().endswith("{")):
            cur = Computation(hdr.group(2))
            comps[cur.name] = cur
            if hdr.group(1):
                entry = cur.name
            continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        cm = _CONST_RE.match(line)
        if cm:
            cur.constants[cm.group(1)] = int(cm.group(2))
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, rtype, op, rest = m.groups()
        operands_text, attrs = _split_operands_attrs(rest)
        operands = _REF_RE.findall(operands_text)
        ins = Instr(name, rtype, op, operands, attrs, line)
        cur.instrs.append(ins)
        cur.by_name[name] = ins
    return comps, entry


def op_scopes(text: str) -> Dict[str, str]:
    """``{instruction: op_name}`` over a module's HLO text: the
    ``metadata={op_name=...}`` each instruction was traced under, i.e. its
    ``jax.named_scope`` path (``jit(step)/grad/jvp(model)/dot_general``).

    An instruction without one (a layout copy or bitcast the compiler
    inserted, a parameter) takes the op_name of its nearest user that has
    one, first in instruction order, within its computation; failing that,
    the op_name of the instruction that calls its computation (the
    ``conditional`` of a branch, the ``while`` of a body). ``""`` where
    neither gives one."""
    comps, _ = parse_hlo(text)
    out: Dict[str, str] = {}
    comp_of: Dict[str, str] = {}
    caller: Dict[str, str] = {}
    for comp in comps.values():
        users: Dict[str, List[str]] = {}
        own: Dict[str, str] = {}
        for ins in comp.instrs:
            m = _OP_NAME_RE.search(ins.attrs)
            own[ins.name] = m.group(1) if m else ""
            comp_of[ins.name] = comp.name
            for o in dict.fromkeys(ins.operands):
                users.setdefault(o, []).append(ins.name)
            for ref in _REF_RE.findall(ins.attrs):
                if ref in comps:
                    caller.setdefault(ref, ins.name)
        for ins in comp.instrs:
            name, seen, level = own[ins.name], {ins.name}, [ins.name]
            while not name and level:
                nxt = []
                for f in level:
                    for u in users.get(f, ()):
                        if u not in seen:
                            seen.add(u)
                            nxt.append(u)
                level = nxt
                name = next((own[u] for u in level if own[u]), "")
            out[ins.name] = name
    for name in out:
        n = name
        while not out[name] and comp_of[n] in caller:
            n = caller[comp_of[n]]
            out[name] = out[n]
    return out


def _trip_count(comps: Dict[str, Computation], cond_name: str
                ) -> Optional[int]:
    cond = comps.get(cond_name)
    if cond is None:
        return None
    for ins in cond.instrs:
        if ins.op == "compare":
            for o in ins.operands:
                if o in cond.constants:
                    return cond.constants[o]
    # fallback: single integer constant in the condition
    if len(cond.constants) == 1:
        return next(iter(cond.constants.values()))
    return None


def _group_size(attrs: str, default: int = 1) -> int:
    m = _GROUPS_IOTA_RE.search(attrs)
    if m:
        return max(int(m.group(2)), 1)
    m = _GROUPS_LIST_RE.search(attrs)
    if m:
        return max(len(m.group(1).split(",")), 1)
    return default


def _dot_flops(ins: Instr, comp: Computation) -> float:
    result_elems = _shape_elems(ins.result_type)
    # contracted size from lhs shape + lhs_contracting_dims
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", ins.attrs)
    contract = 1
    if m and ins.operands:
        lhs = comp.by_name.get(ins.operands[0])
        if lhs is not None:
            shapes = _SHAPE_RE.findall(lhs.result_type)
            if shapes:
                dims = shapes[0][1].split(",") if shapes[0][1] else []
                for idx in (m.group(1).split(",") if m.group(1) else []):
                    i = int(idx)
                    if i < len(dims):
                        contract *= int(dims[i])
    return 2.0 * result_elems * contract


_VIEW_OPS = {"parameter", "constant", "get-tuple-element", "tuple",
             "bitcast", "after-all", "custom-call", "partition-id",
             "replica-id", "iota", "rng-bit-generator"}

# ops that fuse into neighbors on TPU (no independent HBM round-trip)
_FUSABLE = {"convert", "broadcast", "reshape", "transpose", "select",
            "compare", "slice", "clamp", "and", "or", "not", "xor",
            "shift-left", "shift-right-logical", "shift-right-arithmetic",
            "is-finite", "floor", "ceil", "round-nearest-afz",
            "round-nearest-even", "reduce-precision", "map", "exponential-minus-one"}


@dataclasses.dataclass
class HloCost:
    flops: float = 0.0
    bytes: float = 0.0
    coll: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {k: 0.0 for k in COLLECTIVE_KINDS})
    coll_counts: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {k: 0.0 for k in COLLECTIVE_KINDS})
    # largest single collective per kind (operand bytes, NOT multiplied by
    # loop trip counts; each operand of a combined variadic op on its
    # own) — the "is there an all-gather of
    # full-parameter size in this step?" regression instrument
    coll_max: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {k: 0.0 for k in COLLECTIVE_KINDS})
    # matched async start/done pairs per kind: XLA splits a collective
    # into <kind>-start / <kind>-done exactly when it can overlap the
    # wire with independent compute (async collectives / latency-hiding
    # scheduler, repro.launch.env) — each -done closes one pair, so
    # counting them counts the collectives that actually ran async
    coll_async: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {k: 0.0 for k in COLLECTIVE_KINDS})
    unknown_trip_counts: int = 0
    # largest single resolved while-loop trip count (not nested-multiplied)
    max_trip_count: int = 0
    # dtype -> element count for shapes the byte table can't account
    unknown_dtypes: Dict[str, int] = dataclasses.field(default_factory=dict)

    def total_coll(self) -> float:
        return sum(self.coll.values())

    def as_dict(self) -> Dict[str, Any]:
        d = {k: int(v) for k, v in self.coll.items()}
        d["total"] = int(self.total_coll())
        return d


def _operand_bytes(ins: Instr, comp: Computation) -> int:
    total = 0
    for o in ins.operands:
        src = comp.by_name.get(o)
        if src is not None:
            total += _shape_list_bytes(src.result_type)
    return total


def _operand_bytes_of(result: int, kind: str, g: int) -> float:
    if kind == "all-gather":
        return result / max(g, 1)
    if kind == "reduce-scatter":
        return result * g
    return float(result)  # all-reduce / permute / all-to-all


def _collective_operand_bytes(ins: Instr, kind: str,
                              comp: Computation) -> float:
    return _operand_bytes_of(_shape_list_bytes(ins.result_type), kind,
                             _group_size(ins.attrs))


def _largest_collective_operand(ins: Instr, kind: str) -> float:
    """Bytes of the largest single collective in ``ins``. XLA's combiners
    merge independent collectives of one kind into one variadic op (a
    tuple result, one element per merged op), so each element counts on
    its own. Async ``-start`` tuples mix operand, result and context
    buffers and count whole."""
    g = _group_size(ins.attrs)
    shapes = _SHAPE_RE.findall(ins.result_type)
    if ins.op.endswith("-start") or len(shapes) < 2:
        return _operand_bytes_of(_shape_list_bytes(ins.result_type), kind, g)
    return max(_operand_bytes_of(_shape_bytes(d, s), kind, g)
               for d, s in shapes)


def analyze(text: str) -> HloCost:
    comps, entry = parse_hlo(text)
    cost = HloCost()
    if entry is None:
        return cost
    visited_stack: List[str] = []

    def visit(comp_name: str, mult: float) -> None:
        comp = comps.get(comp_name)
        if comp is None or comp_name in visited_stack:
            return
        visited_stack.append(comp_name)
        for ins in comp.instrs:
            base_kind = re.sub(r"-(start|done)$", "", ins.op)
            if base_kind in COLLECTIVE_KINDS:
                if ins.op.endswith("-done"):
                    cost.coll_async[base_kind] += mult
                    continue
                one = _collective_operand_bytes(ins, base_kind, comp)
                cost.coll[base_kind] += mult * one
                cost.coll_counts[base_kind] += mult
                cost.coll_max[base_kind] = max(
                    cost.coll_max[base_kind],
                    _largest_collective_operand(ins, base_kind))
                cost.bytes += mult * _shape_list_bytes(ins.result_type)
                continue
            if ins.op == "while":
                m = re.search(r"condition=%?([\w.\-]+)", ins.attrs)
                b = re.search(r"body=%?([\w.\-]+)", ins.attrs)
                trip = _trip_count(comps, m.group(1)) if m else None
                if trip is None:
                    trip = 1
                    cost.unknown_trip_counts += 1
                else:
                    cost.max_trip_count = max(cost.max_trip_count, trip)
                if b:
                    visit(b.group(1), mult * trip)
                continue
            if ins.op == "conditional":
                for bname in re.findall(r"%([\w.\-]+)",
                                        ins.attrs.split("branch_computations="
                                                        )[-1]) \
                        if "branch_computations" in ins.attrs else []:
                    visit(bname, mult)
                m = re.search(r"true_computation=%?([\w.\-]+)", ins.attrs)
                if m:
                    visit(m.group(1), mult)
                m = re.search(r"false_computation=%?([\w.\-]+)", ins.attrs)
                if m:
                    visit(m.group(1), mult)
                continue
            if ins.op in ("call", "async-start"):
                m = re.search(r"to_apply=%?([\w.\-]+)", ins.attrs)
                if m:
                    visit(m.group(1), mult)
                continue
            if ins.op == "fusion":
                # TPU-target model: fusions do not round-trip HBM beyond
                # what their producing/consuming dots and slices already
                # account for. (Counting every CPU kLoop micro-fusion's
                # operands overstates the memory term ~10x — verified
                # against the per-op profile in EXPERIMENTS.md.)
                m = re.search(r"calls=%?([\w.\-]+)", ins.attrs)
                if m:
                    _visit_fusion_flops(m.group(1), mult)
                continue
            if ins.op == "dot":
                cost.flops += mult * _dot_flops(ins, comp)
                cost.bytes += mult * (_shape_list_bytes(ins.result_type)
                                      + _operand_bytes(ins, comp))
                continue
            if ins.op == "convolution":
                # rough: 2 * result_elems * (kernel elems / output channels)
                cost.flops += mult * 2.0 * _shape_elems(ins.result_type)
                cost.bytes += mult * (_shape_list_bytes(ins.result_type)
                                      + _operand_bytes(ins, comp))
                continue
            if ins.op in _VIEW_OPS:
                continue
            if ins.op in _ELEMENTWISE or ins.op in _FUSABLE:
                # flops only: these fuse into neighbors on TPU.
                if ins.op in _ELEMENTWISE:
                    cost.flops += mult * _shape_elems(ins.result_type)
                continue
            if ins.op in ("dynamic-update-slice", "dynamic-slice", "gather",
                          "pad", "copy", "concatenate", "sort", "copy-start"):
                cost.bytes += mult * _shape_list_bytes(ins.result_type)
                continue
            if ins.op in ("reduce", "reduce-window", "scatter",
                          "select-and-scatter"):
                cost.bytes += mult * _operand_bytes(ins, comp)
                continue
            cost.bytes += mult * _shape_list_bytes(ins.result_type)
        visited_stack.pop()

    def _visit_fusion_flops(comp_name: str, mult: float) -> None:
        comp = comps.get(comp_name)
        if comp is None:
            return
        for ins in comp.instrs:
            if ins.op == "dot":
                cost.flops += mult * _dot_flops(ins, comp)
            elif ins.op == "fusion":
                m = re.search(r"calls=%?([\w.\-]+)", ins.attrs)
                if m:
                    _visit_fusion_flops(m.group(1), mult)
            elif ins.op in _ELEMENTWISE:
                cost.flops += mult * _shape_elems(ins.result_type)

    visit(entry, 1.0)
    cost.unknown_dtypes = unknown_dtypes_in(text)
    return cost


# ------------------------------ public API -----------------------------------


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-kind collective operand bytes with while-loop multipliers."""
    return analyze(hlo_text).as_dict()


def collective_counts(hlo_text: str) -> Dict[str, int]:
    c = analyze(hlo_text)
    return {k: int(v) for k, v in c.coll_counts.items()}


def collective_summary(hlo_text: str) -> Dict[str, Dict[str, int]]:
    """Per-kind {count, bytes, max_bytes, async_pairs} — the
    communication regression
    instrument. ``count``/``bytes`` carry while-loop trip multipliers;
    ``max_bytes`` is the largest SINGLE collective of that kind, which is
    what "no all-gather of full-parameter size" assertions compare against
    (a trip-multiplied total would flag many small collectives as one big
    one)."""
    c = analyze(hlo_text)
    return {k: {"count": int(c.coll_counts[k]),
                "bytes": int(c.coll[k]),
                "max_bytes": int(c.coll_max[k]),
                "async_pairs": int(c.coll_async[k])}
            for k in COLLECTIVE_KINDS}


def full_cost(hlo_text: str) -> Dict[str, float]:
    c = analyze(hlo_text)
    d = {"flops": c.flops, "bytes": c.bytes,
         "unknown_trip_counts": c.unknown_trip_counts,
         "max_trip_count": c.max_trip_count,
         "unknown_dtype_elems": sum(c.unknown_dtypes.values())}
    d.update({f"coll_{k}": v for k, v in c.coll.items()})
    d["coll_total"] = c.total_coll()
    return d
