"""XLA artifact adapter: compiled-HLO parsing for roofline terms.

``compiled.cost_analysis()`` visits a ``while`` body ONCE, so scan-over-
layers programs (all of ours) would be undercounted by the layer count.
This module walks the HLO text recursively instead:

  * FLOPs: every ``dot``/``convolution`` (2 * prod(out) * contracted dims),
    including inside fused computations, multiplied by enclosing
    ``known_trip_count`` factors;
  * HBM bytes (estimate): per top-level instruction, operand + output sizes
    (fusion internals excluded — they stay in registers/VMEM);
  * collective bytes: operand sizes of all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute (sync or async -start),
    scaled by trip counts, bucketed by kind.

Validated against cost_analysis() on unrolled graphs (tests/test_hlo.py).
"""
from __future__ import annotations

import math
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
# header params may contain nested parens (tuple-typed scan carries)
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.+)$")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALL_ATTR_RE = re.compile(
    r"(?:calls|body|condition|to_apply|branch_computations)="
    r"[\{]?%?([\w\.\-]+(?:,\s*%?[\w\.\-]+)*)[\}]?")

COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute")


def shape_bytes(type_str: str) -> int:
    """Total bytes of all array shapes in an HLO type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_NAME_RE = re.compile(r"%([\w\.\-]+)")
_PARAM_RE = re.compile(r"([\w\.\-]+)\s*:\s*((?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[0-9,]*\})?))")


@dataclass
class Instr:
    name: str
    opcode: str
    out_type: str
    operands_str: str
    attrs: str

    def operand_names(self) -> List[str]:
        return _NAME_RE.findall(self.operands_str)

    def out_bytes(self) -> int:
        return shape_bytes(self.out_type)


@dataclass
class Computation:
    name: str
    instrs: List[Instr] = field(default_factory=list)
    symtab: Dict[str, str] = field(default_factory=dict)  # name -> type str

    def type_of(self, name: str) -> str:
        return self.symtab.get(name, "")

    def operand_bytes(self, ins: Instr) -> int:
        inline = shape_bytes(ins.operands_str)
        if inline:
            return inline
        return sum(shape_bytes(self.type_of(n)) for n in ins.operand_names())

    def operand_shapes(self, ins: Instr) -> List[Tuple[str, str]]:
        inline = _SHAPE_RE.findall(ins.operands_str)
        if inline:
            return inline
        out: List[Tuple[str, str]] = []
        for n in ins.operand_names():
            out.extend(_SHAPE_RE.findall(self.type_of(n)))
        return out


_OPCODE_RE = re.compile(
    r"^([a-z0-9\-]+)(?:\()")


def _parse_instr(line: str) -> Optional[Instr]:
    m = _INSTR_RE.match(line)
    if not m:
        return None
    name, rest = m.group(1), m.group(2)
    # rest: "bf16[2,4]{1,0} opcode(operands...), attrs"
    # find the opcode: first token after the type that looks like `op(`
    tm = re.match(r"^(\([^)]*\)|[\w\[\]\{\},\.\/ ]+?)\s+([a-z0-9\-]+)\(", rest)
    if not tm:
        return None
    out_type, opcode = tm.group(1), tm.group(2)
    body = rest[tm.end() - 1:]
    # operands: up to matching close paren
    depth = 0
    end = 0
    for i, ch in enumerate(body):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    operands = body[1:end] if end else ""
    attrs = body[end + 1:] if end else ""
    return Instr(name=name, opcode=opcode, out_type=out_type,
                 operands_str=operands, attrs=attrs)


def parse_computations(hlo_text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    current: Optional[Computation] = None
    for raw in hlo_text.splitlines():
        line = raw.rstrip()
        if not line:
            continue
        stripped = line.strip()
        hdr = _COMP_HDR_RE.match(stripped)
        if hdr and stripped.endswith("{"):
            current = Computation(name=hdr.group(1))
            comps[current.name] = current
            # header parameters carry types: "(p0: f32[2,3], p1: ...)"
            for pname, ptype in _PARAM_RE.findall(stripped):
                current.symtab[pname] = ptype
            continue
        if stripped == "}":
            current = None
            continue
        if current is not None:
            ins = _parse_instr(stripped)
            if ins is not None:
                current.instrs.append(ins)
                current.symtab[ins.name] = ins.out_type
    return comps


def _dot_flops(ins: Instr, comp: Computation) -> float:
    """2 * prod(out dims) * prod(contracted dims)."""
    out_elems = 1
    m = _SHAPE_RE.search(ins.out_type)
    if not m:
        return 0.0
    for d in m.group(2).split(","):
        if d:
            out_elems *= int(d)
    cm = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", ins.attrs)
    shapes = comp.operand_shapes(ins)
    if not cm or not shapes:
        return 2.0 * out_elems     # fallback: unknown K
    lhs_dims = [int(d) for d in shapes[0][1].split(",") if d]
    k = 1
    for ci in cm.group(1).split(","):
        if ci and int(ci) < len(lhs_dims):
            k *= lhs_dims[int(ci)]
    return 2.0 * out_elems * k


def _conv_flops(ins: Instr, comp: Computation) -> float:
    out_elems = 1
    m = _SHAPE_RE.search(ins.out_type)
    if m:
        for d in m.group(2).split(","):
            if d:
                out_elems *= int(d)
    shapes = comp.operand_shapes(ins)
    if len(shapes) < 2:
        return 2.0 * out_elems
    rhs_dims = [int(d) for d in shapes[1][1].split(",") if d]
    # kernel spatial * input features: everything except output-feature dim.
    # dim labels from dnums attr are fiddly; approximate with prod(rhs)/max_dim
    if rhs_dims:
        k = 1
        for d in rhs_dims:
            k *= d
        k //= max(rhs_dims)        # divide out the output-feature dim
        return 2.0 * out_elems * max(k, 1)
    return 2.0 * out_elems


@dataclass
class HloCost:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    collective_bytes: Dict[str, float] = field(
        default_factory=lambda: defaultdict(float))
    collective_bytes_f32: float = 0.0
    collective_count: int = 0

    @property
    def total_collective_bytes(self) -> float:
        return sum(self.collective_bytes.values())


_SKIP_BYTES_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "token", "while", "conditional", "call",
}


def analyze_hlo(hlo_text: str, entry: Optional[str] = None) -> HloCost:
    comps = parse_computations(hlo_text)
    if not comps:
        return HloCost()
    if entry is None:
        m = re.search(r"^ENTRY\s+%?([\w\.\-]+)", hlo_text, re.MULTILINE)
        entry = m.group(1) if m else next(iter(comps))

    memo: Dict[str, HloCost] = {}

    def fused_flops(comp_name: str) -> float:
        """Dot/conv FLOPs anywhere inside a fused computation."""
        c = comps.get(comp_name)
        if c is None:
            return 0.0
        f = 0.0
        for ins in c.instrs:
            if ins.opcode == "dot":
                f += _dot_flops(ins, c)
            elif ins.opcode == "convolution":
                f += _conv_flops(ins, c)
            elif ins.opcode == "fusion":
                cm = re.search(r"calls=%?([\w\.\-]+)", ins.attrs)
                if cm:
                    f += fused_flops(cm.group(1))
        return f

    def _stacked_discount(t: str, body_trips: int) -> int:
        """Bytes of one type, discounted if it is a stacked scan buffer
        (leading dim == trip count): each iteration touches one slice."""
        b = shape_bytes(t)
        if body_trips > 1:
            m = _SHAPE_RE.search(t)
            if m:
                dims = [int(d) for d in m.group(2).split(",") if d]
                if dims and dims[0] == body_trips:
                    b //= body_trips
        return b

    def fusion_bytes(c: Computation, ins: Instr, body_trips: int) -> int:
        """Fusion HBM traffic.  Inside a while body (scan), operands/outputs
        whose leading dim equals the trip count are *stacked xs/ys* — each
        iteration reads/writes one slice (the slicing/DUS happens inside
        the fusion)."""
        total = _stacked_discount(ins.out_type, body_trips)
        for nm in ins.operand_names():
            total += _stacked_discount(c.type_of(nm), body_trips)
        return total

    def walk(comp_name: str, body_trips: int = 1) -> HloCost:
        if comp_name in memo:
            return memo[comp_name]
        cost = HloCost()
        memo[comp_name] = cost       # cycle guard
        c = comps.get(comp_name)
        if c is None:
            return cost
        for ins in c.instrs:
            op = ins.opcode
            base_kind = op.replace("-start", "")
            if base_kind in COLLECTIVE_KINDS:
                b = c.operand_bytes(ins)
                cost.collective_bytes[base_kind] += b
                # f32 collective payloads are CPU-legalization artifacts for
                # bf16 models (TPU reduces the bf16 dot outputs directly);
                # track them so the roofline can report a TPU-adjusted term.
                if "f32[" in (ins.operands_str + c.type_of(
                        (ins.operand_names() or [""])[0])):
                    cost.collective_bytes_f32 += b
                cost.collective_count += 1
                cost.hbm_bytes += b + ins.out_bytes()
                continue
            if op == "while":
                cm = _CALL_ATTR_RE.findall(ins.attrs)
                trip_m = _TRIP_RE.search(ins.attrs)
                trips = int(trip_m.group(1)) if trip_m else 1
                body_re = re.search(r"body=%?([\w\.\-]+)", ins.attrs)
                cond_re = re.search(r"condition=%?([\w\.\-]+)", ins.attrs)
                if body_re:
                    sub = walk(body_re.group(1), body_trips=trips)
                    cost.flops += sub.flops * trips
                    cost.hbm_bytes += sub.hbm_bytes * trips
                    for k, v in sub.collective_bytes.items():
                        cost.collective_bytes[k] += v * trips
                    cost.collective_bytes_f32 += sub.collective_bytes_f32 * trips
                    cost.collective_count += sub.collective_count * trips
                if cond_re:
                    walk(cond_re.group(1))   # negligible; evaluated for memo
                continue
            if op in ("call", "conditional"):
                cm = re.search(r"(?:to_apply|branch_computations)="
                               r"[\{]?%?([\w\.\-]+)", ins.attrs)
                if cm:
                    sub = walk(cm.group(1))
                    cost.flops += sub.flops
                    cost.hbm_bytes += sub.hbm_bytes
                    for k, v in sub.collective_bytes.items():
                        cost.collective_bytes[k] += v
                    cost.collective_count += sub.collective_count
                continue
            if op == "fusion":
                cm = re.search(r"calls=%?([\w\.\-]+)", ins.attrs)
                if cm:
                    cost.flops += fused_flops(cm.group(1))
                # XLA:CPU wraps nearly every elementwise op in its own
                # trivial kLoop fusion ("wrapped_*"); a TPU build fuses those
                # into neighbours, so counting their traffic would overstate
                # HBM bytes ~40x.  Count only real multi-op fusions.
                if not ins.name.startswith(("wrapped_", "convert")):
                    cost.hbm_bytes += fusion_bytes(c, ins, body_trips)
                continue
            if op == "dot":
                cost.flops += _dot_flops(ins, c)
                cost.hbm_bytes += c.operand_bytes(ins) + ins.out_bytes()
                continue
            if op == "convolution":
                cost.flops += _conv_flops(ins, c)
                cost.hbm_bytes += c.operand_bytes(ins) + ins.out_bytes()
                continue
            if op == "custom-call":
                cost.hbm_bytes += c.operand_bytes(ins) + ins.out_bytes()
                continue
            if op in _SKIP_BYTES_OPS or op.endswith("-done"):
                continue
            if op in ("dynamic-slice", "slice", "gather"):
                # reads only the slice, not the full operand
                cost.hbm_bytes += 2 * ins.out_bytes()
                continue
            if op == "dynamic-update-slice":
                names = ins.operand_names()
                upd = shape_bytes(c.type_of(names[1])) if len(names) > 1 else 0
                cost.hbm_bytes += 2 * upd
                continue
            # Everything else (convert/copy/broadcast/transpose/elementwise/
            # reduce) fuses into neighbours on TPU: counting it would model
            # XLA:CPU's fusion granularity, not the target's.  Skipped.
        return cost

    total = walk(entry)
    # normalize defaultdict for stable serialisation
    total.collective_bytes = dict(total.collective_bytes)
    return total


def top_contributors(hlo_text: str, k: int = 20,
                     metric: str = "bytes") -> List[Tuple[float, int, str, str, str]]:
    """Top-k (value, trips, computation, opcode, name) contributors to HBM
    bytes or FLOPs — the dry-run 'profile' used by the perf iteration loop."""
    comps = parse_computations(hlo_text)
    trips: Dict[str, int] = {}
    for c in comps.values():
        for ins in c.instrs:
            if ins.opcode == "while":
                m = _TRIP_RE.search(ins.attrs)
                b = re.search(r"body=%?([\w\.\-]+)", ins.attrs)
                if b:
                    trips[b.group(1)] = int(m.group(1)) if m else 1
    # propagate nesting (wide loops): one level is enough for our scans
    rows = []
    for cname, c in comps.items():
        mult = trips.get(cname, 1)
        for ins in c.instrs:
            if metric == "bytes":
                if ins.opcode in ("dot", "convolution", "custom-call"):
                    val = c.operand_bytes(ins) + ins.out_bytes()
                elif ins.opcode == "fusion" and not ins.name.startswith(
                        ("wrapped_", "convert")):
                    val = c.operand_bytes(ins) + ins.out_bytes()
                elif ins.opcode.replace("-start", "") in COLLECTIVE_KINDS:
                    val = c.operand_bytes(ins) + ins.out_bytes()
                else:
                    continue
            else:
                if ins.opcode == "dot":
                    val = _dot_flops(ins, c)
                elif ins.opcode == "convolution":
                    val = _conv_flops(ins, c)
                else:
                    continue
            rows.append((val * mult, mult, cname, ins.opcode,
                         ins.name + " " + ins.out_type[:40]))
    rows.sort(reverse=True)
    return rows[:k]


def analyze_compiled(compiled) -> Dict[str, float]:
    """Full report for a jax ``compiled`` object (dry-run artifact)."""
    text = compiled.as_text()
    cost = analyze_hlo(text)
    ca = {}
    try:
        ca = compiled.cost_analysis() or {}
    except Exception:
        pass
    mem = {}
    try:
        ma = compiled.memory_analysis()
        mem = {
            "argument_bytes": getattr(ma, "argument_size_in_bytes", 0),
            "output_bytes": getattr(ma, "output_size_in_bytes", 0),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", 0),
            "peak_bytes": (getattr(ma, "argument_size_in_bytes", 0)
                           + getattr(ma, "temp_size_in_bytes", 0)),
        }
    except Exception:
        pass
    return {
        "flops": cost.flops,
        "hbm_bytes": cost.hbm_bytes,
        "collective_bytes": cost.total_collective_bytes,
        "collective_bytes_f32": cost.collective_bytes_f32,
        "collective_bytes_tpu_adjusted": cost.total_collective_bytes
        - 0.5 * cost.collective_bytes_f32,
        "collective_breakdown": cost.collective_bytes,
        "collective_count": cost.collective_count,
        "xla_cost_analysis_flops": float(ca.get("flops", 0.0)),
        "xla_bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        **mem,
    }
