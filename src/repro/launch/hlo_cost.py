"""Post-SPMD HLO cost walker.

XLA's ``compiled.cost_analysis()`` counts every while-loop body exactly
once, so any lax.scan (layers, flash KV blocks, vocab CE blocks,
microbatch accumulation) is undercounted.  This walker parses
``compiled.as_text()`` — whose shapes are already the per-device
(partitioned) shapes — and rolls costs up from the entry computation,
multiplying while bodies by their trip count (taken from the
``known_trip_count`` backend_config, falling back to the largest integer
constant in the loop condition).

Per-device terms produced:
  flops             2*prod(out)*prod(contracting) per dot (+ conv approx)
  hbm_bytes         Σ (operands + outputs) over materializing top-level ops
                    (fusion boundaries, dots, copies, slices, collectives)
  collective_bytes  Σ operand bytes per collective kind
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "s4": 1, "u4": 1, "pred": 1, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(
    r"\b(f64|f32|f16|bf16|f8e4m3fn|f8e5m2|s64|u64|s32|u32|s16|u16|s8|u8|s4|u4"
    r"|pred|c64|c128)\[([0-9,]*)\]")

COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute", "collective-broadcast",
                    "ragged-all-to-all")

# ops whose operands+outputs we count as HBM traffic.  The CPU backend
# leaves long elementwise chains unfused; a TPU build fuses them, so bare
# elementwise/convert/broadcast ops are treated as fused (skipped) and the
# traffic model is: every fusion/dot/collective/reshuffle boundary
# materializes to HBM.  Biased low for pointwise-heavy code, uniform
# across cells — documented in EXPERIMENTS.md §Roofline.
_MATERIALIZING = ("fusion", "dot", "convolution", "dynamic-slice",
                  "dynamic-update-slice", "reduce", "reduce-window", "sort",
                  "scatter", "gather", "transpose", "reshape", "slice",
                  "concatenate", "pad", "select-and-scatter", "cholesky",
                  "triangular-solve", "rng", "custom-call") \
    + COLLECTIVE_KINDS
# "copy" is excluded: on CPU it is mostly loop-carried-buffer aliasing that
# a TPU build elides via donation; counting it charges phantom traffic.
_OUT_ONLY = ()
_SKIP = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast",
         "after-all", "partition-id", "replica-id", "while", "call",
         "conditional", "domain", "opt-barrier", "broadcast", "iota",
         "add", "multiply", "subtract", "divide", "exponential", "tanh",
         "select", "compare", "maximum", "minimum", "convert", "and", "or",
         "not", "xor", "negate", "abs", "sign", "floor", "ceil", "sqrt",
         "rsqrt", "power", "log", "log-plus-one", "exponential-minus-one",
         "cosine", "sine", "clamp", "is-finite", "round-nearest-even",
         "shift-left", "shift-right-logical", "shift-right-arithmetic",
         "remainder", "atan2", "stochastic-convert", "reduce-precision")


def _shape_elems(dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d.strip():
            n *= int(d)
    return n


def _type_bytes(type_str: str) -> int:
    return sum(_shape_elems(dims) * _DTYPE_BYTES[dt]
               for dt, dims in _SHAPE_RE.findall(type_str))


@dataclass
class Instr:
    name: str
    opcode: str
    result_type: str
    operands: List[str]
    attrs: str
    line: str


@dataclass
class Computation:
    name: str
    instrs: List[Instr] = field(default_factory=list)
    symbols: Dict[str, str] = field(default_factory=dict)  # var -> type str


_HDR_RE = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\(")
_NAME_RE = re.compile(r"^(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*")


def _split_type_opcode(rest: str):
    """Split '<result-type> <opcode>(<...>' handling nested tuple types."""
    depth = 0
    for i, ch in enumerate(rest):
        if ch == "(":
            if depth == 0 and i > 0:
                j = i - 1
                while j >= 0 and (rest[j].isalnum() or rest[j] in "-_"):
                    j -= 1
                name = rest[j + 1:i]
                if name and not name[0].isdigit() and (j < 0 or
                                                       rest[j] in " \t"):
                    return rest[:j + 1].strip(), name, rest[i + 1:]
            depth += 1
        elif ch == ")":
            depth -= 1
    return None
_TRIP_RE = re.compile(r'known_trip_count[^}]*?"n"\s*:\s*"?(\d+)')
_BODY_RE = re.compile(r"body=%?([\w\.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w\.\-]+)")
_CALLS_RE = re.compile(r"(?:calls|to_apply)=%?([\w\.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")
_OPND_RE = re.compile(r"%([\w\.\-]+)")


def parse_module(text: str) -> Tuple[Dict[str, Computation], Optional[str]]:
    comps: Dict[str, Computation] = {}
    entry: Optional[str] = None
    cur: Optional[Computation] = None
    for raw in text.splitlines():
        ls = raw.strip()
        if not ls or ls.startswith(("HloModule", "//", "#")):
            continue
        if ls.endswith("{") and "=" not in ls.split("(")[0]:
            m = _HDR_RE.match(ls)
            if m:
                cur = Computation(m.group(2))
                comps[cur.name] = cur
                if m.group(1):
                    entry = cur.name
                continue
        if ls == "}" or cur is None:
            continue
        m = _NAME_RE.match(ls)
        if not m:
            continue
        name = m.group(1)
        split = _split_type_opcode(ls[m.end():])
        if split is None:
            continue
        rtype, opcode, rest = split
        # split operands (up to the matching close paren) from attributes
        depth = 1
        i = 0
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
        opnds_str, attrs = rest[:i], rest[i + 1:]
        operands = _OPND_RE.findall(opnds_str)
        inst = Instr(name, opcode, rtype.strip(), operands, attrs, ls)
        cur.instrs.append(inst)
        cur.symbols[name] = rtype.strip()
    return comps, entry


@dataclass
class Costs:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    collectives: Dict[str, float] = field(default_factory=dict)
    collective_total: float = 0.0

    def add(self, other: "Costs", mult: float = 1.0) -> None:
        self.flops += mult * other.flops
        self.hbm_bytes += mult * other.hbm_bytes
        for k, v in other.collectives.items():
            self.collectives[k] = self.collectives.get(k, 0.0) + mult * v
        self.collective_total += mult * other.collective_total


class CostWalker:
    def __init__(self, comps: Dict[str, Computation], entry: str):
        self.comps = comps
        self.entry = entry
        self._memo: Dict[str, Costs] = {}

    def _operand_bytes(self, comp: Computation, inst: Instr,
                       seen: Optional[set] = None) -> float:
        """Read traffic of an op.  With ``seen``, each buffer is charged
        once per computation execution no matter how many consumers it has
        (a value resident in HBM is streamed once; on-chip reuse after
        that) — without it the multi-consumer fan-out inflates ~3x."""
        tot = 0.0
        for o in inst.operands:
            if seen is not None:
                if o in seen:
                    continue
                seen.add(o)
            t = comp.symbols.get(o)
            if t:
                tot += _type_bytes(t)
        return tot

    def _dot_flops(self, comp: Computation, inst: Instr) -> float:
        out = _SHAPE_RE.findall(inst.result_type)
        out_elems = _shape_elems(out[0][1]) if out else 0
        m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", inst.attrs) or \
            re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", inst.line)
        contract = 1
        if m and inst.operands:
            lhs_t = comp.symbols.get(inst.operands[0], "")
            sh = _SHAPE_RE.findall(lhs_t)
            if sh:
                dims = [int(d) for d in sh[0][1].split(",") if d.strip()]
                for idx in m.group(1).split(","):
                    if idx.strip() and int(idx) < len(dims):
                        contract *= dims[int(idx)]
        return 2.0 * out_elems * contract

    def _conv_flops(self, comp: Computation, inst: Instr) -> float:
        out = _SHAPE_RE.findall(inst.result_type)
        if not out or len(inst.operands) < 2:
            return 0.0
        out_elems = _shape_elems(out[0][1])
        k_t = comp.symbols.get(inst.operands[1], "")
        sh = _SHAPE_RE.findall(k_t)
        if not sh:
            return 0.0
        kdims = [int(d) for d in sh[0][1].split(",") if d.strip()]
        co = kdims[-1] if kdims else 1
        import math
        return 2.0 * out_elems * (math.prod(kdims) / max(co, 1))

    def _trip_count(self, inst: Instr) -> int:
        m = _TRIP_RE.search(inst.line)
        if m:
            return int(m.group(1))
        cm = _COND_RE.search(inst.line)
        if cm and cm.group(1) in self.comps:
            consts = []
            for ci in self.comps[cm.group(1)].instrs:
                consts += [int(c) for c in _CONST_RE.findall(ci.line)]
            if consts:
                return max(consts)
        return 1

    def comp_costs(self, name: str) -> Costs:
        if name in self._memo:
            return self._memo[name]
        self._memo[name] = Costs()  # cycle guard
        comp = self.comps.get(name)
        if comp is None:
            return self._memo[name]
        c = Costs()
        seen_reads: set = set()
        for inst in comp.instrs:
            op = inst.opcode
            base = op
            for suff in ("-start", "-done"):
                if base.endswith(suff):
                    base = base[: -len(suff)]
            if op.endswith("-done"):
                continue
            if base == "while":
                trips = self._trip_count(inst)
                bm = _BODY_RE.search(inst.line)
                if bm:
                    c.add(self.comp_costs(bm.group(1)), trips)
                continue
            if base in ("call", "conditional"):
                for callee in _CALLS_RE.findall(inst.line):
                    c.add(self.comp_costs(callee), 1.0)
                continue
            if base == "fusion":
                # count the fusion's DOTS (they run on the MXU) but not its
                # internal elementwise ops; bytes at the fusion boundary
                for callee in _CALLS_RE.findall(inst.attrs):
                    c.flops += self._fusion_dot_flops(callee)
                c.hbm_bytes += self._fusion_bytes(comp, inst, seen_reads)
                continue
            if base == "dynamic-update-slice":
                # in-place: traffic = the update slice (read + write)
                upd = (comp.symbols.get(inst.operands[1], "")
                       if len(inst.operands) > 1 else inst.result_type)
                c.hbm_bytes += 2 * _type_bytes(upd)
                continue
            if base in ("dynamic-slice", "gather"):
                c.hbm_bytes += 2 * _type_bytes(inst.result_type)
                continue
            if base == "scatter":
                upd = (comp.symbols.get(inst.operands[2], "")
                       if len(inst.operands) > 2 else inst.result_type)
                c.hbm_bytes += 2 * _type_bytes(upd)
                continue
            if base in COLLECTIVE_KINDS:
                b = self._operand_bytes(comp, inst)
                c.collectives[base] = c.collectives.get(base, 0.0) + b
                c.collective_total += b
                c.hbm_bytes += b + _type_bytes(inst.result_type)
                continue
            if base == "dot":
                c.flops += self._dot_flops(comp, inst)
                c.hbm_bytes += (_type_bytes(inst.result_type)
                                + self._operand_bytes(comp, inst, seen_reads))
                continue
            if base == "convolution":
                c.flops += self._conv_flops(comp, inst)
                c.hbm_bytes += (_type_bytes(inst.result_type)
                                + self._operand_bytes(comp, inst, seen_reads))
                continue
            if base in _OUT_ONLY:
                c.hbm_bytes += _type_bytes(inst.result_type)
                continue
            if base in _SKIP:
                continue
            if base in _MATERIALIZING or base.startswith("wrapped"):
                c.hbm_bytes += (_type_bytes(inst.result_type)
                                + self._operand_bytes(comp, inst, seen_reads))
        self._memo[name] = c
        return c

    def _fusion_bytes(self, comp: Computation, inst: Instr,
                      seen: Optional[set] = None) -> float:
        """Fusion boundary traffic.  In-place update fusions (root =
        dynamic-update-slice / scatter) move only the updated slice, not
        the aliased buffer; slice-read fusions move only the slice."""
        callees = _CALLS_RE.findall(inst.attrs)
        root = None
        callee_comp = self.comps.get(callees[0]) if callees else None
        if callee_comp is not None:
            for ci in callee_comp.instrs:
                if ci.line.startswith("ROOT"):
                    root = ci
            if root is None and callee_comp.instrs:
                root = callee_comp.instrs[-1]
        if root is not None and root.opcode in ("dynamic-update-slice",
                                                "scatter"):
            idx = 1 if root.opcode == "dynamic-update-slice" else 2
            upd_t = (callee_comp.symbols.get(root.operands[idx], "")
                     if len(root.operands) > idx else "")
            small = sum(_type_bytes(comp.symbols.get(o, ""))
                        for o in inst.operands
                        if _type_bytes(comp.symbols.get(o, ""))
                        < 0.5 * _type_bytes(inst.result_type))
            return 2 * _type_bytes(upd_t) + small
        if root is not None and root.opcode in ("dynamic-slice",):
            return 2 * _type_bytes(inst.result_type)
        return (_type_bytes(inst.result_type)
                + self._operand_bytes(comp, inst, seen))

    def _fusion_dot_flops(self, callee: str) -> float:
        comp = self.comps.get(callee)
        if comp is None:
            return 0.0
        f = 0.0
        for inst in comp.instrs:
            if inst.opcode == "dot":
                f += self._dot_flops(comp, inst)
            elif inst.opcode == "convolution":
                f += self._conv_flops(comp, inst)
            elif inst.opcode == "fusion":
                for c2 in _CALLS_RE.findall(inst.attrs):
                    f += self._fusion_dot_flops(c2)
        return f


def breakdown(text: str, top: int = 20) -> List[Tuple[str, str, float]]:
    """(opcode, result_type, bytes) top contributors — §Perf attribution."""
    comps, entry = parse_module(text)
    if entry is None:
        entry = next(iter(comps)) if comps else ""
    w = CostWalker(comps, entry)
    items: Dict[Tuple[str, str], float] = {}

    def walk(name: str, mult: float, seen: Tuple[str, ...] = ()):
        comp = comps.get(name)
        if comp is None or name in seen:
            return
        for inst in comp.instrs:
            base = inst.opcode
            for suff in ("-start", "-done"):
                if base.endswith(suff):
                    base = base[:-len(suff)]
            if inst.opcode.endswith("-done"):
                continue
            if base == "while":
                m = _BODY_RE.search(inst.line)
                if m:
                    walk(m.group(1), mult * w._trip_count(inst),
                         seen + (name,))
                continue
            if base in ("call", "conditional"):
                for c2 in _CALLS_RE.findall(inst.line):
                    walk(c2, mult, seen + (name,))
                continue
            if base in _SKIP or base == "copy" or base in _OUT_ONLY:
                continue
            if base == "fusion":
                b = w._fusion_bytes(comp, inst)
            elif base == "dynamic-update-slice":
                upd = (comp.symbols.get(inst.operands[1], "")
                       if len(inst.operands) > 1 else inst.result_type)
                b = 2 * _type_bytes(upd)
            elif base in ("dynamic-slice", "gather"):
                b = 2 * _type_bytes(inst.result_type)
            else:
                b = (_type_bytes(inst.result_type)
                     + w._operand_bytes(comp, inst))
            key = (base, inst.result_type[:60])
            items[key] = items.get(key, 0.0) + mult * b

    walk(entry, 1.0)
    out = sorted(((op, t, b) for (op, t), b in items.items()),
                 key=lambda x: -x[2])
    return out[:top]


def analyze(text: str) -> Dict[str, float]:
    comps, entry = parse_module(text)
    if entry is None:
        entry = next(iter(comps)) if comps else ""
    w = CostWalker(comps, entry)
    c = w.comp_costs(entry)
    out = {"flops": c.flops, "hbm_bytes": c.hbm_bytes,
           "collective_bytes": c.collective_total}
    for k, v in c.collectives.items():
        out[f"coll_{k}"] = v
    return out
