"""Loop-aware cost accounting from compiled HLO text.

XLA's cost_analysis() counts a while-loop body ONCE, which undercounts
layer-scan / grad-accumulation models by the trip product. This module parses
the compiled module text and walks the call graph multiplying by while-loop
trip counts:

  * FLOPs       — 2 * prod(result dims) * prod(contracting dim sizes) for
                  every dot / convolution (elementwise flops ignored: <1%).
  * bytes       — result bytes + resolvable operand bytes per instruction
                  (fusion-internal instructions are skipped: fused
                  intermediates never touch HBM).
  * collectives — result bytes of all-gather / all-reduce / reduce-scatter /
                  all-to-all / collective-permute, by kind.

Trip counts come from the loop-condition computation: jax lowers scan to a
while whose condition compares the counter against a constant.
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "token": 0, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(
    r"(pred|bf16|f16|f32|f64|s4|u4|s8|u8|s16|u16|s32|u32|s64|u64)\[([0-9,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*(.*)$")
_COMP_RE = re.compile(r"^(%[\w.\-]+|ENTRY\s+%?[\w.\-]+)\s*(?:\([^)]*\))?.*\{")
_OPND_RE = re.compile(r"%[\w.\-]+")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def result_part(rhs: str) -> str:
    """The result shape(s) of an instruction's right-hand side: the text
    before the op's operand list, or the whole leading tuple when the
    instruction returns one (a combined all-reduce does)."""
    text = rhs.lstrip()
    if not text.startswith("("):
        pos = text.find("(")
        return text[:pos] if pos >= 0 else text
    depth = 0
    for i, ch in enumerate(text):
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if depth == 0:
            return text[:i + 1]
    return text


@dataclass
class Instruction:
    name: str
    rhs: str

    @property
    def op(self) -> str:
        m = re.search(r"\}?\s*([a-z][\w\-]*)\(", self.rhs)
        return m.group(1) if m else ""

    @property
    def result_bytes(self) -> int:
        return _shape_bytes(result_part(self.rhs))

    @property
    def result_dims(self):
        m = _SHAPE_RE.search(result_part(self.rhs))
        if not m:
            return None
        return [int(d) for d in m.group(2).split(",") if d]


@dataclass
class Computation:
    name: str
    instructions: List[Instruction] = field(default_factory=list)
    is_fusion_body: bool = False


def parse_hlo(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    current: Optional[Computation] = None
    for line in text.splitlines():
        mc = _COMP_RE.match(line)
        if mc and ("->" in line or line.rstrip().endswith("{")):
            name = mc.group(1)
            if name.startswith("ENTRY"):
                name = "ENTRY"
            current = Computation(name=name)
            comps[name] = current
            continue
        if line.strip() == "}":
            continue
        if current is None:
            continue
        md = _DEF_RE.match(line)
        if md:
            current.instructions.append(Instruction(md.group(1), md.group(2)))
    return comps


def _dot_flops(ins: "Instruction", dims_of: Dict[str, list]) -> float:
    """2 * prod(result) * prod(contracting sizes). Operand shapes are looked
    up in the module-wide name -> dims map (HLO operands carry no shapes)."""
    rhs = ins.rhs
    res_dims = ins.result_dims or []
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", rhs)
    if m is None:
        return 0.0
    cdims = [int(d) for d in m.group(1).split(",") if d]
    opnds = _OPND_RE.findall(rhs.split("(", 1)[1]) if "(" in rhs else []
    lhs_dims = dims_of.get(opnds[0]) if opnds else None
    if lhs_dims is None:
        return 0.0
    csize = 1
    for cd in cdims:
        if cd < len(lhs_dims):
            csize *= lhs_dims[cd]
    res = 1
    for d in res_dims:
        res *= d
    return 2.0 * res * csize


def _conv_flops(rhs: str) -> float:
    shapes = _SHAPE_RE.findall(rhs)
    if len(shapes) < 3:
        return 0.0
    res = math.prod(int(d) for d in shapes[0][1].split(",") if d)
    ker = math.prod(int(d) for d in shapes[2][1].split(",") if d)
    # flops ~ 2 * result_elems * kernel_elems / out_channels
    out_ch = int(shapes[0][1].split(",")[-1]) if shapes[0][1] else 1
    return 2.0 * res * ker / max(out_ch, 1)


def _trip_count(while_rhs: str, cond: Optional[Computation]) -> int:
    """Prefer XLA's known_trip_count annotation; fall back to the largest
    integer constant in the loop condition (the scan counter bound)."""
    m = re.search(r'"known_trip_count":\{"n":"(\d+)"\}', while_rhs)
    if m:
        return int(m.group(1))
    best = 1
    if cond is not None:
        for ins in cond.instructions:
            for mm in re.finditer(r"constant\((\d+)\)", ins.rhs):
                best = max(best, int(mm.group(1)))
    return best


@dataclass
class Stats:
    flops: float = 0.0
    bytes_accessed: float = 0.0
    collectives: Dict[str, float] = field(default_factory=dict)

    def scaled(self, k: float) -> "Stats":
        return Stats(self.flops * k, self.bytes_accessed * k,
                     {n: v * k for n, v in self.collectives.items()})

    def __iadd__(self, o: "Stats"):
        self.flops += o.flops
        self.bytes_accessed += o.bytes_accessed
        for n, v in o.collectives.items():
            self.collectives[n] = self.collectives.get(n, 0.0) + v
        return self

    @property
    def collective_bytes(self) -> float:
        return float(sum(self.collectives.values()))


def _called(rhs: str, attr: str) -> Optional[str]:
    m = re.search(attr + r"=(%[\w.\-]+)", rhs)
    return m.group(1) if m else None


def analyze(text: str) -> Stats:
    comps = parse_hlo(text)
    # instruction-name -> result bytes / dims (operand resolution)
    defined: Dict[str, int] = {}
    dims_of: Dict[str, list] = {}
    for comp in comps.values():
        for ins in comp.instructions:
            defined[ins.name] = ins.result_bytes
            rd = ins.result_dims
            if rd is not None:
                dims_of[ins.name] = rd

    memo: Dict[str, Stats] = {}

    def walk(name: str) -> Stats:
        if name in memo:
            return memo[name]
        memo[name] = Stats()  # cycle guard
        comp = comps.get(name)
        if comp is None:
            return memo[name]
        total = Stats()
        for ins in comp.instructions:
            op = ins.op
            rhs = ins.rhs
            if op == "while":
                body = _called(rhs, "body")
                cond = _called(rhs, "condition")
                trips = _trip_count(rhs, comps.get(cond))
                inner = Stats()
                if body:
                    inner += walk(body)
                if cond in comps:
                    inner += walk(cond)
                total += inner.scaled(max(trips, 1))
                continue
            if op in ("fusion", "call", "custom-call", "map", "reduce",
                      "reduce-window", "scatter", "select-and-scatter",
                      "sort", "conditional"):
                # fusion bodies: count dots inside (rare), skip their memory
                # (fused intermediates never hit HBM — the fusion line itself
                # contributes its operand/result bytes below)
                callee = _called(rhs, "calls") or _called(rhs, "to_apply")
                if callee and callee in comps:
                    inner = walk(callee)
                    total += Stats(inner.flops, 0.0, dict(inner.collectives))
            if op == "dot":
                total += Stats(flops=_dot_flops(ins, dims_of))
            elif op == "convolution":
                total += Stats(flops=_conv_flops(rhs))
            m = re.search(r"\b(all-gather|all-reduce|reduce-scatter|"
                          r"all-to-all|collective-permute)(-start)?\(", rhs)
            if m and "-done(" not in rhs:
                total += Stats(collectives={m.group(1): float(ins.result_bytes)})
            # memory: result + resolvable operands (top-level ops only)
            opnds = _OPND_RE.findall(rhs.split("(", 1)[1]) if "(" in rhs else []
            if op == "dynamic-update-slice":
                # in-place: traffic = slice written (+read), not the buffer
                upd = defined.get(opnds[1], 0) if len(opnds) > 1 else 0
                total += Stats(bytes_accessed=float(2 * upd))
            elif op == "dynamic-slice":
                total += Stats(bytes_accessed=float(2 * ins.result_bytes))
            elif op == "fusion":
                # in-place loop-stash fusions (DUS pattern): an operand the
                # same size as the result is aliased, traffic is only the
                # update inputs — count those twice (read + write)
                ob = [defined.get(o, 0) for o in opnds[:8]]
                if ins.result_bytes > (64 << 20) and ins.result_bytes in ob:
                    others = sum(b for b in ob if b != ins.result_bytes)
                    total += Stats(bytes_accessed=float(2 * others))
                else:
                    total += Stats(
                        bytes_accessed=float(ins.result_bytes + sum(ob)))
            else:
                opnd_bytes = sum(defined.get(o, 0) for o in opnds[:8])
                total += Stats(
                    bytes_accessed=float(ins.result_bytes + opnd_bytes))
        memo[name] = total
        return total

    return walk("ENTRY")
