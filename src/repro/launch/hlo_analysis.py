"""Post-compile HLO analysis: FLOPs, HBM bytes and collective traffic with
while-loop awareness.

Why not ``compiled.cost_analysis()`` alone: XLA's HloCostAnalysis counts a
``while`` body **once**, but scan-over-layers executes it ``trip_count``
times — for a 32-layer scanned model that mis-counts compute by ~30×
(verified in tests/test_hlo_analysis.py). This module parses
``compiled.as_text()`` (post-SPMD-partitioning):

  * per-computation symbol tables resolve operand shapes (operand types are
    not inlined in this dump format),
  * while trip counts come from the ``known_trip_count`` backend_config XLA
    attaches to scan-derived loops (fallback: the constant bound in the
    condition computation),
  * per-computation FLOPs (dot contractions + elementwise), HBM bytes
    (operand+result bytes at fusion boundaries — HloCostAnalysis semantics)
    and collective wire traffic (ring-algorithm factors on replica-group
    size) are multiplied up the call graph.

All numbers are per-device (the partitioned module is per-device).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "power",
    "exponential", "log", "rsqrt", "sqrt", "tanh", "logistic", "negate",
    "compare", "select", "and", "or", "xor", "abs", "floor", "ceil",
    "cosine", "sine", "remainder", "atan2", "cbrt", "erf", "sign",
    "expm1", "log1p", "round-nearest-afz", "round-nearest-even", "clamp",
}
_BYTES_OPS = {
    "fusion", "dot", "copy", "transpose", "gather", "scatter", "reduce",
    "convert", "slice", "dynamic-slice", "dynamic-update-slice",
    "concatenate", "pad", "sort", "reduce-window", "select-and-scatter",
    "broadcast", "cholesky", "triangular-solve",
}


def _parse_shapes(s: str) -> List[Tuple[str, List[int]]]:
    out = []
    for m in _SHAPE_RE.finditer(s):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        out.append((dt, [int(d) for d in dims.split(",")] if dims else []))
    return out


def _numel(dims: List[int]) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


def _shapes_bytes(shapes: List[Tuple[str, List[int]]]) -> int:
    return sum(_numel(d) * _DTYPE_BYTES[t] for t, d in shapes)


def _group_size(line: str, total_devices: int) -> int:
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[", line)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    return total_devices


def _wire_factor(op: str, n: int) -> float:
    if n <= 1:
        return 0.0
    if op == "all-reduce":
        return 2.0 * (n - 1) / n
    if op == "all-gather":
        return (n - 1) / n
    if op == "reduce-scatter":
        return float(n - 1)
    if op == "all-to-all":
        return (n - 1) / n
    return 1.0   # collective-permute


@dataclasses.dataclass
class HloStats:
    flops: float = 0.0
    bytes: float = 0.0
    wire_bytes: float = 0.0
    op_bytes: Dict[str, float] = dataclasses.field(default_factory=dict)
    op_counts: Dict[str, int] = dataclasses.field(default_factory=dict)
    while_loops: Dict[str, int] = dataclasses.field(default_factory=dict)

    def add_coll(self, op: str, bytes_: float, count: int = 1):
        self.op_bytes[op] = self.op_bytes.get(op, 0.0) + bytes_
        self.op_counts[op] = self.op_counts.get(op, 0) + count


@dataclasses.dataclass
class _Comp:
    name: str
    header: str
    lines: List[str]
    symbols: Dict[str, List[Tuple[str, List[int]]]] = None  # name -> shapes
    param_names: List[str] = None          # header order
    param_effective: List[int] = None      # bytes actually read per param

    def build_symbols(self):
        self.symbols = {}
        self.param_names = []
        # Parameters from the header: "(p0: f32[1,2], p1: (f32[3], s32[]))"
        hdr = self.header[self.header.find("("):]
        for m in re.finditer(r"([\w\.\-_]+)\s*:\s*((?:\([^)]*\))|(?:[^,()]+))",
                             hdr):
            self.symbols[m.group(1)] = _parse_shapes(m.group(2))
            self.param_names.append(m.group(1))
        for line in self.lines:
            if "=" not in line:
                continue
            lhs, rhs = line.split("=", 1)
            name = lhs.strip().lstrip("%").split()[0] if lhs.strip() else None
            if not name:
                continue
            # Result type: everything before the opcode's '('
            om = re.search(r"([a-z][\w\-]*)\(", rhs)
            type_str = rhs[:om.start()] if om else rhs
            self.symbols[name] = _parse_shapes(type_str)
        self._build_effective()

    def _build_effective(self):
        """Effective bytes read per parameter: a fusion param consumed only
        by dynamic-slice reads only the slice (stacked scanned weights!) —
        matching HloCostAnalysis operand-utilization semantics."""
        self.param_effective = []
        for pname in self.param_names:
            full = _shapes_bytes(self.symbols.get(pname, []))
            uses, ds_bytes, only_ds = 0, 0, True
            pat = re.compile(r"%?" + re.escape(pname) + r"\b")
            for line in self.lines:
                rhs = line.split("=", 1)[1] if "=" in line else line
                if f"parameter(" in rhs and line.strip().lstrip("%").startswith(pname):
                    continue
                hits = pat.findall(rhs)
                if not hits:
                    continue
                uses += len(hits)
                dm = re.search(
                    r"dynamic-slice\(%?" + re.escape(pname) +
                    r"\b.*dynamic_slice_sizes=\{([\d,]+)\}", rhs)
                if dm:
                    dims = [int(d) for d in dm.group(1).split(",")]
                    shapes = self.symbols.get(pname, [])
                    dt = shapes[0][0] if shapes else "f32"
                    ds_bytes += _numel(dims) * _DTYPE_BYTES.get(dt, 4)
                else:
                    only_ds = False
            eff = ds_bytes if (uses and only_ds and ds_bytes) else full
            self.param_effective.append(eff)

    def shapes_of(self, operand: str) -> List[Tuple[str, List[int]]]:
        return self.symbols.get(operand.lstrip("%"), [])


_COMP_HDR = re.compile(r"^(ENTRY\s+)?%?([\w\.\-_]+)\s*\(")


def _split(hlo: str) -> Dict[str, _Comp]:
    comps: Dict[str, _Comp] = {}
    current: Optional[_Comp] = None
    for raw in hlo.splitlines():
        line = raw.strip()
        if line.endswith("{") and not line.startswith("ROOT"):
            m = _COMP_HDR.match(line)
            if m:
                current = _Comp(m.group(2), line, [])
                comps[current.name] = current
                continue
        if line.startswith("}"):
            current = None
            continue
        if current is not None and line:
            current.lines.append(line)
    for c in comps.values():
        c.build_symbols()
    return comps


def _opcode(rhs: str) -> Optional[str]:
    m = re.search(r"\b([a-z][\w\-]*)\(", rhs)
    return m.group(1) if m else None


_OPERANDS_RE = re.compile(r"\(([^)]*)\)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_TRIP_RE = re.compile(r'known_trip_count[^0-9]*(\d+)')


def _operand_names(inner: str) -> List[str]:
    """Operand symbol names from an op's argument list ("%a, %b")."""
    return re.findall(r"%([\w\.\-_]+)", inner)


def _dot_flops(line: str, comp: _Comp) -> float:
    rhs = line.split("=", 1)[1]
    result = _parse_shapes(rhs[:rhs.find(" dot(") + 1])
    if not result:
        return 0.0
    result_numel = _numel(result[0][1])
    ops_m = _OPERANDS_RE.search(rhs[rhs.find(" dot("):])
    cm = _CONTRACT_RE.search(line)
    k = 1
    if ops_m and cm is not None and cm.group(1):
        inner = ops_m.group(1)
        # Inline-typed dumps carry the lhs shape right in the operand list;
        # otherwise resolve the first operand via the symbol table.
        inline = _parse_shapes(inner)
        if inline:
            lhs_dims = inline[0][1]
        else:
            names = _operand_names(inner)
            lhs_shapes = comp.shapes_of(names[0]) if names else []
            lhs_dims = lhs_shapes[0][1] if lhs_shapes else []
        for idx in cm.group(1).split(","):
            i = int(idx)
            if i < len(lhs_dims):
                k *= lhs_dims[i]
    return 2.0 * result_numel * k


def _trip_count(line: str, comps: Dict[str, _Comp], cond: str) -> int:
    m = _TRIP_RE.search(line)
    if m:
        return int(m.group(1))
    best = 1
    for l in comps.get(cond, _Comp("", "", [])).lines:
        for mm in re.finditer(r"constant\((\d+)\)", l):
            best = max(best, int(mm.group(1)))
    return best


def analyze_hlo(hlo_text: str, total_devices: int) -> HloStats:
    comps = _split(hlo_text)
    stats = HloStats()

    parents: Dict[str, List[Tuple[str, int]]] = {n: [] for n in comps}
    fusion_internal: set = set()
    for name, comp in comps.items():
        for line in comp.lines:
            if "while(" in line:
                cm = re.search(r"condition=%?([\w\.\-_]+)", line)
                bm = re.search(r"body=%?([\w\.\-_]+)", line)
                if cm and bm:
                    tc = _trip_count(line, comps, cm.group(1))
                    stats.while_loops[bm.group(1)] = tc
                    parents.setdefault(bm.group(1), []).append((name, tc))
                    parents.setdefault(cm.group(1), []).append((name, tc))
                    continue
            for m in re.finditer(r"(?:calls|to_apply|branch_computations)="
                                 r"\{?%?([\w\.\-_,% ]+)\}?", line):
                for callee in re.split(r"[,\s]+", m.group(1)):
                    callee = callee.strip().lstrip("%")
                    if callee in comps:
                        parents.setdefault(callee, []).append((name, 1))
                        if "fusion(" in line:
                            fusion_internal.add(callee)

    multipliers: Dict[str, float] = {
        n: (1.0 if not parents.get(n) else 0.0) for n in comps}
    for _ in range(24):
        changed = False
        for name in comps:
            ps = parents.get(name)
            if not ps:
                continue
            mult = max(multipliers[p] * tc for p, tc in ps)
            if mult != multipliers[name]:
                multipliers[name] = mult
                changed = True
        if not changed:
            break

    for name, comp in comps.items():
        mult = multipliers.get(name, 1.0) or 1.0
        internal = name in fusion_internal
        for line in comp.lines:
            rhs = line.split("=", 1)[1] if "=" in line else line
            opcode = _opcode(rhs)
            if opcode is None:
                continue
            base = opcode.replace("-start", "").replace("-done", "")
            if base in _COLLECTIVES:
                if opcode.endswith("-done"):
                    continue
                b = _shapes_bytes(_parse_shapes(
                    rhs[:rhs.find(opcode + "(")]))
                n = _group_size(line, total_devices)
                wire = b * _wire_factor(base, n) * mult
                stats.wire_bytes += wire
                stats.add_coll(base, wire, int(mult))
                continue
            if opcode == "dot":
                stats.flops += _dot_flops(line, comp) * mult
            elif opcode in _ELEMENTWISE:
                shapes = _parse_shapes(rhs[:rhs.find(opcode + "(")])
                if shapes:
                    stats.flops += _numel(shapes[0][1]) * mult
            # Unfused elementwise ops (e.g. CPU-backend parallel calls) are
            # charged operand+result bytes too — HloCostAnalysis semantics.
            if not internal and (opcode in _BYTES_OPS or
                                 opcode in _ELEMENTWISE):
                result_b = _shapes_bytes(_parse_shapes(
                    rhs[:rhs.find(opcode + "(")]))
                ops_m = _OPERANDS_RE.search(rhs[rhs.find(opcode + "("):])
                operands = _operand_names(ops_m.group(1)) if ops_m else []
                operand_b = 0
                callee = None
                if opcode == "fusion":
                    cm2 = re.search(r"calls=%?([\w\.\-_]+)", line)
                    callee = comps.get(cm2.group(1)) if cm2 else None
                for i, o in enumerate(operands):
                    full = _shapes_bytes(comp.shapes_of(o))
                    if callee is not None and \
                            i < len(callee.param_effective):
                        full = min(full, callee.param_effective[i]) \
                            if full else callee.param_effective[i]
                    operand_b += full
                stats.bytes += (result_b + operand_b) * mult
    return stats


# Back-compat alias.
analyze_collectives = analyze_hlo
