"""HLO roofline analyzer: FLOPs / HBM bytes / collective bytes from the
text of a compiled HLO module, and a three-term roofline over them.

Why not ``compiled.cost_analysis()``: XLA's analyzer counts a ``while``
body **once**, so anything scanned over layers (our entire layer stack —
see models/transformer.py) is undercounted by ``num_layers``×. This parser
walks computations recursively and

- multiplies while-loop bodies by the trip count (XLA's own
  ``known_trip_count`` backend_config when present, else the constant in
  the loop-condition ``compare``);
- weights ``conditional`` branches by 1/n_branches (the chunked causal
  attention skips above-diagonal KV blocks with ``lax.cond``; averaging
  recovers the expected triangle cost);
- counts HBM traffic only on traffic-bearing ops (dot / convolution /
  custom-call: operand + output bytes). Pure elementwise chains are
  modeled as fused away — 0 bytes — matching how XLA:TPU emits them;
- accumulates collective bytes (all-reduce / all-gather / reduce-scatter /
  all-to-all / collective-permute) separately, for the ICI/DCN term.

The parser targets post-optimization ``compiled.as_text()`` output; it is
deliberately line-based (one instruction per line) and shape-driven, not a
full HLO grammar.
"""
from __future__ import annotations

import dataclasses
import json
import re

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3": 1, "f8e5m2fnuz": 1,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]+(?:[a-z0-9]*)?|pred)\[([\d,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.+?)\s([a-z][a-z0-9\-]*)\((.*)$")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s*\(.*\)\s*->.*{\s*$")
_CALLED_RE = re.compile(r"(?:calls|to_apply|body)=%([\w.\-]+)")
_COND_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_COND_TF_RE = re.compile(
    r"true_computation=%([\w.\-]+).*false_computation=%([\w.\-]+)")
_TRIP_RE = re.compile(r'known_trip_count[="\{\s]+n["\s:]+"?(\d+)')
_CONDITION_RE = re.compile(r"condition=%([\w.\-]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute", "collective-broadcast")
# ops whose operand/output bytes hit HBM even when surrounded by fusions
_TRAFFIC_OPS = ("dot", "convolution", "custom-call")


def _shapes_bytes(text: str) -> float:
    """Total bytes of every dtype[dims] shape literal in `text`."""
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def _shape_dims(tok: tuple[str, str]) -> list[int]:
    return [int(d) for d in tok[1].split(",") if d]


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0           # HBM traffic (traffic-bearing ops only)
    coll_bytes: float = 0.0      # collective payload bytes
    dots: list = dataclasses.field(default_factory=list)   # (flops, label)
    colls: list = dataclasses.field(default_factory=list)  # (bytes, label)

    def add(self, other: "Cost", scale: float = 1.0) -> None:
        self.flops += scale * other.flops
        self.bytes += scale * other.bytes
        self.coll_bytes += scale * other.coll_bytes
        self.dots.extend((f * scale, lbl) for f, lbl in other.dots)
        self.colls.extend((b * scale, lbl) for b, lbl in other.colls)


class HLOAnalyzer:
    """Parse an HLO module's text into per-computation :class:`Cost`."""

    def __init__(self, hlo_text: str):
        self.computations: dict[str, list[str]] = {}
        self.entry: str | None = None
        self._cost_cache: dict[str, Cost] = {}
        self._parse(hlo_text)

    # ------------------------------------------------------------- parsing
    def _parse(self, text: str) -> None:
        current: str | None = None
        for line in text.splitlines():
            m = _COMP_RE.match(line)
            if m and line.rstrip().endswith("{"):
                current = m.group(1)
                self.computations[current] = []
                if line.lstrip().startswith("ENTRY"):
                    self.entry = current
                continue
            if current is None:
                continue
            if line.strip() == "}":
                current = None
                continue
            self.computations[current].append(line)
        if self.entry is None and self.computations:
            # unoptimized modules sometimes drop the ENTRY marker; take the
            # computation the module header names, else the last one
            self.entry = list(self.computations)[-1]

    # ------------------------------------------------------------- costing
    def entry_cost(self) -> Cost:
        assert self.entry is not None, "no computations parsed"
        c = self.computation_cost(self.entry)
        c.dots.sort(key=lambda t: -t[0])
        c.colls.sort(key=lambda t: -t[0])
        return c

    def computation_cost(self, name: str) -> Cost:
        if name in self._cost_cache:
            return self._cost_cache[name]
        # memoize a zero first: malformed self-recursive graphs terminate
        self._cost_cache[name] = Cost()
        lines = self.computations.get(name, ())
        # instruction name -> result type: operands are printed by name
        # only (``dot(%x.1, %y.1)``), so their shapes come from the
        # instruction that defines them
        types = {m.group(1): m.group(2)
                 for m in map(_INSTR_RE.match, lines) if m}
        total = Cost()
        for line in lines:
            total.add(self._instruction_cost(line, types))
        self._cost_cache[name] = total
        return total

    @staticmethod
    def _operand_types(rest: str, types: dict[str, str]) -> list[str]:
        """Types of an instruction's operands, in order: each operand of
        ``rest`` (the text after the opcode's parenthesis) is resolved by
        name through the computation's definitions."""
        args = rest.split(")", 1)[0]
        return [types[n] for n in _OPERAND_RE.findall(args) if n in types]

    def _instruction_cost(self, line: str, types: dict[str, str]) -> Cost:
        m = _INSTR_RE.match(line)
        if not m:
            return Cost()
        _, result_type, opcode, rest = m.groups()
        c = Cost()
        if opcode == "dot":
            self._dot_cost(result_type, self._operand_types(rest, types), c,
                           line)
        elif opcode in ("convolution", "custom-call"):
            # convolution window sizes are not recovered; traffic only
            c.bytes += _shapes_bytes(result_type) + sum(
                _shapes_bytes(t) for t in self._operand_types(rest, types))
            for sub in _CALLED_RE.findall(line):
                c.add(self.computation_cost(sub))
        elif opcode in ("fusion", "call"):
            for sub in _CALLED_RE.findall(line):
                c.add(self.computation_cost(sub))
        elif opcode == "while":
            trip = self._trip_count(line)
            body = _CALLED_RE.search(line)
            if body:
                c.add(self.computation_cost(body.group(1)), scale=trip)
        elif opcode == "conditional":
            branches = self._branches(line)
            if branches:
                w = 1.0 / len(branches)
                for b in branches:
                    c.add(self.computation_cost(b), scale=w)
        elif opcode in _COLLECTIVES:
            b = _shapes_bytes(result_type)
            c.coll_bytes += b
            c.colls.append((b, f"{opcode} {result_type.strip()}"))
        return c

    def _dot_cost(self, result_type: str, operand_types: list[str], c: Cost,
                  line: str) -> None:
        out_shape = _SHAPE_RE.search(result_type)
        operands = [m.groups() for m in map(_SHAPE_RE.search, operand_types)
                    if m]
        if not out_shape or not operands:
            return
        out_dims = _shape_dims(out_shape.groups())
        lhs_dims = _shape_dims(operands[0])
        contract = _CONTRACT_RE.search(line)
        k = 1
        if contract:
            for d in contract.group(1).split(","):
                if d:
                    k *= lhs_dims[int(d)]
        numel_out = 1
        for d in out_dims:
            numel_out *= d
        flops = 2.0 * numel_out * k
        c.flops += flops
        # traffic: both operands read + output written
        op_bytes = sum(
            _shapes_bytes(f"{dt}[{dims}]") for dt, dims in operands[:2])
        c.bytes += op_bytes + _shapes_bytes(result_type)
        c.dots.append((flops, f"dot {result_type.strip()}"))

    def _trip_count(self, line: str) -> int:
        m = _TRIP_RE.search(line)
        if m:
            return int(m.group(1))
        # fall back to the loop condition's compare-against-constant
        cond = _CONDITION_RE.search(line)
        if cond:
            for cl in self.computations.get(cond.group(1), ()):
                cm = re.search(r"constant\((\d+)\)", cl)
                if cm:
                    return int(cm.group(1))
        return 1

    @staticmethod
    def _branches(line: str) -> list[str]:
        m = _COND_BRANCHES_RE.search(line)
        if m:
            return re.findall(r"%([\w.\-]+)", m.group(1))
        m = _COND_TF_RE.search(line)
        if m:
            return [m.group(1), m.group(2)]
        return []


# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------
# v5e per-chip numbers; keep in sync with serving.profiler.HardwareProfile
# (duplicated here so dist has no import edge into serving).
CHIP_FLOPS = 197e12          # bf16 peak, per chip
CHIP_HBM_BW = 819e9          # bytes/s
CHIP_ICI_BW = 50e9           # per-link bytes/s


@dataclasses.dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str              # "compute" | "memory" | "collective"
    flops: float                 # per-device HLO flops
    bytes: float                 # per-device HBM bytes
    coll_bytes: float            # per-device collective bytes
    model_flops: float           # analytic "useful" flops (all devices)
    useful_ratio: float          # model_flops / (flops * chips)
    top_dots: list
    top_colls: list

    def to_json(self) -> str:
        d = dataclasses.asdict(self)
        d["top_dots"] = d["top_dots"][:5]
        d["top_colls"] = d["top_colls"][:5]
        return json.dumps(d)


def roofline(hlo_text: str, chips: int, model_flops: float,
             chip_flops: float = CHIP_FLOPS,
             hbm_bw: float = CHIP_HBM_BW,
             ici_bw: float = CHIP_ICI_BW) -> RooflineTerms:
    """Three-term roofline for one compiled (per-device, SPMD-partitioned)
    module: ideal compute time, HBM time, and collective time, with the
    dominant term named. ``model_flops`` is the analytic whole-job FLOP
    count, giving ``useful_ratio`` (how much of what the graph computes is
    algorithmically necessary; >1 means the HLO undercounts, <1 overhead)."""
    c = HLOAnalyzer(hlo_text).entry_cost()
    compute_s = c.flops / chip_flops
    memory_s = c.bytes / hbm_bw
    collective_s = c.coll_bytes / ici_bw
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    useful = model_flops / max(c.flops * max(chips, 1), 1.0)
    return RooflineTerms(
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        bottleneck=bottleneck, flops=c.flops, bytes=c.bytes,
        coll_bytes=c.coll_bytes, model_flops=model_flops,
        useful_ratio=useful, top_dots=c.dots[:8], top_colls=c.colls[:8])
