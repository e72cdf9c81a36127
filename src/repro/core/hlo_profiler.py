"""Post-SPMD HLO text profiler: the FireBridge "bus transaction monitor"
adapted to XLA, and the engine behind §Roofline.

Why text parsing: ``compiled.cost_analysis()`` counts while-loop (scan) bodies
exactly ONCE — a 40-layer scanned model reports ~1 layer of FLOPs.  This
module parses ``compiled.as_text()`` (post-SPMD, so shapes are per-device and
GSPMD-inserted collectives are visible), builds the computation call graph,
extracts trip counts from while-condition constants, and multiplies per-op
costs through the graph.  It also emits the per-op collective "transaction
stream" consumed by the congestion emulator (core/congestion.py) and the
§Perf diagnostics (duplicate all-gathers, layout-change copies, ...).

Cost models (documented methodology — see EXPERIMENTS.md §Roofline):
  * FLOPs: 2 * out_elems * contracted_elems for every ``dot`` (+ conv),
    trip-multiplied.  Elementwise flops are excluded (matmul-dominated
    workloads; cost_analysis() is reported alongside for reference).
  * HBM traffic: for every non-free op, operand+result bytes at fusion
    granularity (XLA fusions are memory-bound kernels whose HBM traffic is
    their operands+outputs).  dynamic-(update-)slice counts slice bytes only
    (XLA performs them in place).
  * Collective bytes per device: ring formulas — all-reduce 2(g-1)/g * n,
    all-gather/reduce-scatter/all-to-all (g-1)/g * n, collective-permute n.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e5m2": 1, "f8e4m3fn": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(.*?\)|\S+)\s+([\w\-]+)\((.*)$")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->")

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_FREE_OPS = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "reshape", "after-all", "partition-id", "replica-id", "iota",
    "while", "conditional", "call", "custom-call",
}


def _type_bytes_elems(type_str: str) -> Tuple[int, int]:
    total_b = total_e = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        elems = 1
        if dims:
            for d in dims.split(","):
                elems *= int(d)
        total_e += elems
        total_b += elems * _DTYPE_BYTES[dt]
    return total_b, total_e


@dataclasses.dataclass
class Op:
    name: str
    type_str: str
    opcode: str
    operands: List[str]
    attrs: str
    is_root: bool = False

    @property
    def result_bytes(self) -> int:
        return _type_bytes_elems(self.type_str)[0]

    @property
    def result_elems(self) -> int:
        return _type_bytes_elems(self.type_str)[1]

    def result_dims(self) -> List[int]:
        m = _SHAPE_RE.search(self.type_str)
        if not m:
            return []
        return [int(d) for d in m.group(2).split(",")] if m.group(2) else []


@dataclasses.dataclass
class CollectiveRecord:
    kind: str
    op_name: str
    computation: str
    shape: str
    bytes_full: int          # tensor bytes (per device view)
    bytes_moved: int         # ring-model bytes over the wire per device
    group_size: int
    multiplier: int

    @property
    def total_bytes(self) -> int:
        return self.bytes_moved * self.multiplier


@dataclasses.dataclass
class DotRecord:
    op_name: str
    computation: str
    shape: str
    flops: float             # per execution
    multiplier: int
    jax_path: str            # from metadata op_name (source attribution)

    @property
    def total_flops(self) -> float:
        return self.flops * self.multiplier


@dataclasses.dataclass
class Profile:
    flops: float
    traffic_bytes: float
    collective_bytes: float
    collectives: List[CollectiveRecord]
    dot_count: int
    warnings: List[str]
    per_comp_mult: Dict[str, int]
    dots: List[DotRecord] = dataclasses.field(default_factory=list)

    def collective_summary(self) -> Dict[str, Tuple[int, float]]:
        agg: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
        for c in self.collectives:
            agg[c.kind][0] += c.multiplier
            agg[c.kind][1] += c.total_bytes
        return {k: (int(v[0]), v[1]) for k, v in agg.items()}


def _parse_computations(text: str) -> Dict[str, Tuple[List[Op], bool]]:
    comps: Dict[str, Tuple[List[Op], bool]] = {}
    cur: Optional[str] = None
    ops: List[Op] = []
    is_entry = False
    for line in text.splitlines():
        if cur is None:
            if line.rstrip().endswith("{"):
                m = _COMP_HDR_RE.match(line.strip())
                if m:
                    cur = m.group(1)
                    is_entry = line.lstrip().startswith("ENTRY")
                    ops = []
            continue
        if line.strip() == "}":
            comps[cur] = (ops, is_entry)
            cur = None
            continue
        m = _OP_RE.match(line)
        if m:
            name, tstr, opcode, rest = m.groups()
            operand_refs = re.findall(r"%([\w.\-]+)", rest)
            ops.append(Op(name=name, type_str=tstr, opcode=opcode,
                          operands=operand_refs, attrs=rest,
                          is_root="ROOT" in line[:12]))
    return comps


def _trip_count(cond_ops: List[Op]) -> int:
    best = 1
    for op in cond_ops:
        if op.opcode == "constant":
            m = re.search(r"constant\((\d+)\)", op.attrs or "")
            # attrs holds text after "constant(" already split; reconstruct:
            if not m:
                m = re.search(r"^(\d+)\)", op.attrs)
            if m:
                best = max(best, int(m.group(1)))
    return best


def _group_size(attrs: str, world: int) -> int:
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", attrs)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", attrs)
    if m:
        return len(m.group(1).split(","))
    return world


def _dot_flops(op: Op, by_name: Dict[str, Op], warnings: List[str]) -> float:
    out_elems = op.result_elems
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.attrs)
    lhs = by_name.get(op.operands[0]) if op.operands else None
    if lhs is None or m is None:
        warnings.append(f"dot {op.name}: missing lhs shape; counted 2*out")
        return 2.0 * out_elems
    dims = lhs.result_dims()
    contracted = 1
    if m.group(1):
        for i in m.group(1).split(","):
            idx = int(i)
            if idx < len(dims):
                contracted *= dims[idx]
    return 2.0 * out_elems * contracted


def _op_traffic(op: Op, by_name: Dict[str, Op]) -> int:
    oc = op.opcode
    if oc in _FREE_OPS or oc in _COLLECTIVES:
        return 0
    if oc in ("dynamic-update-slice",):
        upd = by_name.get(op.operands[1]) if len(op.operands) > 1 else None
        return 2 * (upd.result_bytes if upd else 0)
    if oc in ("dynamic-slice", "copy", "transpose", "broadcast", "convert"):
        return 2 * op.result_bytes
    # general: operands + result
    total = op.result_bytes
    for o in op.operands:
        src = by_name.get(o)
        if src is not None:
            total += src.result_bytes
    return total


def profile_hlo(text: str, world_size: int) -> Profile:
    comps = _parse_computations(text)
    warnings: List[str] = []
    entry = None
    for name, (_, is_entry) in comps.items():
        if is_entry:
            entry = name
    if entry is None:
        raise ValueError("no ENTRY computation found")

    # call graph edges
    flops_mult: Dict[str, float] = defaultdict(float)
    bytes_mult: Dict[str, float] = defaultdict(float)
    flops_mult[entry] = 1.0
    bytes_mult[entry] = 1.0

    # process in BFS order from entry
    order = [entry]
    seen = {entry}
    i = 0
    while i < len(order):
        comp = order[i]
        i += 1
        ops, _ = comps.get(comp, ([], False))
        fm, bm = flops_mult[comp], bytes_mult[comp]
        for op in ops:
            a = op.attrs
            if op.opcode == "while":
                mb = re.search(r"body=%?([\w.\-]+)", a)
                mc = re.search(r"condition=%?([\w.\-]+)", a)
                if mb and mc:
                    trip = _trip_count(comps.get(mc.group(1), ([], False))[0])
                    for child, mult_f, mult_b in (
                            (mb.group(1), fm * trip, bm * trip),
                            (mc.group(1), 0.0, 0.0)):
                        flops_mult[child] += mult_f
                        bytes_mult[child] += mult_b
                        if child not in seen:
                            seen.add(child)
                            order.append(child)
            elif op.opcode == "fusion":
                mf = re.search(r"calls=%?([\w.\-]+)", a)
                if mf:
                    child = mf.group(1)
                    flops_mult[child] += fm     # dots inside fusions count
                    # bytes counted at the callsite, not inside
                    if child not in seen:
                        seen.add(child)
                        order.append(child)
            elif op.opcode in ("call", "async-start"):
                mf = re.search(r"(?:to_apply|calls)=%?([\w.\-]+)", a)
                if mf:
                    child = mf.group(1)
                    flops_mult[child] += fm
                    bytes_mult[child] += bm
                    if child not in seen:
                        seen.add(child)
                        order.append(child)
            elif op.opcode == "conditional":
                for mf in re.finditer(
                        r"(?:true_computation|false_computation|branch_computations=\{[^}]*)=?%?([\w.\-]+)", a):
                    child = mf.group(1)
                    if child in comps:
                        flops_mult[child] += fm
                        bytes_mult[child] += bm
                        if child not in seen:
                            seen.add(child)
                            order.append(child)

    total_flops = 0.0
    total_traffic = 0.0
    total_coll = 0.0
    dot_count = 0
    coll_records: List[CollectiveRecord] = []
    dot_records: List[DotRecord] = []

    for comp, (ops, _) in comps.items():
        fm = flops_mult.get(comp, 0.0)
        bm = bytes_mult.get(comp, 0.0)
        if fm == 0 and bm == 0:
            continue
        by_name = {op.name: op for op in ops}
        for op in ops:
            oc = op.opcode
            base = oc.replace("-start", "")
            if base in _COLLECTIVES and not oc.endswith("-done"):
                g = _group_size(op.attrs, world_size)
                if base == "all-gather":
                    nb = op.result_bytes
                    moved = nb * (g - 1) // max(g, 1)
                elif base == "reduce-scatter":
                    src = by_name.get(op.operands[0]) if op.operands else None
                    nb = src.result_bytes if src else op.result_bytes * g
                    moved = nb * (g - 1) // max(g, 1)
                elif base == "all-reduce":
                    nb = op.result_bytes
                    moved = 2 * nb * (g - 1) // max(g, 1)
                elif base == "all-to-all":
                    nb = op.result_bytes
                    moved = nb * (g - 1) // max(g, 1)
                else:  # collective-permute
                    nb = op.result_bytes
                    moved = nb
                rec = CollectiveRecord(
                    kind=base, op_name=op.name, computation=comp,
                    shape=op.type_str, bytes_full=nb, bytes_moved=moved,
                    group_size=g, multiplier=int(max(bm, fm)))
                coll_records.append(rec)
                total_coll += rec.total_bytes
                continue
            if oc in ("dot", "convolution"):
                dot_count += 1
                if fm:
                    fl = _dot_flops(op, by_name, warnings)
                    total_flops += fm * fl
                    mpath = re.search(r'op_name="([^"]*)"', op.attrs)
                    dot_records.append(DotRecord(
                        op_name=op.name, computation=comp, shape=op.type_str,
                        flops=fl, multiplier=int(fm),
                        jax_path=mpath.group(1) if mpath else ""))
                if bm:
                    total_traffic += bm * _op_traffic(op, by_name)
                continue
            if bm:
                total_traffic += bm * _op_traffic(op, by_name)

    return Profile(flops=total_flops, traffic_bytes=total_traffic,
                   collective_bytes=total_coll, collectives=coll_records,
                   dot_count=dot_count, warnings=warnings,
                   per_comp_mult={k: int(v) for k, v in flops_mult.items()},
                   dots=dot_records)


# ---------------------------------------------------------------------------
# Roofline terms (TPU v5e target constants per the assignment)
# ---------------------------------------------------------------------------

PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW_PER_LINK = 50e9          # bytes/s per link


@dataclasses.dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float
    hlo_flops: float

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_ratio(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the compute roofline achievable if the program ran at
        the max(terms) bound: ideal_compute_time / bound_time."""
        ideal = self.model_flops / PEAK_FLOPS_BF16
        return ideal / self.bound_s if self.bound_s else 0.0


def roofline(profile: Profile, model_flops_per_device: float,
             n_links: int = 1) -> RooflineTerms:
    return RooflineTerms(
        compute_s=profile.flops / PEAK_FLOPS_BF16,
        memory_s=profile.traffic_bytes / HBM_BW,
        collective_s=profile.collective_bytes / (n_links * ICI_BW_PER_LINK),
        model_flops=model_flops_per_device,
        hlo_flops=profile.flops,
    )
