"""Trip-count-expanded HLO cost analysis.

``compiled.cost_analysis()`` visits while-loop bodies ONCE, so a model lowered
with lax.scan over L layers under-reports FLOPs/bytes/collectives by ~L x
(verified experimentally — see EXPERIMENTS.md §Dry-run). This module parses
``compiled.as_text()`` and expands costs through the call graph:

  cost(ENTRY) with  cost(while) = trip * cost(body) + trip * cost(cond)
                    cost(fusion/call) = cost at call site (+ dot/conv FLOPs
                                        recursively from the fused comp)

Counted:
  * FLOPs: dot (2*result_numel*K from lhs_contracting_dims), convolution
    (2*result*kernel_spatial*Cin/groups); elementwise ignored (sub-1%).
  * bytes (HBM-traffic model): result bytes once (the write) for every
    counted op, plus operand reads for dot/conv/fusion-boundaries/collectives
    (weights+activations striped from HBM); parameter/constant/tuple/gte/
    bitcast excluded; dynamic-update-slice counted as 2x update (in-place).
    Unfused elementwise chains overcount ~1.5x vs ideal TPU fusion — the
    model is kept consistent across all cells so §Perf deltas are valid.
  * collectives: ring-model per-device traffic by op type.

Trip counts: the while's condition computation contains
``constant(N)`` + ``compare direction=LT`` (lax.scan's canonical form);
fallback trip=1 with a warning flag.
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "f8e4m3": 1, "f8e3m4": 1, "s16": 2, "u16": 2, "bf16": 2,
    "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*(\([^)]*\)|[^\s]+)\s+([\w\-]+)(\(.*)$")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?(%[\w.\-]+)\s*\(.*\)\s*->")
_CALLS_RE = re.compile(r"(?:calls|body|condition|to_apply)=(%[\w.\-]+)")
_BODY_RE = re.compile(r"body=(%[\w.\-]+)")
_COND_RE = re.compile(r"condition=(%[\w.\-]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_OPERANDS_RE = re.compile(r"(%[\w.\-]+)")
_CONST_RE = re.compile(r"=\s*s32\[\]\s+constant\((\d+)\)")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_WINDOW_SIZE_RE = re.compile(r"window=\{size=([0-9x]+)")
_FEATURE_GROUPS_RE = re.compile(r"feature_group_count=(\d+)")

#: per-chip roofline peaks by ``jax.Device.device_kind``:
#: (dense bf16 FLOP/s, memory bytes/s)
PEAKS: Dict[str, Tuple[float, float]] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM
    "TPU v5 lite": (197e12, 819e9),
    # the XLA CPU backend: nominal host rates, no published peak -- they
    # only set the gemm/other split ratio of CPU runs
    "cpu": (5e10, 2e10),
}


def peak_rates(device_kind: str) -> Tuple[float, float]:
    """(FLOP/s, bytes/s) of one device of ``device_kind``; a kind missing
    from ``PEAKS`` is an error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no roofline peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                  "collective-permute")

_SKIP_BYTES_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
                   "bitcast", "after-all", "iota", "partition-id",
                   "replica-id"}


def _parse_shape(shape_str: str) -> List[Tuple[str, Tuple[int, ...]]]:
    out = []
    for m in _SHAPE_RE.finditer(shape_str):
        dt = m.group(1)
        if dt not in _DTYPE_BYTES:
            continue
        dims = tuple(int(d) for d in m.group(2).split(",")) \
            if m.group(2) else ()
        out.append((dt, dims))
    return out


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _parse_shape(shape_str):
        total += _DTYPE_BYTES[dt] * (math.prod(dims) if dims else 1)
    return total


def _shape_numel(shape_str: str) -> int:
    total = 0
    for _, dims in _parse_shape(shape_str):
        total += math.prod(dims) if dims else 1
    return total


@dataclass
class Instr:
    name: str
    shape: str
    op: str
    rest: str


@dataclass
class Computation:
    name: str
    instrs: List[Instr] = field(default_factory=list)
    shapes: Dict[str, str] = field(default_factory=dict)

    @property
    def has_dus(self) -> bool:
        return any(i.op == "dynamic-update-slice" for i in self.instrs)

    @property
    def has_slice_read(self) -> bool:
        return any(i.op in ("dynamic-slice", "gather") for i in self.instrs)

    def slice_read_bytes(self) -> float:
        return float(sum(_shape_bytes(i.shape) for i in self.instrs
                         if i.op in ("dynamic-slice", "gather")))

    def dus_update_bytes(self) -> float:
        """2x the update-slice bytes of every interior dynamic-update-slice
        (read update + write slice; the carried buffer itself never moves)."""
        total = 0.0
        for i in self.instrs:
            if i.op != "dynamic-update-slice":
                continue
            ops = _OPERANDS_RE.findall(i.rest.split("),")[0] + ")")
            if len(ops) >= 2 and ops[1] in self.shapes:
                total += 2.0 * _shape_bytes(self.shapes[ops[1]])
        return total


@dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    coll_bytes: Dict[str, float] = field(default_factory=dict)
    coll_counts: Dict[str, float] = field(default_factory=dict)
    unknown_trip_loops: int = 0
    # per-(op, shape) aggregated bytes / flops for §Perf debugging
    detail_bytes: Dict[str, float] = field(default_factory=dict)
    detail_flops: Dict[str, float] = field(default_factory=dict)

    def _dadd(self, d: Dict[str, float], key: str, v: float):
        d[key] = d.get(key, 0.0) + v

    def add(self, other: "Cost", mult: float = 1.0):
        self.flops += other.flops * mult
        self.bytes += other.bytes * mult
        for k, v in other.coll_bytes.items():
            self.coll_bytes[k] = self.coll_bytes.get(k, 0.0) + v * mult
        for k, v in other.coll_counts.items():
            self.coll_counts[k] = self.coll_counts.get(k, 0.0) + v * mult
        for k, v in other.detail_bytes.items():
            self.detail_bytes[k] = self.detail_bytes.get(k, 0.0) + v * mult
        for k, v in other.detail_flops.items():
            self.detail_flops[k] = self.detail_flops.get(k, 0.0) + v * mult
        self.unknown_trip_loops += other.unknown_trip_loops

    def top_bytes(self, n=15):
        return sorted(self.detail_bytes.items(), key=lambda kv: -kv[1])[:n]

    def top_flops(self, n=15):
        return sorted(self.detail_flops.items(), key=lambda kv: -kv[1])[:n]

    @property
    def collective_total(self) -> float:
        return sum(self.coll_bytes.values())


def parse_module(text: str) -> Tuple[Dict[str, Computation], Optional[str]]:
    comps: Dict[str, Computation] = {}
    entry: Optional[str] = None
    cur: Optional[Computation] = None
    for line in text.splitlines():
        stripped = line.strip()
        if not stripped:
            continue
        hdr = _COMP_HDR_RE.match(stripped)
        if hdr and stripped.endswith("{"):
            cur = Computation(hdr.group(1))
            comps[cur.name] = cur
            if stripped.startswith("ENTRY"):
                entry = cur.name
            continue
        if stripped.startswith("}"):
            cur = None
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(line)
        if m:
            ins = Instr(m.group(1), m.group(2), m.group(3), m.group(4))
            cur.instrs.append(ins)
            cur.shapes[ins.name] = ins.shape
    return comps, entry


def _dot_flops(ins: Instr, comp: Computation) -> float:
    result_numel = _shape_numel(ins.shape)
    cm = _CONTRACT_RE.search(ins.rest)
    ops = _OPERANDS_RE.findall(ins.rest.split("),")[0] + ")")
    lhs_shape = None
    for o in ops:
        if o in comp.shapes:
            lhs_shape = comp.shapes[o]
            break
    if lhs_shape is None or cm is None:
        return 2.0 * result_numel  # degenerate fallback
    parsed = _parse_shape(lhs_shape)
    if not parsed:
        return 2.0 * result_numel
    dims = parsed[0][1]
    k = 1
    if cm.group(1):
        for d in cm.group(1).split(","):
            di = int(d)
            if di < len(dims):
                k *= dims[di]
    return 2.0 * result_numel * k


def _conv_flops(ins: Instr, comp: Computation) -> float:
    result_numel = _shape_numel(ins.shape)
    wm = _WINDOW_SIZE_RE.search(ins.rest)
    spatial = 1
    if wm:
        for d in wm.group(1).split("x"):
            spatial *= int(d)
    fg = _FEATURE_GROUPS_RE.search(ins.rest)
    groups = int(fg.group(1)) if fg else 1
    # input feature per group: from rhs shape (kernel) if available
    ops = _OPERANDS_RE.findall(ins.rest)
    cin_per_group = 1
    if len(ops) >= 2 and ops[1] in comp.shapes:
        parsed = _parse_shape(comp.shapes[ops[1]])
        if parsed:
            kd = parsed[0][1]
            if len(kd) >= 2:
                cin_per_group = max(1, math.prod(kd) // (spatial * max(
                    1, kd[-1])))
    return 2.0 * result_numel * spatial * cin_per_group


def _group_size(rest: str, default: int) -> int:
    m = _GROUPS_RE.search(rest)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA_RE.search(rest)
    if m:
        return int(m.group(2))
    return default


def _collective_traffic(op: str, size: float, n: int) -> float:
    if op == "all-reduce":
        return 2.0 * size * (n - 1) / n
    if op == "all-gather":
        return size * (n - 1) / n
    if op == "reduce-scatter":
        return size * (n - 1)
    if op == "all-to-all":
        return size * (n - 1) / n
    return float(size)  # collective-permute


def _trip_count(cond: Computation) -> Optional[int]:
    consts = []
    for ins in cond.instrs:
        if ins.op == "constant" and ins.shape == "s32[]":
            mm = re.match(r"\((\d+)\)", ins.rest)
            if mm:
                consts.append(int(mm.group(1)))
    if consts:
        return max(consts)
    return None


class ModuleCost:
    def __init__(self, text: str, num_devices: int):
        self.comps, self.entry = parse_module(text)
        self.num_devices = num_devices
        self._memo: Dict[str, Cost] = {}

    def compute(self) -> Cost:
        if self.entry is None:
            return Cost()
        return self._cost(self.entry)

    def _cost(self, name: str) -> Cost:
        if name in self._memo:
            return self._memo[name]
        comp = self.comps.get(name)
        total = Cost()
        if comp is None:
            self._memo[name] = total
            return total
        self._memo[name] = total  # guard cycles
        for ins in comp.instrs:
            op = ins.op
            base = op.replace("-start", "").replace("-done", "")
            if base in COLLECTIVE_OPS:
                if op.endswith("-done"):
                    continue
                size = _shape_bytes(ins.shape)
                n = max(2, _group_size(ins.rest, self.num_devices))
                traffic = _collective_traffic(base, size, n)
                total.coll_bytes[base] = total.coll_bytes.get(base, 0.) \
                    + traffic
                total.coll_counts[base] = total.coll_counts.get(base, 0.) + 1
                total.bytes += 2 * size
                total._dadd(total.detail_bytes, f"{base} {ins.shape}",
                            2 * size)
                continue
            if op == "while":
                body = _BODY_RE.search(ins.rest)
                cond = _COND_RE.search(ins.rest)
                trip = None
                if cond and cond.group(1) in self.comps:
                    trip = _trip_count(self.comps[cond.group(1)])
                if trip is None:
                    trip = 1
                    total.unknown_trip_loops += 1
                if body and body.group(1) in self.comps:
                    total.add(self._cost(body.group(1)), trip)
                if cond and cond.group(1) in self.comps:
                    total.add(self._cost(cond.group(1)), trip)
                continue
            if op in ("fusion", "call", "conditional", "map", "reduce",
                      "reduce-window", "sort", "scatter", "custom-call",
                      "select-and-scatter"):
                # FLOPs (and collectives) from fused dots/convs recursively
                in_place = False
                for cm in _CALLS_RE.finditer(ins.rest):
                    called = self.comps.get(cm.group(1))
                    sub = self._cost(cm.group(1))
                    total.flops += sub.flops
                    for k, v in sub.coll_bytes.items():
                        total.coll_bytes[k] = total.coll_bytes.get(k, 0.) + v
                    for k, v in sub.coll_counts.items():
                        total.coll_counts[k] = total.coll_counts.get(k, 0.) + v
                    if called is not None and called.has_dus:
                        # in-place loop-carried buffer update: count interior
                        # slice traffic only (2x DUS update + slice reads +
                        # fused dot io); the pass-through buffer and full-size
                        # interior selects/copies never move on hardware.
                        in_place = True
                        b = (called.dus_update_bytes()
                             + called.slice_read_bytes())
                        for di in called.instrs:
                            if di.op == "dot":
                                b += self._io_bytes(di, called)
                        total.bytes += b
                        total._dadd(total.detail_bytes,
                                    f"{op}(dus) {ins.shape}", b)
                if not in_place:
                    io = self._fusion_io_bytes(ins, comp)
                    total.bytes += io
                    total._dadd(total.detail_bytes, f"{op} {ins.shape}", io)
                continue
            if op == "dot":
                fl = _dot_flops(ins, comp)
                io = self._io_bytes(ins, comp)
                total.flops += fl
                total.bytes += io
                total._dadd(total.detail_flops, f"dot {ins.shape}", fl)
                total._dadd(total.detail_bytes, f"dot {ins.shape}", io)
                continue
            if op == "convolution":
                total.flops += _conv_flops(ins, comp)
                total.bytes += self._io_bytes(ins, comp)
                continue
            if op == "dynamic-update-slice":
                # in-place: read update + write slice
                ops = _OPERANDS_RE.findall(ins.rest)
                upd = 0
                if len(ops) >= 2 and ops[1] in comp.shapes:
                    upd = _shape_bytes(comp.shapes[ops[1]])
                total.bytes += 2 * upd
                continue
            if op in _SKIP_BYTES_OPS:
                continue
            b = _shape_bytes(ins.shape)  # write-once model
            total.bytes += b
            total._dadd(total.detail_bytes, f"{op} {ins.shape}", b)
        return total

    def _fusion_io_bytes(self, ins: Instr, comp: Computation) -> float:
        """Fusion-boundary traffic. Operands that are read through an
        interior dynamic-slice/gather (e.g. one layer's slice of a stacked
        scan buffer) are counted at the SLICE size, not the full buffer —
        only the slice moves on hardware."""
        rb = _shape_bytes(ins.shape)
        called = None
        for cm in _CALLS_RE.finditer(ins.rest):
            called = self.comps.get(cm.group(1)) or called
        slice_read = called is not None and called.has_slice_read
        b = float(rb)
        arg_str = ins.rest.split("),")[0]
        for o in _OPERANDS_RE.findall(arg_str):
            if o in comp.shapes:
                ob = _shape_bytes(comp.shapes[o])
                if slice_read and ob > 4 * max(rb, 1):
                    continue  # counted via interior slice results below
                b += ob
        if slice_read:
            b += called.slice_read_bytes()
        return b

    def _io_bytes(self, ins: Instr, comp: Computation) -> float:
        b = _shape_bytes(ins.shape)
        arg_str = ins.rest.split("),")[0]
        for o in _OPERANDS_RE.findall(arg_str):
            if o in comp.shapes:
                b += _shape_bytes(comp.shapes[o])
        return b


def expanded_cost(hlo_text: str, num_devices: int) -> Cost:
    return ModuleCost(hlo_text, num_devices).compute()
