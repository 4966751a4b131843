"""What the program writes into the trace of a measured window itself: its
host spans (``repro.*``, ``repro.instrument.spans``) and the named scopes of
its step programs, which the compiler carries into each device operation's
scope path (``op_name``).  The per-layer readers of the training phases and
of the causes of device idle time read them through this module.

The trace is read from disk while ``bench/main.py`` still holds it, once
per process: the host spans with ``tracing.load(host_prefix="repro.")``,
the device operations' scope paths from the stat that carries them.  A
trace of a program without these spans or scopes yields nothing, and the
readers then return None; no phase or cause is ever guessed.  The
functions below take plain events, so a test can feed them a synthetic
trace.
"""
from __future__ import annotations

import functools
import glob
import heapq
import os
from pathlib import Path
from typing import (Dict, Iterable, List, NamedTuple, Optional, Sequence,
                    Tuple)

from bench import tracing

#: the program's host span of one training step (``Trainer.run``)
STEP = "repro.train.step"
#: the tracker's profiling window, trigger to the end of its diagnosis
TRACKER_WINDOW = "repro.tracker.window"
#: one window tick of the online pipeline
TICK = "repro.pipeline.tick"
#: the loader of ``Trainer.train_iteration``, with any injected burn
LOADER = "repro.train.loader"

#: cause of idle time -> the prefixes of the innermost spans it takes
#: (the loader span alone is ``loader``; a program span of none of them
#: is ``other``)
CAUSES = {
    "step_host": ("repro.train.", "repro.worker.", "repro.tracer.",
                  "repro.workload."),
    "pipeline": ("repro.pipeline.", "repro.scenario."),
}


# -- loading ------------------------------------------------------------------

def _xplane(directory: Path) -> Optional[str]:
    files = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                      recursive=True)
    return files[0] if files else None


@functools.lru_cache(maxsize=2)
def _host(path: str, stamp: float) -> Tuple[tracing.Event, ...]:
    return tuple(tracing.load(Path(path), host_prefix="repro.").host)


def host_spans(directory: Path = tracing.TRACE_DIR
               ) -> List[tracing.Event]:
    """The program's host spans in the trace under ``directory`` (empty
    when there is no trace or the program wrote none)."""
    f = _xplane(directory)
    if f is None:
        return []
    return list(_host(str(directory), os.path.getmtime(f)))


def in_window(spans: Iterable[tracing.Event], lo: int, hi: int,
              name: str) -> List[tracing.Event]:
    """Spans called ``name`` that overlap [lo, hi]."""
    return [s for s in spans if s.name == name and s.end > lo
            and s.start < hi]


# -- idle time by cause ---------------------------------------------------------

def innermost(spans: Sequence[tracing.Event]
              ) -> List[Tuple[int, int, str]]:
    """Cuts time into intervals, each held by one innermost span: among
    the spans holding an instant, the one that began last (of those, the
    one that ends first).  For properly nested spans that is the deepest;
    where two overlap without nesting, the later one wins."""
    points = sorted({s.start for s in spans} | {s.end for s in spans})
    by_start = sorted(spans, key=lambda s: s.start)
    heap: List[Tuple[int, int, str]] = []
    out: List[Tuple[int, int, str]] = []
    i = 0
    for a, b in zip(points, points[1:]):
        while i < len(by_start) and by_start[i].start <= a:
            s = by_start[i]
            heapq.heappush(heap, (-s.start, s.end, s.name))
            i += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        if heap:
            name = heap[0][2]
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))
    return out


def attribute(idle: Sequence[Tuple[int, int]],
              spans: Sequence[tracing.Event]) -> Dict[Optional[str], int]:
    """Nanoseconds of ``idle`` by the innermost span holding each of them;
    the key None holds what no span holds."""
    segs = innermost(spans)
    out: Dict[Optional[str], int] = {}
    j = 0
    for a, b in idle:
        held = 0
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            d = min(b, segs[k][1]) - max(a, segs[k][0])
            if d > 0:
                out[segs[k][2]] = out.get(segs[k][2], 0) + d
                held += d
            k += 1
        if b - a > held:
            out[None] = out.get(None, 0) + (b - a - held)
    return out


def cause_of(name: Optional[str]) -> Optional[str]:
    if name is None:
        return None
    if name == LOADER:
        return "loader"
    return next((c for c, pre in CAUSES.items() if name.startswith(pre)),
                "other")


def idle_by_cause(summary: tracing.Summary,
                  spans: Sequence[tracing.Event]
                  ) -> Optional[Dict[Optional[str], float]]:
    """Device idle time of the window by the cause of its innermost
    program span, in % of the window, averaged over the chips; the key
    None holds idle time no program span holds.  None when the window
    holds no program span."""
    lo, hi = summary.lo, summary.hi
    spans = [s for s in spans if s.end > lo and s.start < hi]
    if not spans or hi <= lo:
        return None
    tot: Dict[Optional[str], float] = {}
    for evs in summary.events.values():
        for name, ns in attribute(tracing.gaps(evs, lo, hi), spans).items():
            c = cause_of(name)
            tot[c] = tot.get(c, 0.0) + ns
    return {c: 100.0 * ns / (hi - lo) / summary.chips
            for c, ns in tot.items()}


def idle_share(ctx, cause: str) -> Optional[float]:
    """The reader of one cause's share of the window's idle time, in %."""
    if ctx.trace is None:
        return None
    by = idle_by_cause(ctx.trace, host_spans())
    if by is None:
        return None
    return by.get(cause, 0.0)


# -- device time by training phase ----------------------------------------------

def _phase(path: str) -> Optional[str]:
    if path.startswith("opt/") or "/opt/" in path:
        return "opt"
    if "transpose(jvp(fwd))" in path:
        return "recompute" if "rematted_computation" in path else "bwd"
    if "jvp(fwd)" in path:
        return "fwd"
    return None


def _paths(scope: str) -> List[str]:
    """The whole paths of a scope path that joins several with ``;`` (a
    fused operation's): each after the first is written relative to the
    first, from the last of the first's components that it begins with,
    else from the first's parent."""
    first, *rest = scope.split(";")
    head = first.split("/")
    out = [first]
    for p in rest:
        c = p.split("/", 1)[0]
        k = max((i for i, h in enumerate(head) if h == c),
                default=len(head) - 1)
        out.append("/".join(head[:k] + [p]))
    return out


@functools.lru_cache(maxsize=4096)
def phase_of(scope: str) -> Optional[str]:
    """The training phase of an operation from its scope path, by the
    step programs' named scopes: the optimizer's (an ``opt`` component),
    under the backward of the loss's ``fwd`` scope
    (``transpose(jvp(fwd))``) rematerialized forward (under
    ``rematted_computation``) or backward, else forward (``jvp(fwd)``);
    None for an operation in none of them.  A fused operation whose
    joined paths lie in two phases is ``mixed``: it is counted apart and
    in none of the four."""
    got = {_phase(p) for p in _paths(scope)} - {None}
    if len(got) > 1:
        return "mixed"
    return got.pop() if got else None


class Op(NamedTuple):
    """A device operation with its scope path ('' when none is known)."""
    name: str
    start: int
    end: int
    scope: str


def ms_by_phase(ops: Dict[str, List[Op]], lo: int, hi: int, steps: int
                ) -> Optional[Dict[Optional[str], float]]:
    """Device milliseconds per step of the operations that do work
    themselves (``tracing.leaves``), by phase, summed over the chips and
    averaged over them; the key None holds operations in no phase, and
    ``mixed`` fused operations in two (``phase_of``).  None
    when no operation carries a phase's scope (a program without the
    scopes, or a trace without scope paths) or there are no steps."""
    if steps <= 0 or not ops:
        return None
    out: Dict[Optional[str], float] = {}
    scoped = False
    for evs in ops.values():
        for op in tracing.leaves(evs):
            d = min(op.end, hi) - max(op.start, lo)
            if d <= 0:
                continue
            p = phase_of(op.scope)
            scoped = scoped or p is not None
            out[p] = out.get(p, 0.0) + d
    if not scoped:
        return None
    return {p: ns / 1e6 / len(ops) / steps for p, ns in out.items()}


# -- the readers' common parts --------------------------------------------------

def phase_ms(ctx, phase: str) -> Optional[float]:
    """The reader of one phase's device milliseconds per step."""
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace.lo, ctx.trace.hi
    steps = len(in_window(host_spans(), lo, hi, STEP))
    if not steps:
        return None
    by = ms_by_phase(device_ops(), lo, hi, steps)
    return None if by is None else by.get(phase, 0.0)


def per_100_steps(ctx, name: str) -> Optional[float]:
    """100 x the program's spans ``name`` over its step spans in the
    window."""
    if ctx.trace is None:
        return None
    spans = host_spans()
    lo, hi = ctx.trace.lo, ctx.trace.hi
    steps = len(in_window(spans, lo, hi, STEP))
    if not steps:
        return None
    return 100.0 * len(in_window(spans, lo, hi, name)) / steps


def mean_ms(ctx, name: str) -> Optional[float]:
    """Mean host milliseconds of the program's spans ``name`` in the
    window."""
    if ctx.trace is None:
        return None
    got = in_window(host_spans(), ctx.trace.lo, ctx.trace.hi, name)
    if not got:
        return None
    return sum(s.end - s.start for s in got) / len(got) / 1e6


# -- device operations with their scope paths ----------------------------------
# ``jax.profiler.ProfileData`` gives an event the stats of its own and
# not those of its metadata (``test_profile_data_hides_metadata_stats``),
# which is where the chip's profiler keeps an operation's scope path, nor
# the metadata's id that would tell two modules' ``%fusion.N`` apart.  So
# the device planes are read from the trace's XSpace protobuf
# (tsl/profiler/protobuf/xplane.proto) here, field by field; host planes
# are skipped whole.

#: stats that may carry an operation's scope path, in the order tried
SCOPE_STATS = ("tf_op",)


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i: int, end: int):
    """(field, value) of one message in ``buf[i:end]``; a length-delimited
    value is its (start, end), a fixed one its raw bytes."""
    while i < end:
        key, i = _varint(buf, i)
        field, kind = key >> 3, key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif kind == 1:
            v, i = bytes(buf[i:i + 8]), i + 8
        elif kind == 5:
            v, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"protobuf wire type {kind} in a trace")
        yield field, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _stat(buf, span, names: Dict[int, str]) -> Tuple[str, object]:
    """(name, value) of one XStat; an interned string (``ref_value``) is
    the name of the stat metadata it refers to."""
    key, val = "", None
    for f, v in _fields(buf, *span):
        if f == 1:
            key = names.get(v, str(v))
        elif f == 5:
            val = _text(buf, v)
        elif f == 7:
            val = names.get(v, "")
        elif f in (3, 4):
            val = v
    return key, val


def _event(buf, i: int, end: int):
    """(metadata id, offset ps, duration ps, stats' spans) of one XEvent:
    the fields of the trace's most numerous message, read inline."""
    mid = off = dur = 0
    stats = []
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
            f = key >> 3
            if f == 1:
                mid = v
            elif f == 2:
                off = v
            elif f == 3:
                dur = v
        elif kind == 2:
            n, i = _varint(buf, i)
            if key >> 3 == 4:
                stats.append((i, i + n))
            i += n
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {kind} in a trace")
    return mid, off, dur, stats


def _scope(stats: Dict[str, object]) -> str:
    for k in SCOPE_STATS:
        if isinstance(stats.get(k), str):
            return stats[k]
    return ""


def read_ops(data: bytes) -> Dict[str, List[Op]]:
    """The device planes' ``XLA Ops`` events of a serialized XSpace, by
    chip, each with the scope path its own or its metadata's stats give
    ('' when none)."""
    buf = memoryview(data)
    out: Dict[str, List[Op]] = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:                              # XSpace.planes
            continue
        name, lines, meta, stat_names = "", [], [], {}
        for g, v in _fields(buf, *plane):
            if g == 2:
                name = _text(buf, v)
                if not name.startswith("/device:TPU:"):
                    break
            elif g == 3:
                lines.append(v)
            elif g == 4:
                meta.append(v)
            elif g == 5:                        # map<int64, XStatMetadata>
                sid, sname = 0, ""
                for h, w in _fields(buf, *v):
                    if h == 2:
                        for k, x in _fields(buf, *w):
                            if k == 1:
                                sid = x
                            elif k == 2:
                                sname = _text(buf, x)
                stat_names[sid] = sname
        if not name.startswith("/device:TPU:"):
            continue
        ops: Dict[int, Tuple[str, str]] = {}    # metadata id -> name, scope
        for v in meta:                          # map<int64, XEventMetadata>
            for h, w in _fields(buf, *v):
                if h != 2:
                    continue
                mid, mname, stats = 0, "", {}
                for k, x in _fields(buf, *w):
                    if k == 1:
                        mid = x
                    elif k == 2:
                        mname = _text(buf, x)
                    elif k == 5:
                        key, val = _stat(buf, x, stat_names)
                        stats[key] = val
                ops[mid] = (tracing.short_name(mname), _scope(stats))
        for v in lines:
            lname, t0, events = "", 0, []
            for g, w in _fields(buf, *v):
                if g == 2:
                    lname = _text(buf, w)
                elif g == 3:
                    t0 = w
                elif g == 4:
                    events.append(w)
            if lname != tracing.OPS_LINE:
                continue
            evs = out.setdefault(name, [])
            for w in events:
                mid, off, dur, own = _event(buf, *w)
                opname, scope = ops.get(mid, ("", ""))
                if not scope:       # else the metadata's scope stands
                    scope = _scope(dict(_stat(buf, x, stat_names)
                                        for x in own))
                start = t0 + off // 1000
                evs.append(Op(opname, start, start + dur // 1000, scope))
    return out


def device_ops(directory: Path = tracing.TRACE_DIR) -> Dict[str, List[Op]]:
    """Device operations of the trace under ``directory`` with their scope
    paths, by chip (empty when there is no trace)."""
    f = _xplane(directory)
    if f is None:
        return {}
    return _ops(f, os.path.getmtime(f))


@functools.lru_cache(maxsize=2)
def _ops(path: str, stamp: float) -> Dict[str, List[Op]]:
    with open(path, "rb") as f:
        return read_ops(f.read())
