"""The profiler trace of a measured window, and its reduction to numbers.

``capture`` records the window with jax's profiler (device activity and
the harness's own ``bench.*`` annotations; Python function tracing off)
into ``.bench_trace/`` at the root of the checkout.  ``load`` reads the
device operations and the host annotations back; the functions below turn
them into busy and idle time, time by operation name, collective time that
no compute hides, and the longest idle gaps labelled by what the host was
doing.  They take plain ``(name, start_ns, end_ns)`` events, so a test can
feed them a synthetic trace.
"""
from __future__ import annotations

import glob
import os
import shutil
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".bench_trace"
WINDOW = "bench.window"
#: the device line whose events are the operations the chip ran
OPS_LINE = "XLA Ops"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "send", "recv")


@dataclass(frozen=True)
class Event:
    name: str
    start: int          # ns
    end: int            # ns


@dataclass
class Trace:
    devices: Dict[str, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)

    def window(self) -> Tuple[int, int]:
        spans = [e for e in self.host if e.name == WINDOW]
        if not spans:
            raise ValueError(f"no {WINDOW!r} annotation in the trace")
        return min(e.start for e in spans), max(e.end for e in spans)


@contextmanager
def capture(enabled: bool):
    if not enabled:
        yield None
        return
    import jax
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    try:
        yield TRACE_DIR
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """A host span in the trace (``jax.profiler.TraceAnnotation``)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def wrap(fn: Callable, name: str) -> Callable:
    def spanned(*a, **kw):
        with annotate(name):
            return fn(*a, **kw)
    return spanned


def load(directory: Path = TRACE_DIR, host_prefix: str = "bench.") -> Trace:
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no trace under {directory}")
    tr = Trace()
    for f in files:
        for plane in ProfileData.from_file(f).planes:
            if plane.name.startswith("/device:TPU:"):
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        tr.devices.setdefault(plane.name, []).extend(
                            Event(short_name(e.name), int(e.start_ns),
                                  int(e.start_ns + e.duration_ns))
                            for e in line.events)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    tr.host.extend(
                        Event(e.name, int(e.start_ns),
                              int(e.start_ns + e.duration_ns))
                        for e in line.events
                        if e.name.startswith(host_prefix))
    return tr


def short_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``%fusion.12``."""
    return hlo.split(" = ", 1)[0]


def remove(directory: Path = TRACE_DIR) -> None:
    shutil.rmtree(directory, ignore_errors=True)


# -- reduction ----------------------------------------------------------------

#: operations whose event spans the operations of their body
CONTROL_FLOW = ("%while", "%conditional", "%call")


def leaves(events: Iterable[Event]) -> List[Event]:
    """The operations that do work themselves: a loop's or a call's event
    spans the operations of its body on the same line."""
    return [e for e in events if not e.name.startswith(CONTROL_FLOW)]


def union(events: Iterable[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Merged intervals covered by ``events``, clipped to [lo, hi]."""
    ivs = sorted((max(e.start, lo), min(e.end, hi)) for e in events
                 if e.end > lo and e.start < hi)
    out: List[List[int]] = []
    for a, b in ivs:
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(ivs: Sequence[Tuple[int, int]]) -> int:
    return sum(b - a for a, b in ivs)


def busy_ns(events: Iterable[Event], lo: int, hi: int) -> int:
    return covered(union(events, lo, hi))


def gaps(events: Iterable[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Idle intervals of the device in [lo, hi]."""
    out, t = [], lo
    for a, b in union(events, lo, hi):
        if a > t:
            out.append((t, a))
        t = b
    if t < hi:
        out.append((t, hi))
    return out


def time_by_name(events: Iterable[Event], lo: int, hi: int
                 ) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for e in events:
        d = min(e.end, hi) - max(e.start, lo)
        if d > 0:
            out[e.name] = out.get(e.name, 0) + d
    return out


def matching_ns(events: Iterable[Event], lo: int, hi: int,
                prefixes: Sequence[str]) -> Tuple[int, int]:
    """(time, count) of events whose name starts with one of
    ``prefixes``."""
    t = n = 0
    for e in events:
        if e.name.startswith(tuple(prefixes)):
            d = min(e.end, hi) - max(e.start, lo)
            if d > 0:
                t += d
                n += 1
    return t, n


def is_collective(name: str) -> bool:
    low = name.lower()
    return any(c in low for c in COLLECTIVES)


def exposed_collective_ns(events: Sequence[Event], lo: int, hi: int) -> int:
    """Collective time during which no other operation runs."""
    coll = union([e for e in events if is_collective(e.name)], lo, hi)
    comp = union([e for e in events if not is_collective(e.name)], lo, hi)
    hidden, j = 0, 0
    for a, b in coll:
        for c, d in comp:
            if d <= a:
                continue
            if c >= b:
                break
            hidden += min(b, d) - max(a, c)
    return covered(coll) - hidden


def label_gaps(idle: Sequence[Tuple[int, int]], host: Sequence[Event],
               k: int = 10) -> List[List]:
    """The ``k`` longest idle gaps, each named by the innermost host span
    that holds its middle (``host`` when no span does)."""
    spans = sorted((e for e in host if e.name != WINDOW),
                   key=lambda e: e.end - e.start)
    out = []
    for a, b in sorted(idle, key=lambda g: g[0] - g[1])[:k]:
        mid = (a + b) // 2
        name = next((e.name for e in spans if e.start <= mid <= e.end),
                    "host")
        out.append([name, (b - a) / 1e9])
    return out


@dataclass
class Summary:
    """What the per-layer readers take from a trace."""
    window_s: float
    busy_s: float                      # mean over the chips traced
    chips: int
    events: Dict[str, List[Event]]
    lo: int
    hi: int
    host: List[Event]

    def op_seconds(self, prefixes: Sequence[str]) -> Tuple[float, int]:
        """Summed over chips: seconds and count of the operations whose
        name starts with one of ``prefixes``."""
        t = n = 0
        for evs in self.events.values():
            a, b = matching_ns(leaves(evs), self.lo, self.hi, prefixes)
            t += a
            n += b
        return t / 1e9, n

    def exposed_collective_s(self) -> float:
        """Mean over the chips."""
        return sum(exposed_collective_ns(leaves(evs), self.lo, self.hi)
                   for evs in self.events.values()) / 1e9 / self.chips

    def breakdown(self, k: int = 10) -> dict:
        tot: Dict[str, int] = {}
        for evs in self.events.values():
            for n, t in time_by_name(leaves(evs), self.lo,
                                     self.hi).items():
                tot[n] = tot.get(n, 0) + t
        ops = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        first = sorted(self.events)[0]
        idle = gaps(self.events[first], self.lo, self.hi)
        return {"device_ops": [[n, t / 1e9 / self.chips] for n, t in ops],
                "idle_gaps": label_gaps(idle, self.host, k)}


def summarize(tr: Trace, chips: Optional[int] = None) -> Summary:
    lo, hi = tr.window()
    devs = {k: v for k, v in sorted(tr.devices.items())}
    if chips is not None:
        devs = dict(list(devs.items())[:chips])
    if not devs:
        raise ValueError("the trace holds no device operations")
    busy = [busy_ns(evs, lo, hi) for evs in devs.values()]
    return Summary(window_s=(hi - lo) / 1e9,
                   busy_s=sum(busy) / len(busy) / 1e9, chips=len(devs),
                   events=devs, lo=lo, hi=hi, host=tr.host)
