"""attn_ms.watched: device milliseconds per training step of the watched
job's attention core, in every phase: the operations that do work
themselves (``tracing.leaves``) whose scope path holds an ``attn``
component, the named scope of ``repro.models.attention_core``'s forward
and backward.  Read from each device operation's scope path in the trace,
per ``repro.train.step`` span of the window (``bench.program_spans``).
None for a program without that scope."""
from typing import Dict, List, Optional

from bench import program_spans, tracing

SCOPE = "attn"


def ms_in_scope(ops: Dict[str, List[program_spans.Op]], lo: int, hi: int,
                steps: int, scope: str = SCOPE) -> Optional[float]:
    """Device milliseconds per step, summed over the chips and averaged
    over them, of the operations one of whose scope paths holds the
    component ``scope``; None when no operation does or there are no
    steps."""
    if steps <= 0:
        return None
    ns, seen = 0, False
    for evs in ops.values():
        for op in tracing.leaves(evs):
            if not any(scope in p.split("/")
                       for p in program_spans._paths(op.scope)):
                continue
            seen = True
            ns += max(0, min(op.end, hi) - max(op.start, lo))
    return ns / 1e6 / len(ops) / steps if seen else None


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace.lo, ctx.trace.hi
    steps = len(program_spans.in_window(program_spans.host_spans(), lo, hi,
                                        program_spans.STEP))
    return ms_in_scope(program_spans.device_ops(), lo, hi, steps)
