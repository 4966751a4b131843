"""summarize_ms.fleet: device time of the pattern-summary kernel per
pipeline window tick, in ms, from the trace (the operations named
``%pattern_summary...``: the two Pallas calls of each summarize)."""

KERNEL = ("%pattern_summary",)


def read(ctx):
    if ctx.trace is None:
        return None
    seconds, count = ctx.trace.op_seconds(KERNEL)
    ticks = ctx.outcome.counters.get("ticks", 0)
    if count == 0 or not ticks:
        return None
    return 1000.0 * seconds / ticks
