"""pattern_summary_roofline: the pattern-summary kernel's share of its
roofline, in %.  The least time of each call the window made is the work
its real ``(E, n)`` rows need (``bench.flops.summarize_work``: one read of
the samples, the row targets and the outputs) at the chip's peak; the
bound is memory bandwidth.  The share is their sum over the kernel's
traced device time."""
from bench import flops

KERNEL = ("%pattern_summary",)


def read(ctx):
    if ctx.trace is None:
        return None
    seconds, count = ctx.trace.op_seconds(KERNEL)
    shapes = ctx.outcome.counters.get("summarize_shapes") or []
    if count == 0 or seconds <= 0 or not shapes:
        return None
    least = sum(flops.least_time_s(flops.summarize_work(e, n),
                                   ctx.peak["flops_bf16"],
                                   ctx.peak["hbm_bytes_s"])[0]
                for e, n in shapes)
    return 100.0 * least / seconds
