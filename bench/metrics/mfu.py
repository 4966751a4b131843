"""mfu: model FLOP utilization of the watched training step, in %.

``tokens_per_s`` of the traced window times the model FLOPs one trained
token needs (``bench.flops``: forward and backward matmuls, attention
counted causally, rematerialization not counted), over the chips' bf16
peak (``bench.peaks``)."""
from bench import flops


def read(ctx):
    rate = ctx.outcome.e2e.get("tokens_per_s")
    if not rate:
        return None
    return flops.utilization(rate, ctx.cell.config,
                             ctx.outcome.counters["seq_len"], ctx.chips,
                             ctx.peak["flops_bf16"])
