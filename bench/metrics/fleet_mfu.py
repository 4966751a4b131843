"""fleet_mfu: model FLOP utilization of the fleet's split training steps,
in %: ``fleet_tokens_per_s`` of the traced window times the model FLOPs per
trained token (``bench.flops``), over the chips' bf16 peak."""
from bench import flops


def read(ctx):
    rate = ctx.outcome.e2e.get("fleet_tokens_per_s")
    if not rate:
        return None
    return flops.utilization(rate, ctx.cell.config,
                             ctx.outcome.counters["seq_len"], ctx.chips,
                             ctx.peak["flops_bf16"])
