"""bwd_ms.watched: device milliseconds per training step of the watched
job's backward: operations under ``transpose(``, recompute excluded.
Read from each device operation's scope path in the trace, per
``repro.train.step`` span of the window (``bench.program_spans``)."""
from bench import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx, "bwd")
