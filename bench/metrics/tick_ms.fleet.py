"""tick_ms.fleet: mean host milliseconds of the online pipeline's window
tick, from the program's own ``repro.pipeline.tick`` spans in the traced
window (summarize, EMA, localize, incidents, mitigation, escalation;
``bench.program_spans``)."""
from bench import program_spans


def read(ctx):
    return program_spans.mean_ms(ctx, program_spans.TICK)
