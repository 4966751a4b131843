"""idle_pipeline.fleet: the share of the traced window, in %, in which the
device was idle while the innermost program span on the host was a
``repro.pipeline.*`` or ``repro.scenario.*`` span: the online pipeline's
tick and the detector's intake.  Each idle nanosecond goes to the
innermost span holding it (``bench.program_spans``)."""
from bench import program_spans


def read(ctx):
    return program_spans.idle_share(ctx, "pipeline")
