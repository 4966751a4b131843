"""idle_step_host.fleet: the share of the traced window, in %, in which the
device was idle while the innermost program span on the host was any
other ``repro.train.*``, ``repro.worker.*``, ``repro.tracer.*`` or
``repro.workload.*`` span: the host side of the split step, its fences
and the tracer.  Each idle nanosecond goes to the innermost span holding
it (``bench.program_spans``)."""
from bench import program_spans


def read(ctx):
    return program_spans.idle_share(ctx, "step_host")
