"""idle_loader.fleet: the share of the traced window, in %, in which the
device was idle while the innermost program span on the host was
``repro.train.loader`` (the batch, its copy to the device and any
injected loader burn).  Each idle nanosecond goes to the innermost span
holding it (``bench.program_spans``)."""
from bench import program_spans


def read(ctx):
    return program_spans.idle_share(ctx, "loader")
