"""tracker_windows.watched: profiling windows the watched job's tracker
opened per 100 training steps of the traced window: 100 x the program's
``repro.tracker.window`` spans over its ``repro.train.step`` spans
(``bench.program_spans``)."""
from bench import program_spans


def read(ctx):
    return program_spans.per_100_steps(ctx, program_spans.TRACKER_WINDOW)
