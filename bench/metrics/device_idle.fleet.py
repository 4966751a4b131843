"""The device's idle share of the traced window, in %: 1 minus the union
of the intervals in which an operation ran on a chip, over the window,
averaged over the chips."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
