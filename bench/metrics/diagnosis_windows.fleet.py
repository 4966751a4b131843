"""diagnosis_windows.fleet: the mean count of profiling windows from a
fault's onset to the report that names it, over the window's incidents
(1 = named in the window the fault began)."""


def read(ctx):
    counts = ctx.outcome.counters.get("diagnosis_windows") or []
    if not counts:
        return None
    return float(sum(counts)) / len(counts)
