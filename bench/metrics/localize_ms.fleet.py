"""localize_ms.fleet: host time from the start of localization to the end
of mitigation planning and execution in each window tick, in ms (the
harness's clock around ``localize`` ... ``MitigationEngine.step``)."""


def read(ctx):
    spans = ctx.outcome.counters.get("localize_s") or []
    if not spans:
        return None
    return 1000.0 * sum(spans) / len(spans)
