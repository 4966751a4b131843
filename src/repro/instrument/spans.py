"""Host spans of the program's layers, on the profiler's clock.

``span(name, **attrs)`` is a ``jax.profiler.TraceAnnotation``: while a
profiler session runs, it writes one event into the trace's host plane,
on the same clock as the device's operations, so a device-idle interval
can be put down to what the host was doing in it.  Names are constant
strings under ``repro.``; attributes (``worker=``, ``window=``, ...) go in
as keyword arguments and show up as stats of the event, leaving its name
clean.  With no session an annotation costs about half a microsecond and
formats nothing.

The span also keeps its own elapsed host seconds (``seconds``), so a
timing field the program reports is the same measurement as its span in
the trace.  A span begins when it is made and ends at ``end()`` or at the
end of its ``with`` block, on the thread that made it.

Importing this module imports no jax; the first span imports
``jax.profiler``, which initializes no backend.
"""
from __future__ import annotations

import functools
import time


@functools.cache
def _profiler():
    import jax.profiler
    return jax.profiler


class span:
    """One host span.  ``with span("repro.pipeline.tick") as s: ...`` then
    ``s.seconds``; or ``s = span(...)`` ... ``s.end()`` across calls."""

    __slots__ = ("_me", "_t0", "seconds")
    _annotation = "TraceAnnotation"

    def __init__(self, name: str, **attrs):
        self._me = getattr(_profiler(), self._annotation)(name, **attrs)
        self._t0 = time.perf_counter()
        self.seconds = 0.0

    def end(self) -> float:
        """Ends the span; returns its host seconds."""
        self.seconds = time.perf_counter() - self._t0
        self._me.__exit__(None, None, None)
        return self.seconds

    def __enter__(self) -> "span":
        return self

    def __exit__(self, *exc) -> None:
        self.end()


class step_span(span):
    """One step of a training loop (``StepTraceAnnotation``): the
    profiler's per-step views count these."""

    __slots__ = ()
    _annotation = "StepTraceAnnotation"

    def __init__(self, name: str, step: int):
        super().__init__(name, step_num=step)
