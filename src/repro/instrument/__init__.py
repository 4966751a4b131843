"""The attachment to a training job: anchors and profiling windows
(``hooks``), the phase tracer and samplers (``tracer``), and the host spans
on the profiler's clock (``spans``)."""
