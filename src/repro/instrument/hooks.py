"""``import PerfTracker``-style attachment (paper §4, Usage).

The provider never sees user code: ``PerfTracker.wrap(loader, opt_step)``
replaces the two anchor callables with timed versions (the paper
monkey-patches ``dataloader.next`` / ``optimizer.step`` the same way);
everything else (iteration detection, trigger, profiling window, pattern
upload, localization) happens behind the wrappers.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

from repro.core.detector import DetectorConfig, Trigger
from repro.core.events import Kind
from repro.core.service import DiagnosisResult, PerfTrackerService
from repro.instrument.tracer import Tracer

#: a window closes at its deadline only once it holds this many whole
#: iterations, so slow iterations still give the diagnosis a pattern
MIN_WINDOW_ITERS = 3


@dataclass
class PerfTrackerConfig:
    window_s: float = 2.0            # paper default 20 s; scaled for tests
    detector: DetectorConfig = field(default_factory=DetectorConfig)
    family: str = "dense"
    auto_profile: bool = True
    #: summarize backend name for this worker's daemon (None = env/auto)
    summarize_backend: Optional[str] = None


class PerfTracker:
    """Single-worker online attachment. In a fleet, one instance runs per
    worker and uploads patterns to the global service (see core.service)."""

    def __init__(self, cfg: PerfTrackerConfig = PerfTrackerConfig(),
                 worker: int = 0):
        self.cfg = cfg
        self.service = PerfTrackerService(
            family=cfg.family, detector_cfg=cfg.detector,
            summarize_backend=cfg.summarize_backend)
        self.tracer = Tracer(worker)
        self._window_deadline: Optional[float] = None
        self._window_iters = 0
        self.last_trigger: Optional[Trigger] = None
        self.results: List[DiagnosisResult] = []

    # -- anchors -----------------------------------------------------------
    def _on_anchor(self, name: str):
        now = time.perf_counter()
        trig = self.service.detector.feed(name, now)
        if trig is not None and self.cfg.auto_profile \
                and self._window_deadline is None:
            self.last_trigger = trig
            self.tracer.start_window()
            self._window_deadline = now + self.cfg.window_s
            self._window_iters = 0
        elif self._window_deadline is not None:
            self._window_iters += name == "optimizer.step"
            if now >= self._window_deadline \
                    and self._window_iters >= MIN_WINDOW_ITERS:
                self._finish_window()

    def _finish_window(self):
        self._window_deadline = None
        profile = self.tracer.stop_window()
        # wire mode: the true single-worker daemon shape — and it reuses
        # the (E, n) batch the tracer pre-packed onto profile.packed, which
        # the fleet-wide gather path would rebuild from raw streams
        res = self.service.diagnose_profiles([profile],
                                             trigger=self.last_trigger,
                                             mode="wire")
        self.results.append(res)

    def flush(self) -> Optional[DiagnosisResult]:
        if self._window_deadline is not None:
            self._finish_window()
        return self.results[-1] if self.results else None

    # -- wrapping ----------------------------------------------------------
    def wrap(self, dataloader_next: Callable, optimizer_step: Callable):
        def wrapped_next(*a, **kw):
            self._on_anchor("dataloader.next")
            with self.tracer.phase("dataloader.py:__next__", Kind.PYTHON,
                                   depth=2):
                return dataloader_next(*a, **kw)

        def wrapped_step(*a, **kw):
            with self.tracer.phase("optimizer.py:step", Kind.PYTHON,
                                   depth=2):
                out = optimizer_step(*a, **kw)
            self._on_anchor("optimizer.step")
            return out

        return wrapped_next, wrapped_step
