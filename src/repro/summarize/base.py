"""Backend protocol for batched behavior-pattern summarization (DESIGN.md §3).

A *summarize backend* consumes one zero-padded ``(E, n)`` utilization matrix
(one row per function execution, see ``repro.summarize.packing``) and returns
an ``(E, 3)`` float array of per-row critical-duration statistics::

    out[e] = (mean, std, count)

where ``[lo, hi)`` is the Algorithm-1 critical execution duration of row
``e``, ``mean``/``std`` are the population statistics of ``u[e, lo:hi]`` and
``count = hi - lo`` (samples, including interior zeros kept by the gap
bound).  All-zero rows may return any ``count``; the engine overrides them
with the row's true (unpadded) length, so backends need not know padding.

Backends are registered by name and selected per call, per service, or
globally via the ``REPRO_SUMMARIZE_BACKEND`` environment variable
(``python`` | ``numpy`` | ``pallas`` | ``auto``).  ``auto`` (the default)
prefers the fastest backend that can run in this process: ``pallas`` when
this process already drives a TPU, else ``numpy``.  A backend that is not
installed (``pallas`` without jax) falls back down the chain
``pallas -> numpy -> python``; one that is installed but fails to load
raises, so a broken kernel never hides behind the host path.
"""
from __future__ import annotations

import os
from typing import Callable, Dict, List, Optional, Protocol, runtime_checkable

import numpy as np

ENV_BACKEND = "REPRO_SUMMARIZE_BACKEND"

#: fallback order used by ``auto`` and by unavailable explicit choices
FALLBACK_CHAIN = ("pallas", "numpy", "python")


@runtime_checkable
class SummarizeBackend(Protocol):
    """Batched Algorithm-1 executor."""

    name: str

    def batch_stats(self, u: np.ndarray) -> np.ndarray:
        """u: (E, n) utilization in [0, 1]. Returns (E, 3) [mean, std, count]."""
        ...

    def available(self) -> bool:
        """Whether this backend can run in the current process."""
        ...


_REGISTRY: Dict[str, Callable[[], SummarizeBackend]] = {}
_INSTANCES: Dict[str, SummarizeBackend] = {}


def register_backend(name: str, factory: Callable[[], SummarizeBackend]) -> None:
    _REGISTRY[name] = factory


def available_backends() -> List[str]:
    """Names of registered backends that report themselves runnable."""
    return [n for n in _REGISTRY if _instance(n).available()]


def _instance(name: str) -> SummarizeBackend:
    if name not in _INSTANCES:
        if name not in _REGISTRY:
            raise KeyError(
                f"unknown summarize backend {name!r}; "
                f"registered: {sorted(_REGISTRY)}")
        _INSTANCES[name] = _REGISTRY[name]()
    return _INSTANCES[name]


def get_backend(name: Optional[str] = None) -> SummarizeBackend:
    """Resolve a backend by explicit name, env var, or ``auto`` fallback.

    An explicit/env choice that is registered but not installed (``pallas``
    with no jax) degrades down FALLBACK_CHAIN instead of raising.
    """
    choice = name or os.environ.get(ENV_BACKEND, "auto")
    if choice != "auto":
        be = _instance(choice)           # unknown names still raise
        if be.available():
            return be
        start = (FALLBACK_CHAIN.index(choice) + 1
                 if choice in FALLBACK_CHAIN else 0)
        chain = FALLBACK_CHAIN[start:]
    else:
        chain = FALLBACK_CHAIN
    for cand in chain:
        if cand not in _REGISTRY:
            continue
        be = _instance(cand)
        # fallback candidates must both claim to be a good default (auto_ok:
        # pallas declines off-TPU, where interpret mode is orders of
        # magnitude slower than numpy) AND run here — auto_ok first, so a
        # declining backend never pays its availability probe (pallas's
        # would import jax into an otherwise jax-free daemon process); an
        # explicit name is only honored verbatim above, never via fallback
        if getattr(be, "auto_ok", be.available)() and be.available():
            return be
    return _instance("python")           # always available
