"""The fault cycle of a fleet cell, drawn from the seed.

Every block of ``workers`` cycles faults each worker once, in an order
drawn from the seed, so every seed injects the same set of faults in
another order.  Cycle ``k`` puts its fault on one worker for the first
``fault`` of its windows, starting at ``lead + k * cycle``; the rest of the
cycle is healthy windows in which the detector recovers before the next
fault begins.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np


@dataclass(frozen=True)
class Incident:
    worker: int
    start_window: int
    end_window: int          # exclusive


def cycle_count(seconds: float, cycle_s: float, workers: int,
                minimum: int) -> int:
    """Cycles that fit ``seconds`` at ``cycle_s`` each (as timed at
    warm-up): a whole number of blocks of ``workers`` cycles, and at least
    ``minimum``."""
    blocks = int(seconds // max(cycle_s * workers, 1e-9))
    need = int(np.ceil(minimum / workers))
    return workers * max(blocks, need, 1)


def schedule(seed: int, cycles: int, workers: int, lead: int,
             cycle: int, fault: int) -> List[Incident]:
    rng = np.random.default_rng([seed, 0xFA17])
    order: List[int] = []
    while len(order) < cycles:
        order.extend(int(w) for w in rng.permutation(workers))
    return [Incident(w, lead + k * cycle, lead + k * cycle + fault)
            for k, w in enumerate(order[:cycles])]
