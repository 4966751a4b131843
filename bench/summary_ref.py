"""The plain reference of the summarize step: the critical execution duration
of each row of utilization samples (Algorithm 1 of the paper) and the mean,
standard deviation and length of the row over it, row by row in float64.
It imports nothing of the program.

For a gap bound ``g`` a row splits at every run of zero samples longer than
``g`` into regions, each from its first nonzero sample to its last.  The
critical duration is the region of most mass (the leftmost of equals) at
the least ``g`` at which some region holds ``MASS`` of the row's mass.  A
row with no mass reports ``(0, 0, n)``.

The program decides these in float32.  Where a region's mass lies within
``BAND`` of the row's mass of the threshold, or of the most massive
region's, the decision can round either way, and every answer such a
rounding gives is accepted for that row; any other row has one answer.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

MASS = 0.8
#: share of a row's mass within which a decision may round either way
BAND = 1e-5


def answers(row) -> List[Tuple[float, float, int]]:
    """Every (mean, std, length) that Algorithm 1 gives for ``row`` under a
    rounding of its decisions by up to ``BAND``; one for most rows."""
    u = np.asarray(row, np.float64)
    n = len(u)
    total = float(u.sum())
    if total <= 0.0:
        return [(0.0, 0.0, n)]
    edge = np.diff(np.concatenate([[0], (u > 0).astype(np.int8), [0]]))
    st, en = np.flatnonzero(edge == 1), np.flatnonzero(edge == -1)
    csum = np.concatenate([[0.0], np.cumsum(u)])
    seg = csum[en] - csum[st]
    gaps = st[1:] - en[:-1]
    band = BAND * total
    low, high = MASS * total - band, MASS * total + band
    out = []
    for g in [0] + sorted(set(gaps.tolist())):
        cut = np.concatenate([[True], gaps > g])
        mass = np.bincount(np.cumsum(cut) - 1, seg)
        top = mass.max()
        if top < low:
            continue
        first, last = st[cut], en[np.concatenate([cut[1:], [True]])]
        for k in np.flatnonzero(mass >= top - band):
            x = u[first[k]:last[k]]
            a = (float(x.mean()), float(x.std()), int(last[k] - first[k]))
            if a not in out:
                out.append(a)
        if top >= high:
            break
    return out


def stats(u) -> np.ndarray:
    """(E, 3): each row's first answer, in the layout of the program's
    ``batch_stats``."""
    return np.array([answers(r)[0] for r in np.asarray(u)],
                    np.float64).reshape(-1, 3)


def gaps(u, got) -> Tuple[float, int]:
    """The widest gap of a moment of ``got`` from the reference's, and the
    rows whose length differs from every answer the reference accepts."""
    moment, miss = 0.0, 0
    for row, (mean, std, length) in zip(np.asarray(u), np.asarray(got)):
        ok = answers(row)
        same = [a for a in ok if a[2] == length]
        miss += not same
        gap = min(max(abs(mean - a[0]), abs(std - a[1]))
                  for a in same or ok)
        moment = max(moment, gap if np.isfinite(gap) else float("inf"))
    return moment, miss
