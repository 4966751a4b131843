"""The three summarize backends (DESIGN.md §3).

``python``  — the reference implementation: a per-row loop around the exact
              Algorithm-1 binary search in ``repro.core.patterns`` (the
              oracle every other backend is tested against).
``numpy``   — batched: all E rows advance one shared binary-search step per
              pass, in *segment space* (one entry per nonzero run instead of
              per sample).  Same selection rules as the Pallas kernel
              (max-mass feasible region, leftmost tie).
``pallas``  — the TPU kernel ``repro.kernels.pattern_summary`` wired into the
              daemon pipeline; compiled on a TPU backend, interpret mode
              everywhere else.
"""
from __future__ import annotations

from typing import Set, Tuple

import numpy as np

from repro.summarize.base import register_backend


class PythonBackend:
    """Row-at-a-time oracle (the pre-refactor hot loop, kept as ground truth)."""

    name = "python"

    def available(self) -> bool:
        return True

    def batch_stats(self, u: np.ndarray) -> np.ndarray:
        from repro.core.patterns import critical_duration
        u = np.asarray(u)
        out = np.zeros((u.shape[0], 3), np.float64)
        for i, row in enumerate(u):
            if float(row.sum()) <= 0.0:
                out[i] = (0.0, 0.0, len(row))
                continue
            lo, hi = critical_duration(row)
            seg = row[lo:hi].astype(np.float64)
            out[i] = (seg.mean(), seg.std(), hi - lo)
        return out


class NumpyBackend:
    """Vectorized Algorithm 1 in *segment space*.

    Each row is compressed once into its nonzero runs (segments): per
    segment, the prefix sum at its end, the prefix sum just before its
    start, and the zero-gap separating it from the previous segment.  The
    binary search over gap bounds then runs entirely on the ``(E, S)``
    segment arrays (S = max segments per row — usually a small fraction of
    n), with each region-start prefix sum recovered gather-free by a cummax
    over the monotone per-segment prefix sums.  Region masses are exactly
    the f32 prefix-sum differences the sample-space formulation computes,
    and segment boundaries are nonzero samples, so region trimming is free.
    Galloping probes (0, then ~doubling from below, capped by the bisection
    midpoint) finish dense rows — whose optimal gap bound is 0-2 — in one
    or two passes."""

    name = "numpy"

    def available(self) -> bool:
        return True

    def batch_stats(self, u: np.ndarray) -> np.ndarray:
        # late import: patterns imports the summarize package back
        from repro.core.patterns import mass_target
        u = np.ascontiguousarray(u, np.float32)
        E, n = u.shape
        if E == 0 or n == 0:
            return np.zeros((E, 3))
        nz = u > 0.0
        csum = np.cumsum(u, axis=1, dtype=np.float32)
        # float64 row sum, NOT csum[:, -1]: the python oracle's target
        # comes from the same f64 sum (exact for f32 addends, so identical
        # under any zero-padding width), while sequential-f32 cumsum drifts
        # from it by enough to flip borderline feasibility on long rows
        total = u.sum(axis=1, dtype=np.float64)
        target = mass_target(total)
        empty = total <= 0.0
        all_empty = np.stack([np.zeros(E), np.zeros(E),
                              np.full(E, float(n))], axis=1)

        # -- one-time segmentation: nonzero runs as (row, start, end) -----
        prev = np.empty_like(nz)
        prev[:, 0] = False
        prev[:, 1:] = nz[:, :-1]
        nxt = np.empty_like(nz)
        nxt[:, -1] = False
        nxt[:, :-1] = nz[:, 1:]
        r_st, c_st = np.nonzero(nz & ~prev)          # row-major order
        c_en = np.nonzero(nz & ~nxt)[1]              # pairs with c_st
        if r_st.size == 0:
            return all_empty
        K = np.bincount(r_st, minlength=E)           # segments per row
        S = int(K.max())
        off = np.concatenate([[0], np.cumsum(K)[:-1]])
        o = np.arange(r_st.size) - off[r_st]         # segment ordinal

        BIG = np.int32(n + 1)
        gapb = np.full((E, S), BIG, np.int32)        # zero-gap before seg k
        cs_end = np.full((E, S), -1.0, np.float32)   # csum at segment end
        cs_st0 = np.zeros((E, S), np.float32)        # csum before seg start
        st_col = np.zeros((E, S), np.int32)
        en_col = np.zeros((E, S), np.int32)
        st_col[r_st, o] = c_st
        en_col[r_st, o] = c_en
        cs_end[r_st, o] = csum[r_st, c_en]
        cs_st0[r_st, o] = np.where(
            c_st > 0, csum[r_st, np.maximum(c_st - 1, 0)], np.float32(0.0))
        j = np.flatnonzero(o > 0)  # row-major: entry j-1 is segment o-1
        gapb[r_st[j], o[j]] = c_st[j] - c_en[j - 1] - 1

        # -- binary search over gap bounds, all rows in parallel ----------
        # g* <= the row's largest interior gap (no splits there => one
        # region holding all mass); single-segment rows need no search
        max_gap = np.where(gapb == BIG, 0, gapb).max(axis=1).astype(np.int32)
        best_g = max_gap.copy()
        lo_g = np.zeros((E,), np.int32)
        hi_g = np.where(empty, np.int32(-1), max_gap - 1)

        while True:
            act = lo_g <= hi_g
            if not act.any():
                break
            g = np.minimum((lo_g + hi_g) >> 1,
                           np.where(lo_g == 0, 0, 2 * lo_g))
            split = gapb > g[:, None]                # k=0 always splits
            base = np.maximum.accumulate(
                np.where(split, cs_st0, np.float32(0.0)), axis=1)
            mass = cs_end - base                     # padded entries <= -1
            feas = act & (mass.max(axis=1).astype(np.float64) >= target)
            miss = act & ~feas
            best_g[feas] = g[feas]
            hi_g[feas] = g[feas] - 1
            lo_g[miss] = g[miss] + 1

        # -- best region at g*: max-mass group, leftmost on ties ----------
        split = gapb > best_g[:, None]
        kidx = np.broadcast_to(np.arange(S, dtype=np.int32), (E, S))
        first_k = np.maximum.accumulate(
            np.where(split, kidx, np.int32(0)), axis=1)
        base = np.maximum.accumulate(
            np.where(split, cs_st0, np.float32(0.0)), axis=1)
        best_k = np.argmax(cs_end - base, axis=1)
        ar = np.arange(E)
        lo = st_col[ar, first_k[ar, best_k]]         # already zero-trimmed
        hi = en_col[ar, best_k] + 1

        # -- duration-weighted moments over [lo, hi) ----------------------
        idx = np.broadcast_to(np.arange(n, dtype=np.int32), (E, n))
        inside = (idx >= lo[:, None]) & (idx < hi[:, None])
        cnt = np.maximum((hi - lo).astype(np.float64), 1.0)
        mean = np.where(inside, u, 0).sum(axis=1, dtype=np.float64) / cnt
        var = np.where(inside,
                       np.square(u - mean[:, None].astype(np.float32)),
                       0).sum(axis=1, dtype=np.float64) / cnt
        return np.where(empty[:, None], all_empty,
                        np.stack([mean, np.sqrt(var),
                                  (hi - lo).astype(np.float64)], axis=1))


def _bucket(x: int, floor: int) -> int:
    """Smallest power of two >= max(x, floor)."""
    return 1 << (max(int(x), floor) - 1).bit_length()


class PallasBackend:
    """Batches rows through the TPU kernel; interpret mode everywhere else.

    Every call pads its ``(E, n)`` matrix to power-of-two buckets (zero rows
    and trailing zero samples change no row's result), so a run of many
    profiling windows compiles a small, fixed set of kernel shapes;
    ``shapes`` records the buckets this instance has run."""

    name = "pallas"

    def __init__(self, block_events: int = 8):
        self.block_events = block_events
        self.shapes: Set[Tuple[int, int]] = set()
        self._jnp = None

    def _modules(self):
        if self._jnp is None:
            import jax.numpy as jnp
            from repro.kernels.ops import pattern_summary
            from repro.kernels.pattern_summary import row_targets
            self._jnp = jnp
            self._kernel = pattern_summary
            self._targets = row_targets
        return self._jnp, self._kernel

    def available(self) -> bool:
        """Runnable wherever jax is installed.  The spec lookup lets a
        jax-free process ask without paying the jax import; once jax is
        there, a kernel that fails to load is an error, not a fallback."""
        if self._jnp is not None:
            return True
        import importlib.util
        if importlib.util.find_spec("jax") is None:
            return False
        self._modules()
        return True

    def auto_ok(self) -> bool:
        """Only the ``auto`` default: compiled-on-TPU pallas is fast, the
        interpreter is not — don't auto-pick it on CPU hosts.  Asks only a
        process whose jax backend is already up (a TPU training process
        always is): probing would make a daemon or a fleet parent take the
        chip away from the job it watches."""
        import sys
        if "jax" not in sys.modules:
            return False
        from jax._src import xla_bridge
        if not xla_bridge.backends_are_initialized():
            return False
        import jax
        return jax.default_backend() == "tpu"

    def interpret(self) -> bool:
        from repro.kernels.ops import auto_interpret
        return auto_interpret()

    def batch_stats(self, u: np.ndarray) -> np.ndarray:
        E, n = u.shape
        if E == 0 or n == 0:
            return np.zeros((E, 3))
        jnp, kernel = self._modules()
        shape = (_bucket(E, self.block_events), _bucket(n, 128))
        padded = np.zeros(shape, np.float32)
        padded[:E, :n] = u
        target = np.zeros(shape[0], np.float32)   # padded rows: all zero
        target[:E] = self._targets(padded[:E, :n])
        self.shapes.add(shape)
        out = np.asarray(kernel(jnp.asarray(padded), jnp.asarray(target),
                                block_events=self.block_events,
                                interpret=self.interpret()),
                         np.float64)[:E]
        # an all-zero row reports the padded width; no row is wider than n
        out[:, 2] = np.minimum(out[:, 2], n)
        return out


register_backend("python", PythonBackend)
register_backend("numpy", NumpyBackend)
register_backend("pallas", PallasBackend)
