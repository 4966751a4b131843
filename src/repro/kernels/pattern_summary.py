"""Pallas TPU kernel for PerfTracker's behavior-pattern summarization
(paper §4.2, Algorithm 1) — the observability hot loop at 10 kHz x 20 s x
thousands of events per worker.

TPU-native re-think (DESIGN.md §2): the paper's per-event sequential binary
search becomes, per event row, ``iters = ceil(log2(n+1))+1`` vectorized
feasibility sweeps over the sample axis.  With

  csum(i)  = u[0] + ... + u[i]                       (f32 prefix sum)
  rl(i)    = zero-run length ending at i             (0 at a nonzero sample)

a sample is a *split* for gap bound g when ``rl(i) > g`` (it sits inside a
zero run longer than g); regions are the maximal runs of non-split samples.
Because csum never decreases and a split sample is a zero,

  base(i)  = max over splits j <= i of csum(j)       (mass before i's region)
  mass(i)  = csum(i) - base(i)                       (region mass up to i)

and g is feasible when ``max_i mass(i)`` reaches the row's target: the
numpy backend's float64 rule, ``0.8 * total - 1e-9``, computed on the host
by ``row_targets`` and rounded up to f32 (an f32 mass reaches the rounded
value exactly when it reaches the f64 one).  Only ``base`` and
the row max depend on g: ``csum`` and ``rl`` are computed once by
``_prefix_kernel`` and read back by every sweep of ``_search_kernel``.

Layout: 8 event rows (one sublane group) by tiles of ``TILE`` samples (lanes).
Every sweep walks the tiles in order and carries its prefix state (the
running base, the last split, the row accumulators) in VMEM scratch, so
fast memory holds a few (8, T) tiles whatever the window length.  Prefix
scans inside a tile are log-step shift-and-combine passes (``pltpu.roll``);
gathers are masked reductions; argmax is max, then the least index of it.

Output per event: (mean, std, count) over the critical execution duration.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.patterns import mass_target

#: samples per tile: a few (8, TILE) f32 tiles stay well inside VMEM
TILE = 8192
#: sweep phases of ``_search_kernel`` after the ``iters`` search sweeps
_SELECT, _REGION, _SPREAD = 0, 1, 2
_N_PHASES = 3


def _scan(x, op, fill):
    """Inclusive prefix scan of ``op`` along the lanes of a (rows, T) tile:
    log2(T) steps, each combining x with itself shifted right (the shifted-in
    lanes take ``fill``, the identity of ``op``)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    shift = 1
    while shift < x.shape[1]:
        moved = pltpu.roll(x, shift, 1)
        x = op(x, jnp.where(lane >= shift, moved, fill))
        shift *= 2
    return x


def _col(ref):
    """A per-row carried value: (8, 1) from its lane-replicated scratch."""
    return ref[:, :1]


def _put(ref, value):
    ref[...] = jnp.broadcast_to(value, ref.shape).astype(ref.dtype)


def _rowmax(x):
    return jnp.max(x, axis=1, keepdims=True)


def _rowmin(x):
    return jnp.min(x, axis=1, keepdims=True)


def _rowsum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _prefix_kernel(u_ref, csum_ref, rl_ref, total_ref, run_sum, last_nz):
    t = pl.program_id(1)
    T = u_ref.shape[1]

    @pl.when(t == 0)
    def _():
        _put(run_sum, 0.0)
        _put(last_nz, -1)

    u = u_ref[...]
    idx = jax.lax.broadcasted_iota(jnp.int32, u.shape, 1) + t * T
    csum = _scan(u, jnp.add, 0.0) + _col(run_sum)
    lnz = jnp.maximum(_scan(jnp.where(u > 0.0, idx, -1), jnp.maximum, -1),
                      _col(last_nz))
    csum_ref[...] = csum
    rl_ref[...] = idx - lnz
    # both are nondecreasing along the row: the row max is the last value
    _put(run_sum, _rowmax(csum))
    _put(last_nz, _rowmax(lnz))
    _put(total_ref, _rowmax(csum))


def _search_kernel(total_ref, target_ref, csum_ref, rl_ref, u_ref, out_ref,
                   base_c, mass_c, s1, s2,
                   lo_g, hi_g, best_g, split_c, rs, lo, hi,
                   *, n: int, n_valid: int, iters: int):
    p, t = pl.program_id(1), pl.program_id(2)
    T = csum_ref.shape[1]
    last_tile = t == pl.num_programs(2) - 1
    big = n + 1
    idx = jax.lax.broadcasted_iota(jnp.int32, csum_ref.shape, 1) + t * T
    total = _col(total_ref)

    @pl.when((p == 0) & (t == 0))
    def _():
        _put(lo_g, 0)
        _put(hi_g, n)
        _put(best_g, n)
        _put(lo, big)
        _put(hi, 0)
        _put(s1, 0.0)
        _put(s2, 0.0)

    @pl.when(t == 0)
    def _():
        _put(base_c, 0.0)
        _put(mass_c, -1.0)
        _put(split_c, -1)

    def masses(g):
        """Split mask and region mass of this tile at gap bound g (8, 1)."""
        split = rl_ref[...] > g
        csum = csum_ref[...]
        base = jnp.maximum(
            _scan(jnp.where(split, csum, 0.0), jnp.maximum, 0.0),
            _col(base_c))
        _put(base_c, _rowmax(base))
        return split, jnp.where(split, -1.0, csum - base)

    def last_split(split):
        sp = jnp.maximum(_scan(jnp.where(split, idx, -1), jnp.maximum, -1),
                         _col(split_c))
        _put(split_c, _rowmax(sp))
        return sp

    @pl.when(p < iters)
    def _search():
        g = (_col(lo_g) + _col(hi_g)) // 2
        _, mass = masses(g)
        _put(mass_c, jnp.maximum(_col(mass_c), _rowmax(mass)))

        @pl.when(last_tile)
        def _():
            active = _col(lo_g) <= _col(hi_g)
            feas = active & (_col(mass_c) >= _col(target_ref))
            miss = active & ~feas
            _put(best_g, jnp.where(feas, g, _col(best_g)))
            _put(hi_g, jnp.where(feas, g - 1, _col(hi_g)))
            _put(lo_g, jnp.where(miss, g + 1, _col(lo_g)))

    @pl.when(p == iters + _SELECT)
    def _select():
        # the region holding the leftmost max-mass sample (mass never
        # decreases inside a region, so that region has the max final mass)
        split, mass = masses(_col(best_g))
        sp = last_split(split)
        m = _rowmax(mass)
        first = _rowmin(jnp.where(mass == m, idx, big))
        start = _rowmax(jnp.where(idx == first, sp, -1)) + 1
        better = m > _col(mass_c)
        _put(mass_c, jnp.where(better, m, _col(mass_c)))
        _put(rs, jnp.where(better, start, _col(rs)))

    @pl.when(p == iters + _REGION)
    def _region():
        # [lo, hi): first to last nonzero sample of the selected region
        u = u_ref[...]
        split = rl_ref[...] > _col(best_g)
        sp = last_split(split)
        member = (~split) & (sp + 1 == _col(rs)) & (u > 0.0)
        _put(lo, jnp.minimum(_col(lo), _rowmin(jnp.where(member, idx, big))))
        _put(hi, jnp.maximum(_col(hi), _rowmax(jnp.where(member, idx + 1, 0))))
        _put(s1, _col(s1) + _rowsum(jnp.where(member, u, 0.0)))

    @pl.when(p == iters + _SPREAD)
    def _spread():
        u = u_ref[...]
        cnt = jnp.maximum(_col(hi) - _col(lo), 1).astype(jnp.float32)
        mean = _col(s1) / cnt
        inside = (idx >= _col(lo)) & (idx < _col(hi))
        _put(s2, _col(s2) + _rowsum(
            jnp.where(inside, jnp.square(u - mean), 0.0)))

        @pl.when(last_tile)
        def _():
            empty = total <= 0.0          # all-zero row: whole window, 0/0
            std = jnp.sqrt(_col(s2) / cnt)
            lane = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
            out = jnp.where(lane == 0, jnp.where(empty, 0.0, mean),
                            jnp.where(lane == 1, jnp.where(empty, 0.0, std),
                                      jnp.where(empty, float(n_valid), cnt)))
            out_ref[...] = jnp.where(lane < 3, out, 0.0)


def search_iters(n: int) -> int:
    """Binary-search sweeps that settle every gap bound in [0, n]."""
    return max(1, math.ceil(math.log2(n + 1)) + 1)


def row_targets(u) -> np.ndarray:
    """(E,) f32 region mass each row's critical duration must reach: the
    numpy backend's ``mass_target`` of the float64 row sum, on the host,
    rounded up to the next f32."""
    t = mass_target(np.asarray(u).sum(axis=1, dtype=np.float64))
    t32 = t.astype(np.float32)
    return np.where(t32 < t, np.nextafter(t32, np.float32(np.inf)), t32)


def pattern_summary(u, target, block_events: int = 8,
                    interpret: bool = True):
    """u: (E, n) utilization samples in [0,1] (zero-padded rows ok);
    target: (E,) f32 from ``row_targets(u)``.  Returns (E, 3) float32:
    [mean, std, count] of each row's critical execution duration
    (all-zero rows: [0, 0, n]).

    Rows pad to ``block_events`` and samples to a whole number of tiles of
    ``TILE`` lanes (at most the row, rounded up to 128); trailing zeros
    never change the selected duration."""
    E, n = u.shape
    be = block_events
    T = min(TILE, 128 * pl.cdiv(n, 128))
    n_pad = T * pl.cdiv(n, T)
    u = jnp.pad(u.astype(jnp.float32), ((0, (-E) % be), (0, n_pad - n)))
    Ep = u.shape[0]
    target = jnp.broadcast_to(
        jnp.pad(target.astype(jnp.float32), (0, Ep - E))[:, None], (Ep, 128))
    nt = n_pad // T
    iters = search_iters(n_pad)
    row_tile = pl.BlockSpec((be, T), lambda i, t: (i, t))
    row_out = pl.BlockSpec((be, 128), lambda i, t: (i, 0))
    f32 = lambda: pltpu.VMEM((be, 128), jnp.float32)      # noqa: E731
    i32 = lambda: pltpu.VMEM((be, 128), jnp.int32)        # noqa: E731

    csum, rl, total = pl.pallas_call(
        _prefix_kernel,
        grid=(Ep // be, nt),
        in_specs=[row_tile],
        out_specs=[row_tile, row_tile, row_out],
        out_shape=[jax.ShapeDtypeStruct((Ep, n_pad), jnp.float32),
                   jax.ShapeDtypeStruct((Ep, n_pad), jnp.int32),
                   jax.ShapeDtypeStruct((Ep, 128), jnp.float32)],
        scratch_shapes=[f32(), i32()],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(u)

    sweep = lambda i, p, t: (i, t)                        # noqa: E731
    out = pl.pallas_call(
        functools.partial(_search_kernel, n=n_pad, n_valid=n, iters=iters),
        grid=(Ep // be, iters + _N_PHASES, nt),
        in_specs=[pl.BlockSpec((be, 128), lambda i, p, t: (i, 0)),
                  pl.BlockSpec((be, 128), lambda i, p, t: (i, 0)),
                  pl.BlockSpec((be, T), sweep),
                  pl.BlockSpec((be, T), sweep),
                  # u is read only by the last two sweeps: parked on its
                  # first tile until then, so the search sweeps skip its DMA
                  pl.BlockSpec((be, T), lambda i, p, t: (
                      i, jnp.where(p > iters + _SELECT, t, 0)))],
        out_specs=pl.BlockSpec((be, 128), lambda i, p, t: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Ep, 128), jnp.float32),
        scratch_shapes=[f32(), f32(), f32(), f32(),
                        i32(), i32(), i32(), i32(), i32(), i32(), i32()],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(total, target, csum, rl, u)
    return out[:E, :3]
