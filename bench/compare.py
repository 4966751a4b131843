"""The numbers ``correct`` compares, and the check of each against its
limit.

Training is compared by slice: every layer of a stacked weight is a leaf of
its own.  A gradient or a change is read by its norm, and a gap is the
distance between the program's norm and the reference's, over the larger
of the reference's norm of that slice and the median slice's.  The change
leaves out slices whose reference gradient is under a thousandth of the
median slice's: AdamW moves those by round-off alone.  The change is also
read by its median slice: the worst slice of the change is a key bias,
whose gradient all but cancels under softmax, and it swings from seed to
seed.
"""
from __future__ import annotations

import functools
from typing import Dict, Iterable, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: gradients under this share of the median slice's are round-off
ROUNDOFF_GRAD = 1e-3


@functools.partial(jax.jit, static_argnums=1)
def _leaf_norms(x, stacked: bool):
    x = x.astype(jnp.float32)
    x = x.reshape(x.shape[0], -1) if stacked else x.reshape(1, -1)
    return jnp.sqrt(jnp.sum(jnp.square(x), -1))


@functools.partial(jax.jit, static_argnums=2)
def _leaf_change(after, before, stacked: bool):
    return _leaf_norms(after.astype(jnp.float32)
                       - before.astype(jnp.float32), stacked)


def _stacked(path: str) -> bool:
    return path.startswith("blocks/")


def slice_norms(flat: Dict[str, object]) -> Dict[str, np.ndarray]:
    """path -> norms of its slices (one per layer of a stacked leaf),
    one leaf at a time."""
    return {k: np.asarray(_leaf_norms(v, _stacked(k)), np.float64)
            for k, v in flat.items()}


def change_norms(after: Dict[str, object], before: Dict[str, object]
                 ) -> Dict[str, np.ndarray]:
    """path -> norms of the slices of ``after - before``."""
    return {k: np.asarray(_leaf_change(after[k], before[k], _stacked(k)),
                          np.float64) for k in before}


def _pairs(prog, ref, keep=None):
    for k in sorted(ref):
        r = np.asarray(ref[k], np.float64)
        p = np.asarray(prog[k], np.float64)
        if p.shape != r.shape:
            raise ValueError(f"{k}: program has {p.shape} slices, the "
                             f"reference {r.shape}")
        for i in range(len(r)):
            if keep is None or keep[k][i]:
                yield f"{k}[{i}]", p[i], r[i]


def slice_gaps(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
               keep=None) -> Dict[str, float]:
    """slice -> gap of its norms."""
    pairs = list(_pairs(prog, ref, keep))
    floor = float(np.median([r for _, _, r in pairs]))
    out = {}
    for name, p, r in pairs:
        gap = abs(p - r) / max(r, floor, 1e-30)
        out[name] = float(gap) if np.isfinite(gap) else float("inf")
    return out


def norm_gap(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
             keep=None) -> Tuple[float, str]:
    """Worst gap of slice norms, and the slice it is in."""
    gaps = slice_gaps(prog, ref, keep)
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def moving(ref_grad: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Slices that the reference's gradient moves beyond round-off."""
    med = float(np.median(np.concatenate(list(ref_grad.values()))))
    return {k: np.asarray(v) >= ROUNDOFF_GRAD * med
            for k, v in ref_grad.items()}


def loss_gap(prog: Iterable[float], ref: Iterable[float]) -> float:
    gaps = [abs(p - r) / abs(r) for p, r in zip(prog, ref)]
    return float(max(g if np.isfinite(g) else float("inf") for g in gaps))


def training_numbers(prog: dict, ref: dict) -> Dict[str, float]:
    """The training numbers: each step's loss, the first clipped gradient
    (worst slice), and the change of the weights over the steps (worst
    slice, and the median slice's gap)."""
    change = slice_gaps(prog["change"], ref["change"], moving(ref["grad"]))
    return {"loss_gap": loss_gap(prog["loss"], ref["loss"]),
            "grad_gap": norm_gap(prog["grad"], ref["grad"])[0],
            "change_gap": max(change.values()),
            "change_median_gap": float(np.median(list(change.values())))}


def check(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, dict]]:
    """Each number beside its limit; correct when every one is within.
    A number that is not finite fails, as does a limit with no number."""
    out, ok = {}, True
    for name, limit in limits.items():
        v = numbers.get(name)
        good = v is not None and np.isfinite(v) and v <= limit
        ok &= bool(good)
        shown = v if v is None or np.isfinite(v) else str(v)
        out[name] = {"value": shown, "limit": limit}
    return ok, out


def lines(checks: Dict[str, dict]) -> List[str]:
    return [f"check {k}: {c['value']} (limit {c['limit']})"
            for k, c in checks.items()]
