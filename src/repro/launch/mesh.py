"""Mesh construction.

Meshes are built by FUNCTIONS (not module constants) so importing this
module never touches jax device state.  Every axis is ``AxisType.Auto``:
the sharding rules in ``repro.dist.sharding`` place parameters and batches
and leave the rest to the compiler's propagation (the jax default of
Explicit axes would demand an out-sharding on every gather).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None):
    """Any (shape, axes) the device pool allows (elastic-scaling entry
    point); ``devices`` defaults to all of ``jax.devices()``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips (pod = DCN axis)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def parse_mesh(spec: str):
    """``"DATAxMODEL"`` (e.g. ``"2x2"``) over the devices jax reports, as
    they are: the product must equal the device count."""
    shape = tuple(int(s) for s in spec.lower().split("x"))
    n = len(jax.devices())
    if len(shape) != 2 or shape[0] * shape[1] != n:
        raise ValueError(f"--mesh {spec!r} needs DATAxMODEL with "
                         f"DATA*MODEL == {n} devices")
    return make_mesh(shape, ("data", "model"))
