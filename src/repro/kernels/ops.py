"""Jit'd public wrappers for the Pallas kernels.

Off a TPU the kernels run with ``interpret=True`` (the Pallas interpreter
executes the kernel body for correctness); on a TPU backend the same calls
lower to Mosaic.  ``auto_interpret()`` is the one rule that decides.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.pattern_summary import pattern_summary as _psum
from repro.kernels.ssd_scan import ssd_scan as _ssd


def auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("causal", "window", "softcap", "scale",
                                   "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    scale=0.0, block_q=128, block_k=128, interpret=None):
    interpret = auto_interpret() if interpret is None else interpret
    return _flash(q, k, v, causal=causal, window=window, softcap=softcap,
                  scale=scale, block_q=block_q, block_k=block_k,
                  interpret=interpret)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, chunk=128, interpret=None):
    interpret = auto_interpret() if interpret is None else interpret
    return _ssd(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)


@partial(jax.jit, static_argnames=("block_events", "interpret"))
def pattern_summary(u, target, block_events=8, interpret=None):
    interpret = auto_interpret() if interpret is None else interpret
    return _psum(u, target, block_events=block_events, interpret=interpret)
