"""Pure-jnp/numpy oracles for every Pallas kernel (the allclose targets for
tests/test_kernels.py shape/dtype sweeps)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.patterns import critical_duration

NEG_INF = -1.0e30


# -- flash attention ---------------------------------------------------------

def attention_oracle(q, k, v, *, causal=True, window=0, softcap=0.0,
                     scale=0.0):
    """Unblocked softmax attention with GQA. q: (B,Sq,H,D); k/v: (B,S,KV,D)."""
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = scale or 1.0 / math.sqrt(D)
    qg = q.reshape(B, Sq, KV, G, D)
    s = jnp.einsum("btkgd,bskd->btkgs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    qpos = jnp.arange(Sq)
    kpos = jnp.arange(k.shape[1])
    mask = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window:
        mask &= qpos[:, None] - kpos[None, :] < window
    s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("btkgs,bskd->btkgd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


# -- SSD scan ----------------------------------------------------------------

def ssd_oracle(x, dt, A, Bm, Cm):
    """Naive sequential state-space recurrence (fp32).
    x: (B,S,H,P); dt: (B,S,H); A: (H,); Bm/Cm: (B,S,G,N)."""
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    xf = np.asarray(x, np.float64)
    dtf = np.asarray(dt, np.float64)
    Af = np.asarray(A, np.float64)
    Bf = np.repeat(np.asarray(Bm, np.float64), rep, axis=2)  # (B,S,H,N)
    Cf = np.repeat(np.asarray(Cm, np.float64), rep, axis=2)
    state = np.zeros((B, H, N, P))
    y = np.zeros_like(xf)
    for t in range(S):
        a = np.exp(dtf[:, t] * Af)                    # (B,H)
        state = state * a[..., None, None] + np.einsum(
            "bhn,bhp,bh->bhnp", Bf[:, t], xf[:, t], dtf[:, t])
        y[:, t] = np.einsum("bhn,bhnp->bhp", Cf[:, t], state)
    return jnp.asarray(y, x.dtype)


# -- pattern summary -----------------------------------------------------------

def pattern_summary_oracle(u: np.ndarray) -> np.ndarray:
    """Per-row (mean, std, count) via the exact Algorithm-1 search
    (repro.core.patterns.critical_duration); all-zero rows: (0, 0, n)."""
    out = []
    for row in np.asarray(u, np.float64):
        n = len(row)
        if row.sum() <= 0:
            out.append((0.0, 0.0, float(n)))
            continue
        lo, hi = critical_duration(row)
        seg = row[lo:hi]
        out.append((float(seg.mean()), float(seg.std()), float(hi - lo)))
    return np.asarray(out, np.float32)
