"""Kernel micro-bench: wall us/call for the XLA reference paths on CPU (the
Pallas kernels run in interpret mode here, so wall numbers are reported for
the XLA oracle paths; TPU perf is covered by §Roofline in EXPERIMENTS.md)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.models.attention import AttnSpec, blocked_attention
from repro.models.ssm import ssd_chunked
from repro.kernels import ops


def _t(fn, *args, reps=5):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6


def run():
    rows = []
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    B, S, H, KV, D = 1, 512, 4, 2, 64
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, KV, D))
    v = jax.random.normal(ks[2], (B, S, KV, D))
    spec_u = AttnSpec(q_block=128, kv_block=128, folded=False)
    spec_f = AttnSpec(q_block=128, kv_block=128, folded=True)
    f_u = jax.jit(lambda q, k, v: blocked_attention(q, k, v, spec_u))
    f_f = jax.jit(lambda q, k, v: blocked_attention(q, k, v, spec_f))
    t_u = _t(f_u, q, k, v)
    t_f = _t(f_f, q, k, v)
    rows.append(("kernels/blocked_attention_unfolded", t_u,
                 f"B{B}xS{S}xH{H}xD{D}"))
    rows.append(("kernels/blocked_attention_folded", t_f,
                 f"speedup={t_u/t_f:.2f}x (causal folding)"))

    Bs, Ss, Hs, P, G, N = 1, 512, 4, 32, 2, 16
    x = jax.random.normal(ks[0], (Bs, Ss, Hs, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bs, Ss, Hs)))
    A = -jnp.exp(jax.random.uniform(ks[2], (Hs,)))
    Bm = jax.random.normal(ks[3], (Bs, Ss, G, N))
    Cm = jax.random.normal(ks[4], (Bs, Ss, G, N))
    f_ssd = jax.jit(lambda *a: ssd_chunked(*a, 64)[0])
    rows.append(("kernels/ssd_chunked_xla", _t(f_ssd, x, dt, A, Bm, Cm),
                 f"B{Bs}xS{Ss}xH{Hs}xP{P}"))

    import numpy as np
    from repro.kernels.pattern_summary import row_targets
    u_np = np.clip(np.random.default_rng(0).normal(0.5, 0.3, (64, 512)),
                   0, 1).astype(np.float32)
    u, target = jnp.asarray(u_np), jnp.asarray(row_targets(u_np))
    rows.append(("kernels/pattern_summary_interpret",
                 _t(lambda u: ops.pattern_summary(u, target), u, reps=2),
                 "64 events x 512 samples (interpret mode)"))
    return rows


if __name__ == "__main__":
    for r in run():
        print(",".join(map(str, r)))
