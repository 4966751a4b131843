"""Mixture-of-Experts layer with expert parallelism.

Scheme (DESIGN.md §4, "replicated-token EP"): activations are sharded over
the data axes and *replicated* over the model axis; experts are sharded over
the model axis. Each model shard dispatches the tokens it already holds to
its local experts (capacity-bounded, sort-based — scatter/gather, **no
one-hot dispatch einsums**, which would poison HLO_FLOPs), computes the
grouped expert FFN, and the partial outputs are summed with a single
psum over the model axis — the same collective a Megatron row-parallel MLP
would issue, so EP adds no extra collective class.

Implemented with shard_map when a mesh is present; identical local math runs
un-mapped on a single device (smoke tests).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L

Array = jax.Array


def init_moe(key, cfg, stack=(), dtype=jnp.float32):
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 4)
    p = {
        "router": L.dense_init(ks[0], (d, E), stack, jnp.float32),
        # fan-in d, as in L.init_mlp
        "wi": L.dense_init(ks[1], (E, d, 2 * ff), stack, dtype).reshape(
            stack + (E, d, 2, ff)),
        "wo": L.dense_init(ks[2], (E, ff, d), stack, dtype),
    }
    if cfg.num_shared_experts:
        p["shared"] = L.init_mlp(ks[3], d, ff * cfg.num_shared_experts,
                                 cfg.mlp, cfg.use_bias, stack, dtype)
    return p


def _capacity(tokens_local: int, cfg) -> int:
    c = int(math.ceil(tokens_local * cfg.top_k / cfg.num_experts
                      * cfg.capacity_factor))
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU lane alignment


def _moe_local(p, x: Array, cfg, e_start: int, e_count: int, capacity: int
               ) -> Tuple[Array, Array]:
    """Dispatch + grouped expert FFN over the local expert slice.
    x: (T, d) local tokens; p['wi']: (e_count, d, 2, ff) (FSDP-gathered).
    Returns (y (T, d) partial output, aux load-balancing stats (2E,))."""
    T, d = x.shape
    E, k = cfg.num_experts, cfg.top_k
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)                 # (T, E)
    gate_vals, idx = jax.lax.top_k(probs, k)                # (T, k)

    eid = idx.reshape(-1)                                   # (T*k,)
    tid = jnp.repeat(jnp.arange(T), k)
    gate = gate_vals.reshape(-1)

    order = jnp.argsort(eid, stable=True)
    eid_s, tid_s, gate_s = eid[order], tid[order], gate[order]
    counts = jnp.bincount(eid_s, length=E)                  # (E,)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * k) - starts[eid_s]                 # rank within expert
    keep = ((pos < capacity) & (eid_s >= e_start)
            & (eid_s < e_start + e_count))
    le = jnp.where(keep, eid_s - e_start, 0)
    sp = jnp.where(keep, pos, 0)

    buf = jnp.zeros((e_count, capacity, d), x.dtype)
    vals = jnp.where(keep[:, None], x[tid_s], 0)
    buf = buf.at[le, sp].add(vals)                          # scatter dispatch

    h = jnp.einsum("ecd,edgf->ecgf", buf, p["wi"])
    act = (jax.nn.silu if cfg.mlp == "swiglu" else jax.nn.gelu)
    h = act(h[:, :, 0, :]) * h[:, :, 1, :]
    out = jnp.einsum("ecf,efd->ecd", h, p["wo"])            # (e_count, C, d)

    tok_out = out[le, sp]                                   # gather combine
    w = jnp.where(keep, gate_s, 0.0).astype(x.dtype)
    y = jnp.zeros((T, d), x.dtype).at[tid_s].add(tok_out * w[:, None])

    # load-balance stats: tokens-per-expert + mean router prob (GShard aux)
    frac_tokens = counts.astype(jnp.float32)
    mean_prob = probs.sum(axis=0)
    return y, jnp.concatenate([frac_tokens, mean_prob])


def aux_loss_from_stats(stats: Array, cfg, total_tokens: float) -> Array:
    E = cfg.num_experts
    f = stats[:E] / jnp.maximum(total_tokens * cfg.top_k, 1.0)
    pbar = stats[E:] / jnp.maximum(total_tokens, 1.0)
    return E * jnp.sum(f * pbar) * cfg.aux_loss_weight


def apply_moe(p, x: Array, cfg, dist=None) -> Tuple[Array, Array]:
    """x: (B, S, d). Returns (y, aux stats (2E,) summed over the fleet)."""
    B, S, d = x.shape
    E = cfg.num_experts

    if dist is None or dist.mesh is None:
        y, stats = _moe_local(p, x.reshape(B * S, d), cfg, 0, E,
                              _capacity(B * S, cfg))
        routed = y.reshape(B, S, d)
    else:
        mesh = dist.mesh
        dp, tp = dist.dp_axes, dist.tp_axis
        ep = dist.tp_size
        assert E % ep == 0, (E, ep)
        e_loc = E // ep
        t_loc = (B // dist.dp_size) * S
        cap = _capacity(t_loc, cfg)

        # ZeRO-1 experts, and serving (fsdp off): weights resident, no
        # per-layer gathers
        zero1 = getattr(dist, "zero1_moe", False) or not dist.fsdp
        pspec = {"router": P(None, None),
                 "wi": P(tp, None, None, None) if zero1
                 else P(tp, dp, None, None),
                 "wo": P(tp, None, None) if zero1 else P(tp, None, dp)}
        routed_p = {k: p[k] for k in ("router", "wi", "wo")}

        def body(pl, xl):
            if zero1:
                # ZeRO-1: bf16 experts already resident — no gathers
                wi, wo = pl["wi"], pl["wo"]
            else:
                # FSDP-gather the local experts' weights over the data axes
                wi = jax.lax.all_gather(pl["wi"], dp, axis=1, tiled=True)
                wo = jax.lax.all_gather(pl["wo"], dp, axis=2, tiled=True)
            eg = {"router": pl["router"], "wi": wi, "wo": wo}
            e0 = jax.lax.axis_index(tp) * e_loc
            T = xl.shape[0] * xl.shape[1]
            y, stats = _moe_local(eg, xl.reshape(T, xl.shape[2]), cfg,
                                  e0, e_loc, cap)
            y = jax.lax.psum(y, tp)               # combine expert partials
            # every model shard computes identical router stats for its
            # data shard's tokens -> divide the tp duplication out
            stats = jax.lax.psum(stats, (tp,) + tuple(dp)) / ep
            return y.reshape(xl.shape), stats

        routed, stats = jax.shard_map(
            body, mesh=mesh,
            in_specs=(pspec, P(dp, None, None)),
            out_specs=(P(dp, None, None), P()),
            check_vma=False,
        )(routed_p, x)

    if "shared" in p:
        routed = routed + L.apply_mlp(p["shared"], x, cfg.mlp)
    return routed, stats
