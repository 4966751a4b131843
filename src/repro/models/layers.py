"""Core neural-net building blocks (pure-functional JAX, no flax).

Conventions:
  * params are nested dicts of jnp arrays;
  * ``init_*`` take a PRNG key and return params;
  * norm/softmax run in fp32 regardless of activation dtype;
  * weights carry a leading ``stack`` dim when used inside lax.scan layer
    stacks (init with ``stack=(L,)``).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


def dense_init(key, shape, stack=(), dtype=jnp.float32, scale: float = 1.0):
    """Truncated-normal fan-in init."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, stack + shape,
                                        jnp.float32) * std).astype(dtype)


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------

def init_norm(kind: str, d: int, stack=(), dtype=jnp.float32):
    p = {"scale": jnp.ones(stack + (d,), dtype)}
    if kind == "layer":
        p["bias"] = jnp.zeros(stack + (d,), dtype)
    return p


def apply_norm(p, x: Array, kind: str, eps: float) -> Array:
    xf = x.astype(jnp.float32)
    if kind == "rms":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    else:  # layernorm
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (..., S, H, D); positions: (..., S) int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = jnp.cos(ang)[..., None, :]                   # (..., S, 1, D/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU / plain GELU)
# ---------------------------------------------------------------------------

def init_mlp(key, d: int, ff: int, kind: str, use_bias: bool, stack=(),
             dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    gated = kind in ("swiglu", "geglu")
    p = {}
    if gated:
        # gate, up; fan-in d: drawn as one (d, 2*ff) matrix, then split
        p["wi"] = dense_init(k1, (d, 2 * ff), stack, dtype).reshape(
            stack + (d, 2, ff))
    else:
        p["wi"] = dense_init(k1, (d, ff), stack, dtype)
    p["wo"] = dense_init(k2, (ff, d), stack, dtype)
    if use_bias:
        p["bi"] = jnp.zeros(stack + ((2, ff) if gated else (ff,)), dtype)
        p["bo"] = jnp.zeros(stack + (d,), dtype)
    return p


def apply_mlp(p, x: Array, kind: str) -> Array:
    if kind in ("swiglu", "geglu"):
        h = jnp.einsum("...d,dgf->...gf", x, p["wi"])
        if "bi" in p:
            h = h + p["bi"]
        gate, up = h[..., 0, :], h[..., 1, :]
        act = jax.nn.silu(gate) if kind == "swiglu" else jax.nn.gelu(gate)
        h = act * up
    else:
        h = jnp.einsum("...d,df->...f", x, p["wi"])
        if "bi" in p:
            h = h + p["bi"]
        h = jax.nn.gelu(h)
    y = jnp.einsum("...f,fd->...d", h, p["wo"])
    if "bo" in p:
        y = y + p["bo"]
    return y


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------

def init_embed(key, vocab: int, d: int, dtype=jnp.float32):
    return {"table": dense_init(key, (vocab, d), (), dtype, scale=1.0)}


def embed_lookup(p, ids: Array, scale: bool, d: int) -> Array:
    out = jnp.take(p["table"], ids, axis=0)
    if scale:
        out = out * jnp.asarray(math.sqrt(d), out.dtype)
    return out


def lm_logits(table_or_head: Array, x: Array, softcap: float) -> Array:
    logits = jnp.einsum("...d,vd->...v", x, table_or_head)
    logits = logits.astype(jnp.float32)
    if softcap:
        logits = jnp.tanh(logits / softcap) * softcap
    return logits


def softcap(x: Array, cap: float) -> Array:
    return jnp.tanh(x / cap) * cap if cap else x


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def cross_entropy(logits: Array, labels: Array, vocab_size: int,
                  pad_id: int = -1) -> Tuple[Array, Array]:
    """Mean next-token NLL over non-pad labels. logits fp32 (..., V_padded);
    labels int32. Padded vocab positions are masked out."""
    v = logits.shape[-1]
    logits = jnp.where(
        jnp.arange(v) < vocab_size, logits, jnp.finfo(jnp.float32).min)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = lse - ll
    mask = (labels >= 0).astype(jnp.float32)
    total = jnp.maximum(mask.sum(), 1.0)
    return (nll * mask).sum() / total, total
