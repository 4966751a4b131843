"""Seeded weights of a dense decoder, made on the device in one jitted call.

The layout is the one the program's ``Transformer`` trains (stacked layer
weights with a leading layer axis; GQA projections ``(d, heads, head_dim)``;
a tied embedding table), written out here from the configuration alone so
that the reference can take the same weights without the program.  The
harness checks it against the program's own parameter shapes before a run.

Matrices are drawn N(0, 1/fan_in) with the fan-in over the axes a token's
vector is multiplied along; norm scales are ones and biases zeros.  The
values are drawn in float32 and cast to the configuration's parameter type.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: leaf name -> number of leading (per-layer) axes that are the fan-in
_FAN_IN_AXES = {"wq": 1, "wk": 1, "wv": 1, "attn/wo": 2, "wi": 1,
                "mlp/wo": 1}
#: leaf names of the biases (zeros at the start)
BIASES = ("bias", "bi", "bo", "bq", "bk", "bv")


def layout(c: dict) -> Dict[str, Tuple[int, ...]]:
    """Flat ``path -> shape`` of the parameters of configuration ``c``."""
    L, d, V = c["num_hidden_layers"], c["hidden_size"], c["vocab_size"]
    h, kv, hd, ff = (c["num_attention_heads"], c["num_key_value_heads"],
                     c["head_dim"], c["intermediate_size"])
    gated = c["mlp"] in ("swiglu", "geglu")
    layer_norm = c["norm"] == "layer"
    out = {"embed/table": (V, d), "final_norm/scale": (d,)}
    if layer_norm:
        out["final_norm/bias"] = (d,)
    for ln in ("ln1", "ln2"):
        out[f"blocks/{ln}/scale"] = (L, d)
        if layer_norm:
            out[f"blocks/{ln}/bias"] = (L, d)
    out.update({"blocks/attn/wq": (L, d, h, hd),
                "blocks/attn/wk": (L, d, kv, hd),
                "blocks/attn/wv": (L, d, kv, hd),
                "blocks/attn/wo": (L, h, hd, d),
                "blocks/mlp/wi": (L, d, 2, ff) if gated else (L, d, ff),
                "blocks/mlp/wo": (L, ff, d)})
    if c.get("qkv_bias"):
        out.update({"blocks/attn/bq": (L, h, hd),
                    "blocks/attn/bk": (L, kv, hd),
                    "blocks/attn/bv": (L, kv, hd)})
    if c.get("mlp_bias"):
        out["blocks/mlp/bi"] = (L, 2, ff) if gated else (L, ff)
        out["blocks/mlp/bo"] = (L, d)
    if not c.get("tie_word_embeddings", True):
        out["lm_head"] = (V, d)
    return out


def nest(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return tree


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, path))
        else:
            out[path] = v
    return out


def seed_key(seed: int, stream: int = 0):
    """A jax key from any whole number (beyond 32 bits too)."""
    words = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")


def _std(path: str, shape) -> float:
    name = "/".join(path.split("/")[-2:])
    leaf = path.split("/")[-1]
    if path in ("embed/table", "lm_head"):
        return 1.0 / math.sqrt(shape[-1])
    axes = _FAN_IN_AXES.get(name, _FAN_IN_AXES.get(leaf))
    if axes is None:
        return 0.0
    per_layer = shape[1:]
    return 1.0 / math.sqrt(int(np.prod(per_layer[:axes])))


def _make(key, c: dict, dtype):
    flat = layout(c)
    keys = jax.random.split(key, len(flat))
    out = {}
    for k, (path, shape) in zip(keys, sorted(flat.items())):
        leaf = path.split("/")[-1]
        if leaf == "scale":
            v = jnp.ones(shape, jnp.float32)
        elif leaf in BIASES:
            v = jnp.zeros(shape, jnp.float32)
        else:
            v = jax.random.normal(k, shape, jnp.float32) * _std(path, shape)
        out[path] = v.astype(dtype)
    return nest(out)


_MAKE = jax.jit(_make, static_argnums=(1, 2))


def make(c: dict, seed: int, dtype=None) -> dict:
    """The parameters for ``seed``, in ``dtype`` (the configuration's
    parameter type by default), on the default device."""
    dtype = jnp.dtype(dtype or c["param_dtype"])
    frozen = _Frozen(c)
    return _MAKE(seed_key(seed), frozen, dtype)


class _Frozen(dict):
    """A hashable view of a configuration dict, for jit's static args."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))
