"""The plain reference of a dense decoder's training step: loss, gradients
and AdamW, in straightforward ``jax.numpy`` at float32 with ``highest``
matrix-multiply precision.  It imports nothing of the program and takes
nothing the program made: its weights come from ``bench.weights`` for the
same seed, its batches from the same seeded feed.

Each batch row runs alone and its gradient is taken layer by layer, and
inside a layer each block of query rows is rematerialized, so that the
reference fits on one chip at the timed sizes once the program's state is
freed.

``Control`` computes the same with every matrix multiplication in fp8
(operands rounded to e4m3 with a per-tensor scale, gradients flowing back
rounded to e5m2): the precision one step below the configuration's bf16,
whose readings must fail the comparison.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HIGHEST = jax.lax.Precision.HIGHEST


def mm_f32(eq, a, b):
    return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def _quant(x, dtype, top):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, top / amax, 1.0)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def _q8(x):
    """Value rounded to e4m3; gradient passes straight through."""
    return x + jax.lax.stop_gradient(
        _quant(x, jnp.float8_e4m3fn, 448.0) - x)


@jax.custom_vjp
def _grad_q8(y):
    return y


def _gq_fwd(y):
    return y, None


def _gq_bwd(_, g):
    return (_quant(g, jnp.float8_e5m2, 57344.0),)


_grad_q8.defvjp(_gq_fwd, _gq_bwd)


def mm_fp8(eq, a, b):
    return _grad_q8(jnp.einsum(eq, _q8(a.astype(jnp.float32)),
                               _q8(b.astype(jnp.float32)),
                               precision=HIGHEST))


# -- the model ----------------------------------------------------------------

def _norm(x, p, kind, eps):
    if kind == "rms":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * p["scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rope(x, theta):
    """x: (S, heads, D); rotates the two halves of D (GPT-NeoX style)."""
    S, _, D = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _attention(q, k, v, c, mm, q_block):
    """Causal (sliding-window) GQA for one sequence, in blocks of query
    rows.  q: (S, H, D); k, v: (S, KV, D)."""
    S, H, D = q.shape
    KV = k.shape[1]
    G = H // KV
    window = c.get("sliding_window") or 0
    qg = q.reshape(S, KV, G, D)
    kpos = jnp.arange(S)

    @jax.checkpoint
    def block(qb, start):
        s = mm("tkgd,skd->kgts", qb, k) / math.sqrt(D)
        qpos = start + jnp.arange(qb.shape[0])
        mask = kpos[None, :] <= qpos[:, None]
        if window:
            mask &= qpos[:, None] - kpos[None, :] < window
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jnp.exp(s - jnp.max(s, -1, keepdims=True))
        p = p / jnp.sum(p, -1, keepdims=True)
        return mm("kgts,skd->tkgd", p, v)

    outs = [block(qg[i:i + q_block], i) for i in range(0, S, q_block)]
    return jnp.concatenate(outs, 0).reshape(S, H, D)


def _layer(x, p, c, mm, q_block):
    h = _norm(x, p["ln1"], c["norm"], c["norm_epsilon"])
    a = p["attn"]
    q = mm("sd,dhk->shk", h, a["wq"])
    k = mm("sd,dhk->shk", h, a["wk"])
    v = mm("sd,dhk->shk", h, a["wv"])
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    o = _attention(q, k, v, c, mm, q_block)
    x = x + mm("shk,hkd->sd", o, a["wo"])
    h = _norm(x, p["ln2"], c["norm"], c["norm_epsilon"])
    f = p["mlp"]
    if c["mlp"] in ("swiglu", "geglu"):
        u = mm("sd,dgf->sgf", h, f["wi"])
        if "bi" in f:
            u = u + f["bi"]
        act = _silu if c["mlp"] == "swiglu" else _gelu_tanh
        u = act(u[:, 0]) * u[:, 1]
    else:
        u = mm("sd,df->sf", h, f["wi"])
        if "bi" in f:
            u = u + f["bi"]
        u = _gelu_tanh(u)
    y = mm("sf,fd->sd", u, f["wo"])
    if "bo" in f:
        y = y + f["bo"]
    return x + y


def _head_loss_sum(head, final_norm, x, labels, c, mm, rows):
    """Summed next-token NLL of one sequence's last hidden states, in
    blocks of ``rows`` positions."""
    def block(x, labels):
        h = _norm(x, final_norm, c["norm"], c["norm_epsilon"])
        logits = mm("sd,vd->sv", h, head)
        lse = jax.nn.logsumexp(logits, -1)
        ll = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
        return jnp.sum(lse - ll)
    block = jax.checkpoint(block)
    return sum(block(x[i:i + rows], labels[i:i + rows])
               for i in range(0, x.shape[0], rows))


# -- the optimizer ------------------------------------------------------------

@dataclass(frozen=True)
class AdamW:
    """AdamW with linear warm-up, global-norm clipping and decoupled weight
    decay on the weight matrices (not on norms or biases)."""
    lr_peak: float
    warmup_steps: int
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0

    def lr(self, step: int) -> float:
        """The rate of step ``step`` (from 1); the checked steps lie in
        the warm-up, the only part of the schedule followed here."""
        if not 0 < step < self.warmup_steps:
            raise ValueError(f"step {step} is outside the warm-up of "
                             f"{self.warmup_steps} steps")
        return self.lr_peak * step / self.warmup_steps

    @staticmethod
    def decays(path: str) -> bool:
        return path.split("/")[-1] not in ("scale",) + W.BIASES


def _update(g, m, v, w, lr, b1c, b2c, decay, o: AdamW):
    m = o.b1 * m + (1 - o.b1) * g
    v = o.b2 * v + (1 - o.b2) * g * g
    delta = (m / b1c) / (jnp.sqrt(v / b2c) + o.eps)
    delta = delta + decay * o.weight_decay * w
    return m, v, w - lr * delta


# -- following the program's first steps --------------------------------------

class Reference:
    """Follows AdamW steps of configuration ``c`` from the seeded weights,
    and keeps what the comparison reads: each step's loss, the first
    clipped gradient and the change of the weights.

    All of its state lives on the device.  A sequence's gradient is taken
    layer by layer (each layer's backward pass recomputes its forward from
    the layer's input) and added into one accumulator, so that beside the
    weights, the two moments and the accumulator only one layer's work is
    held at a time."""

    def __init__(self, c: dict, opt: AdamW, mm: Callable = mm_f32,
                 q_block: int = 1024):
        self.c, self.opt, self.mm = dict(c), opt, mm
        cc = W._Frozen(self.c)

        def take(blocks, i):
            return jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
                blocks)

        def layer(p, x):
            return _layer(x, p, cc, mm, q_block)

        def fwd(blocks, i, x):
            return layer(take(blocks, i), x)

        def bwd(blocks, acc, i, x, dy):
            _, vjp = jax.vjp(layer, take(blocks, i), x)
            dp, dx = vjp(dy)
            acc = jax.tree_util.tree_map(
                lambda a, d: a.at[i].add(d), acc, dp)
            return acc, dx

        def head(params, acc, loss, x, labels):
            table = params["embed"]["table"]
            hd = params.get("lm_head", table)
            f = lambda hd, fn, x: _head_loss_sum(        # noqa: E731
                hd, fn, x, labels, cc, mm, q_block)
            l, (d_hd, d_fn, dx) = jax.value_and_grad(f, (0, 1, 2))(
                hd, params["final_norm"], x)
            if "lm_head" in params:
                acc = dict(acc, lm_head=acc["lm_head"] + d_hd)
            else:
                acc = dict(acc, embed={"table": acc["embed"]["table"]
                                       + d_hd})
            acc["final_norm"] = jax.tree_util.tree_map(
                jnp.add, acc["final_norm"], d_fn)
            return acc, loss + l, dx

        def embed_bwd(acc, tokens, dx):
            t = acc["embed"]["table"].at[tokens].add(dx)
            return dict(acc, embed={"table": t})

        def update(g, m, v, w, scale, lr, b1c, b2c):
            out = {}
            for k in g:
                out[k] = _update(g[k] * scale, m[k], v[k], w[k], lr, b1c,
                                 b2c, float(AdamW.decays(k)), opt)
            return ({k: o[0] for k, o in out.items()},
                    {k: o[1] for k, o in out.items()},
                    {k: o[2] for k, o in out.items()})

        self._fwd = jax.jit(fwd)
        self._bwd = jax.jit(bwd, donate_argnums=(1,))
        self._head = jax.jit(head, donate_argnums=(1, 2))
        self._embed_bwd = jax.jit(embed_bwd, donate_argnums=(0,))
        self._update = jax.jit(update, donate_argnums=(1, 2, 3))
        self._zeros = jax.jit(lambda t: jax.tree_util.tree_map(
            jnp.zeros_like, t))
        self._f32 = jax.jit(lambda t: jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32), t))
        self._sqnorm = jax.jit(lambda t: sum(
            jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(t)))

    def _grads(self, params, tokens, labels):
        """Summed loss and summed gradients over a batch, one sequence and
        one layer at a time."""
        L = self.c["num_hidden_layers"]
        acc = self._zeros(params)
        loss = jnp.zeros((), jnp.float32)
        for b in range(tokens.shape[0]):
            tok = jnp.asarray(tokens[b])
            x = params["embed"]["table"][tok]
            xs = [x]
            for i in range(L):
                xs.append(self._fwd(params["blocks"], i, xs[-1]))
            acc, loss, dx = self._head(params, acc, loss, xs[-1],
                                       jnp.asarray(labels[b]))
            blocks = acc.pop("blocks")
            for i in reversed(range(L)):
                blocks, dx = self._bwd(params["blocks"], blocks, i, xs[i],
                                       dx)
            acc["blocks"] = blocks
            del xs
            acc = self._embed_bwd(acc, tok, dx)
        return loss, acc

    def follow(self, seed: int, batches: List[Dict[str, np.ndarray]],
               norms: Callable, change: Callable) -> dict:
        """Run ``len(batches)`` steps from the seeded weights.  ``norms``
        maps a flat tree to its per-slice norms and ``change`` two trees to
        those of their difference (``bench.compare``)."""
        c, o = self.c, self.opt
        # the weights as the configuration's parameter type holds them
        params = W.flatten(self._f32(W.make(c, seed, c["param_dtype"])))
        m = W.flatten(self._zeros(W.nest(params)))
        v = W.flatten(self._zeros(W.nest(params)))
        out = {"loss": []}
        for t, batch in enumerate(batches, start=1):
            loss, grads = self._grads(W.nest(params), batch["tokens"],
                                      batch["labels"])
            n = float(batch["tokens"].size)
            out["loss"].append(float(loss) / n)
            gnorm = math.sqrt(float(self._sqnorm(grads))) / n
            scale = min(1.0, o.clip_norm / max(gnorm, 1e-12)) / n
            grads = W.flatten(grads)
            if t == 1:
                out["grad"] = {k: x * scale for k, x in norms(grads).items()}
            m, v, params = self._update(grads, m, v, params, scale,
                                        o.lr(t), 1 - o.b1 ** t,
                                        1 - o.b2 ** t)
            del grads
        del m, v
        out["change"] = change(params,
                               W.flatten(W.make(c, seed, c["param_dtype"])))
        return out
