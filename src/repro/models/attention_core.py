"""Blocked online-softmax attention core with a flash-style custom VJP.

Forward saves only (q, k, v, out, lse); the backward recomputes block
probabilities — O(B*S*d) residual memory instead of O(S^2) scan residuals
(verified against naive autodiff in tests/test_attention.py).

Visible blocks: the unfolded path computes only the (q block, kv block)
pairs holding at least one unmasked (query, key) pair.  For q block i the
kv blocks it sees are one contiguous range ``[j_lo(i), j_hi(i)]``
(``_kv_range``): causal attention ends it at the block holding the block's
last query, a window starts it at the block holding its first query's
oldest visible key, and a ``kv_len`` ends it at the last live key.  The
backward walks the transposed range of q blocks for each kv block
(``_q_range``) and accumulates dq in place.  A skipped block held only
masked scores, which contributed exact zeros; blocks on a mask's edge keep
their element mask.  ``visible_blocks`` lists the pairs visited.

Causal folding (``spec.folded``): q blocks are paired (i, NQ-1-i); each pair
runs an inner scan of exactly NQ+1 block-updates where iteration t updates
  - pair-low  with kv block t          while t <= i_lo,
  - pair-high with kv block t-i_lo-1   otherwise,
so attention-core FLOPs drop from NQ*NK to ~(NQ+1)*NQ/2 block-updates
(the exact S^2/2 + O(S*BK) causal lower bound) with uniform per-iteration
work. The backward uses the transposed pairing over kv blocks.
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

Array = jax.Array
NEG_INF = -1.0e30
#: named scope of the core's forward and backward: every operation of the
#: core carries an ``attn`` component in its ``op_name``, so a device trace
#: can tell attention's time in each phase
ATTN_SCOPE = "attn"


class AttnSpec(NamedTuple):
    causal: bool = True
    window: int = 0          # 0 = full
    softcap: float = 0.0
    scale: float = 0.0       # 0 -> 1/sqrt(D)
    q_block: int = 512
    kv_block: int = 512
    folded: bool = False     # balanced causal folding


def _mask(qpos: Array, kpos: Array, spec: AttnSpec, kv_len) -> Array:
    m = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if spec.causal:
        m &= qpos[:, None] >= kpos[None, :]
    if spec.window:
        m &= qpos[:, None] - kpos[None, :] < spec.window
    if kv_len is not None:
        m &= kpos[None, :] < kv_len
    return m


def _scores(q, k, qpos, kpos, spec, kv_len):
    """s: (B, BQ, KV, G, BK) fp32, masked."""
    scale = spec.scale or 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("btkgd,bskd->btkgs", q, k,
                   preferred_element_type=jnp.float32) * scale
    if spec.softcap:
        s = jnp.tanh(s / spec.softcap) * spec.softcap
    mask = _mask(qpos, kpos, spec, kv_len)
    return jnp.where(mask[None, :, None, None, :], s, NEG_INF), mask


def _block_update(carry, q, k, v, qpos, kpos, spec, kv_len):
    m, l, acc = carry
    s, mask = _scores(q, k, qpos, kpos, spec, kv_len)
    m_new = jnp.maximum(m, s.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(mask[None, :, None, None, :], p, 0.0)
    l_new = l * alpha + p.sum(axis=-1)
    pv = jnp.einsum("btkgs,bskd->btkgd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    acc_new = acc * alpha[..., None] + pv
    return m_new, l_new, acc_new


def _split_blocks(x, n, bs):
    # (B, S, ...) -> (n, B, bs, ...)
    B = x.shape[0]
    return x.reshape((B, n, bs) + x.shape[2:]).swapaxes(0, 1)


def _kv_range(i, spec: AttnSpec, BQ, BK, NK, q_offset, kv_len):
    """Inclusive range [lo, hi] of the kv blocks q block ``i`` sees (empty
    when lo > hi); ``i``, ``q_offset`` and ``kv_len`` may be traced."""
    lo, hi = 0, NK - 1
    first = q_offset + i * BQ                   # the block's first query
    if spec.causal:
        hi = jnp.minimum(hi, (first + BQ - 1) // BK)
    if kv_len is not None:
        hi = jnp.minimum(hi, (kv_len - 1) // BK)
    if spec.window:
        oldest = first - spec.window + 1        # the oldest key it sees
        lo = jnp.maximum(lo, oldest // BK)
        if kv_len is not None:
            hi = jnp.where(kv_len - 1 >= oldest, hi, -1)
    return lo, hi


def _q_range(j, spec: AttnSpec, BQ, BK, NQ, q_offset, kv_len):
    """Inclusive range [lo, hi] of the q blocks that see kv block ``j``:
    the transpose of ``_kv_range``."""
    lo, hi = 0, NQ - 1
    last = (j + 1) * BK - 1                     # the block's last live key
    if kv_len is not None:
        last = jnp.minimum(last, kv_len - 1)
        hi = jnp.where(j * BK < kv_len, hi, -1)
    if spec.causal:
        lo = jnp.maximum(lo, (j * BK - q_offset) // BQ)
    if spec.window:
        hi = jnp.minimum(hi, (last + spec.window - 1 - q_offset) // BQ)
    return lo, hi


def visible_blocks(spec: AttnSpec, Sq: int, Skv: int, BQ: int, BK: int,
                   q_offset: int = 0, kv_len=None):
    """The (q block, kv block) pairs the unfolded path computes, in order."""
    NQ, NK = Sq // BQ, Skv // BK
    out = []
    for i in range(NQ):
        lo, hi = _kv_range(i, spec, BQ, BK, NK, q_offset, kv_len)
        out += [(i, j) for j in range(int(lo), int(hi) + 1)]
    return out


@jax.named_scope(ATTN_SCOPE)
def _forward(q, k, v, spec: AttnSpec, q_offset, kv_len):
    """Returns (out (B,Sq,H,Dv), lse (B,Sq,KV,G))."""
    B, Sq, H, D = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // KV
    BQ, BK = min(spec.q_block, Sq), min(spec.kv_block, Skv)
    assert Sq % BQ == 0 and Skv % BK == 0, (Sq, BQ, Skv, BK)
    NQ, NK = Sq // BQ, Skv // BK

    qg = _split_blocks(q.reshape(B, Sq, KV, G, D), NQ, BQ)
    kb = _split_blocks(k, NK, BK)
    vb = _split_blocks(v, NK, BK)

    fold = (spec.folded and spec.causal and not spec.window
            and kv_len is None and Sq == Skv and BQ == BK and NQ >= 2
            and NQ % 2 == 0)

    qpos_of = lambda i: q_offset + i * BQ + jnp.arange(BQ)
    kpos_of = lambda j: j * BK + jnp.arange(BK)

    def zinit():
        return (jnp.full((B, BQ, KV, G), NEG_INF, jnp.float32),
                jnp.zeros((B, BQ, KV, G), jnp.float32),
                jnp.zeros((B, BQ, KV, G, Dv), jnp.float32))

    if not fold:
        def outer(_, qi):
            qblk, i = qi
            qpos = qpos_of(i)

            def inner(j, c):
                kblk = jax.lax.dynamic_index_in_dim(kb, j, 0, False)
                vblk = jax.lax.dynamic_index_in_dim(vb, j, 0, False)
                return _block_update(c, qblk, kblk, vblk, qpos, kpos_of(j),
                                     spec, kv_len)

            lo, hi = _kv_range(i, spec, BQ, BK, NK, q_offset, kv_len)
            m, l, acc = jax.lax.fori_loop(lo, hi + 1, inner, zinit())
            out = acc / jnp.maximum(l, 1e-30)[..., None]
            lse = m + jnp.log(jnp.maximum(l, 1e-30))
            return None, (out, lse)

        _, (outs, lses) = jax.lax.scan(outer, None, (qg, jnp.arange(NQ)))
    else:
        # ---- balanced causal folding: one block-update per iteration ----
        Pn = NQ // 2
        ilo = jnp.arange(Pn)
        ihi = NQ - 1 - ilo
        q_lo, q_hi = qg[:Pn], qg[::-1][:Pn]

        def outer(_, qi):
            qlo, qhi, lo, hi = qi
            plo, phi = qpos_of(lo), qpos_of(hi)

            def inner(c, t):
                clo, chi = c
                use_lo = t <= lo
                j = jnp.where(use_lo, t, t - lo - 1)
                kblk = jax.lax.dynamic_index_in_dim(kb, j, 0, False)
                vblk = jax.lax.dynamic_index_in_dim(vb, j, 0, False)
                qblk = jnp.where(use_lo, qlo, qhi)
                qpos = jnp.where(use_lo, plo, phi)
                cin = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(use_lo, a, b), clo, chi)
                cout = _block_update(cin, qblk, kblk, vblk, qpos,
                                     kpos_of(j), spec, kv_len)
                clo = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(use_lo, n, o), cout, clo)
                chi = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(use_lo, o, n), cout, chi)
                return (clo, chi), None

            (clo, chi), _ = jax.lax.scan(inner, (zinit(), zinit()),
                                         jnp.arange(NQ + 1))

            def fin(c):
                out = c[2] / jnp.maximum(c[1], 1e-30)[..., None]
                lse = c[0] + jnp.log(jnp.maximum(c[1], 1e-30))
                return out, lse
            (olo, llo), (ohi, lhi) = fin(clo), fin(chi)
            return None, ((olo, llo), (ohi, lhi))

        _, ((out_lo, lse_lo), (out_hi, lse_hi)) = jax.lax.scan(
            outer, None, (q_lo, q_hi, ilo, ihi))
        outs = jnp.concatenate([out_lo, out_hi[::-1]], axis=0)
        lses = jnp.concatenate([lse_lo, lse_hi[::-1]], axis=0)

    out = outs.swapaxes(0, 1).reshape(B, Sq, KV * G, Dv)
    lse = lses.swapaxes(0, 1).reshape(B, Sq, KV, G)
    return out.astype(q.dtype), lse


def _recompute_p(q, k, lse, qpos, kpos, spec, kv_len):
    """p (B,BQ,KV,G,BK) fp32 and pre-softcap scores t (for softcap grad)."""
    scale = spec.scale or 1.0 / math.sqrt(q.shape[-1])
    t = jnp.einsum("btkgd,bskd->btkgs", q, k,
                   preferred_element_type=jnp.float32) * scale
    if spec.softcap:
        z = jnp.tanh(t / spec.softcap) * spec.softcap
    else:
        z = t
    mask = _mask(qpos, kpos, spec, kv_len)
    z = jnp.where(mask[None, :, None, None, :], z, NEG_INF)
    p = jnp.exp(z - lse[..., None])
    p = jnp.where(mask[None, :, None, None, :], p, 0.0)
    return p, t, mask


@jax.named_scope(ATTN_SCOPE)
def _backward(res, dout, spec: AttnSpec, q_offset, kv_len):
    q, k, v, out, lse = res
    B, Sq, H, D = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // KV
    BQ, BK = min(spec.q_block, Sq), min(spec.kv_block, Skv)
    NQ, NK = Sq // BQ, Skv // BK
    scale = spec.scale or 1.0 / math.sqrt(D)

    qg = _split_blocks(q.reshape(B, Sq, KV, G, D), NQ, BQ)
    kb = _split_blocks(k, NK, BK)
    vb = _split_blocks(v, NK, BK)
    dog = _split_blocks(dout.reshape(B, Sq, KV, G, Dv), NQ, BQ)
    lseg = _split_blocks(lse, NQ, BQ)
    # delta = rowsum(dout * out)
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(B, Sq, KV, G)
    dg = _split_blocks(delta, NQ, BQ)

    qpos_of = lambda i: q_offset + i * BQ + jnp.arange(BQ)
    kpos_of = lambda j: j * BK + jnp.arange(BK)

    f32 = lambda x: x.astype(jnp.float32)

    def _block_bwd(qblk, kblk, vblk, doblk, lseblk, dblk, qpos, kpos):
        """One (q, kv) block backward update; returns (dk, dv, dq) blocks."""
        p, t, mask = _recompute_p(qblk, kblk, lseblk, qpos, kpos,
                                  spec, kv_len)
        dp = jnp.einsum("btkgd,bskd->btkgs", doblk, vblk,
                        preferred_element_type=jnp.float32)
        dz = p * (dp - dblk[..., None])
        if spec.softcap:
            th = jnp.tanh(t / spec.softcap)
            dz = dz * (1.0 - jnp.square(th))
        dz = dz * scale
        dvb = jnp.einsum("btkgs,btkgd->bskd", p, f32(doblk))
        dkb = jnp.einsum("btkgs,btkgd->bskd", dz, f32(qblk))
        dqb = jnp.einsum("btkgs,bskd->btkgd", dz, f32(kblk))
        return dkb, dvb, dqb

    fold = (spec.folded and spec.causal and not spec.window
            and kv_len is None and Sq == Skv and BQ == BK and NQ >= 2
            and NQ % 2 == 0)

    if not fold:
        def kv_step(dq_acc, kj):
            kblk, vblk, j = kj
            kpos = kpos_of(j)

            def q_step(i, carry):
                dk, dv, dq_acc = carry
                at = lambda x: jax.lax.dynamic_index_in_dim(x, i, 0, False)
                dkb, dvb, dqb = _block_bwd(at(qg), kblk, vblk, at(dog),
                                           at(lseg), at(dg), qpos_of(i), kpos)
                dq_acc = jax.lax.dynamic_update_index_in_dim(
                    dq_acc, at(dq_acc) + dqb, i, 0)
                return dk + dkb, dv + dvb, dq_acc

            zk = jnp.zeros((B, BK, KV, D), jnp.float32)
            zv = jnp.zeros((B, BK, KV, Dv), jnp.float32)
            lo, hi = _q_range(j, spec, BQ, BK, NQ, q_offset, kv_len)
            dk, dv, dq_acc = jax.lax.fori_loop(lo, hi + 1, q_step,
                                               (zk, zv, dq_acc))
            return dq_acc, (dk, dv)

        dq0 = jnp.zeros((NQ, B, BQ, KV, G, D), jnp.float32)
        dq_acc, (dks, dvs) = jax.lax.scan(kv_step, dq0,
                                          (kb, vb, jnp.arange(NK)))
    else:
        # Balanced causal folding, transposed for the backward: kv blocks
        # pair (j, NK-1-j); iteration t of NQ+1 updates
        #   pair-high kv with q block  j_hi + t        while t <= j_lo,
        #   pair-low  kv with q block  j_lo + t-j_lo-1 otherwise —
        # exactly one block-backward per iteration (S^2/2 lower bound).
        Pn = NK // 2
        jlo = jnp.arange(Pn)
        jhi = NK - 1 - jlo
        k_lo, k_hi = kb[:Pn], kb[::-1][:Pn]
        v_lo, v_hi = vb[:Pn], vb[::-1][:Pn]

        def kv_pair_step(dq_acc, kj):
            klo, vlo, khi, vhi, lo, hi = kj

            def t_step(carry, t):
                dk_lo, dv_lo, dk_hi, dv_hi, dq_acc = carry
                use_hi = t <= lo
                i = jnp.where(use_hi, hi + t, lo + (t - lo - 1))
                j = jnp.where(use_hi, hi, lo)
                kblk = jnp.where(use_hi, khi, klo)
                vblk = jnp.where(use_hi, vhi, vlo)
                qblk = jax.lax.dynamic_index_in_dim(qg, i, 0, False)
                doblk = jax.lax.dynamic_index_in_dim(dog, i, 0, False)
                lseblk = jax.lax.dynamic_index_in_dim(lseg, i, 0, False)
                dblk = jax.lax.dynamic_index_in_dim(dg, i, 0, False)
                dkb, dvb, dqb = _block_bwd(qblk, kblk, vblk, doblk, lseblk,
                                           dblk, qpos_of(i), kpos_of(j))
                dk_lo = jnp.where(use_hi, dk_lo, dk_lo + dkb)
                dv_lo = jnp.where(use_hi, dv_lo, dv_lo + dvb)
                dk_hi = jnp.where(use_hi, dk_hi + dkb, dk_hi)
                dv_hi = jnp.where(use_hi, dv_hi + dvb, dv_hi)
                dq_acc = jax.lax.dynamic_update_index_in_dim(
                    dq_acc,
                    jax.lax.dynamic_index_in_dim(dq_acc, i, 0, False) + dqb,
                    i, 0)
                return (dk_lo, dv_lo, dk_hi, dv_hi, dq_acc), None

            zk = jnp.zeros((B, BK, KV, D), jnp.float32)
            zv = jnp.zeros((B, BK, KV, Dv), jnp.float32)
            (dk_lo, dv_lo, dk_hi, dv_hi, dq_acc), _ = jax.lax.scan(
                t_step, (zk, zv, zk, zv, dq_acc), jnp.arange(NQ + 1))
            return dq_acc, ((dk_lo, dv_lo), (dk_hi, dv_hi))

        dq0 = jnp.zeros((NQ, B, BQ, KV, G, D), jnp.float32)
        dq_acc, ((dk_lo, dv_lo), (dk_hi, dv_hi)) = jax.lax.scan(
            kv_pair_step, dq0, (k_lo, v_lo, k_hi, v_hi, jlo, jhi))
        dks = jnp.concatenate([dk_lo, dk_hi[::-1]], axis=0)
        dvs = jnp.concatenate([dv_lo, dv_hi[::-1]], axis=0)
    dq = dq_acc.swapaxes(0, 1).reshape(B, Sq, H, D).astype(q.dtype)
    dk = dks.swapaxes(0, 1).reshape(B, Skv, KV, D).astype(k.dtype)
    dv = dvs.swapaxes(0, 1).reshape(B, Skv, KV, Dv).astype(v.dtype)
    return dq, dk, dv


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def blocked_attention(q: Array, k: Array, v: Array, spec: AttnSpec,
                      q_offset: int = 0, kv_len=None) -> Array:
    out, _ = _forward(q, k, v, spec, q_offset, kv_len)
    return out


def _fwd(q, k, v, spec, q_offset, kv_len):
    out, lse = _forward(q, k, v, spec, q_offset, kv_len)
    return out, (q, k, v, out, lse)


def _bwd(spec, q_offset, kv_len, res, dout):
    return _backward(res, dout, spec, q_offset, kv_len)


blocked_attention.defvjp(_fwd, _bwd)
