"""Blocked flash-style attention (XLA path): fwd + custom-VJP backward vs the
unblocked oracle, folded and unfolded."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _prop import given, settings, st   # hypothesis or graceful skip

from repro.models import attention_core as core
from repro.models.attention import AttnSpec, attention_ref, blocked_attention


def rand_qkv(key, B, S, H, KV, D, dtype=jnp.float32, q_offset=0):
    """S queries and S + q_offset keys and values."""
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S + q_offset, KV, D), dtype)
    v = jax.random.normal(ks[2], (B, S + q_offset, KV, D), dtype)
    return q, k, v


#: (spec, q_offset, kv_len): the queries sit at positions q_offset + t of
#: Skv = Sq + q_offset keys (q_offset > 0: a chunk of prefill after a
#: cached prefix); a kv_len masks every key from it on
SPECS = [pytest.param(spec, q_offset, kv_len, id=f"spec{n}")
         for n, (spec, q_offset, kv_len) in enumerate([
    (AttnSpec(q_block=64, kv_block=64, folded=False), 0, None),
    (AttnSpec(q_block=64, kv_block=64, folded=True), 0, None),
    (AttnSpec(q_block=64, kv_block=64, softcap=30.0), 0, None),
    (AttnSpec(q_block=64, kv_block=64, window=100), 0, None),
    (AttnSpec(q_block=64, kv_block=64, causal=False), 0, None),
    (AttnSpec(q_block=32, kv_block=64, folded=False), 0, None),
    # a window as long as the sequence (starcoder2's 4096 at S 4096)
    (AttnSpec(q_block=64, kv_block=64, window=256), 0, None),
    # a window shorter than two blocks and no multiple of one
    (AttnSpec(q_block=32, kv_block=32, window=45), 0, None),
    # unequal q and kv blocks under a window, each way round
    (AttnSpec(q_block=32, kv_block=64, window=70), 0, None),
    (AttnSpec(q_block=64, kv_block=32, window=70), 0, None),
    # chunked prefill: fewer queries than keys
    (AttnSpec(q_block=64, kv_block=64), 64, None),
    (AttnSpec(q_block=32, kv_block=64, window=100), 128, None),
    # a static kv_len, causal and not
    (AttnSpec(q_block=64, kv_block=32), 0, 100),
    (AttnSpec(q_block=32, kv_block=64, causal=False), 0, 100),
])]


@pytest.mark.parametrize("spec, q_offset, kv_len", SPECS)
def test_forward_matches_oracle(spec, q_offset, kv_len):
    q, k, v = rand_qkv(jax.random.PRNGKey(0), 2, 256, 6, 2, 32,
                       q_offset=q_offset)
    out = blocked_attention(q, k, v, spec, q_offset, kv_len)
    exp = attention_ref(q, k, v, spec, q_offset, kv_len)
    assert jnp.max(jnp.abs(out - exp)) < 2e-5


@pytest.mark.parametrize("spec, q_offset, kv_len", SPECS)
def test_custom_vjp_matches_autodiff(spec, q_offset, kv_len):
    q, k, v = rand_qkv(jax.random.PRNGKey(1), 1, 128, 4, 2, 16,
                       q_offset=q_offset)

    def f(impl):
        def g(q, k, v):
            return (impl(q, k, v, spec, q_offset, kv_len) * jnp.cos(
                jnp.arange(16, dtype=jnp.float32))).sum()
        return g

    g1 = jax.grad(f(blocked_attention), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f(attention_ref), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert jnp.max(jnp.abs(a - b)) < 5e-5


def test_folded_equals_unfolded_grads():
    q, k, v = rand_qkv(jax.random.PRNGKey(2), 2, 256, 4, 4, 32)
    s1 = AttnSpec(q_block=64, kv_block=64, folded=False)
    s2 = AttnSpec(q_block=64, kv_block=64, folded=True)
    f = lambda s: jax.grad(
        lambda q: blocked_attention(q, k, v, s).sum())(q)
    assert jnp.max(jnp.abs(f(s1) - f(s2))) < 2e-5


def _brute_visible(spec, Sq, Skv, BQ, BK, q_offset, kv_len):
    """Block pairs holding at least one unmasked (query, key) pair."""
    m = np.asarray(core._mask(q_offset + jnp.arange(Sq), jnp.arange(Skv),
                              spec, kv_len))
    return [(i, j) for i in range(Sq // BQ) for j in range(Skv // BK)
            if m[i * BQ:(i + 1) * BQ, j * BK:(j + 1) * BK].any()]


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("window", [0, 1, 45, 64, 100, 300])
def test_visible_blocks_are_the_blocks_with_an_unmasked_pair(causal, window):
    """The forward's range of kv blocks per q block and the backward's
    range of q blocks per kv block both give exactly the block pairs that
    hold an unmasked pair."""
    Sq, Skv = 128, 256
    for BQ, BK in [(32, 32), (32, 64), (64, 32)]:
        for q_offset in (0, 32, 96, 128):
            for kv_len in (None, 1, 100):
                spec = AttnSpec(causal=causal, window=window, q_block=BQ,
                                kv_block=BK)
                want = _brute_visible(spec, Sq, Skv, BQ, BK, q_offset, kv_len)
                got = core.visible_blocks(spec, Sq, Skv, BQ, BK, q_offset,
                                          kv_len)
                assert got == want, (BQ, BK, q_offset, kv_len)
                by_kv = []
                for j in range(Skv // BK):
                    lo, hi = core._q_range(j, spec, BQ, BK, Sq // BQ,
                                           q_offset, kv_len)
                    by_kv += [(i, j) for i in range(int(lo), int(hi) + 1)]
                assert sorted(by_kv) == want, (BQ, BK, q_offset, kv_len)


@pytest.mark.parametrize("S, visited", [(4096, 36), (2048, 10)])
def test_visible_blocks_of_the_starcoder2_cells(S, visited):
    """starcoder2's window of 4096 masks nothing at S <= 4096: causality
    alone leaves 36 of 64 block pairs at S 4096, 10 of 16 at S 2048."""
    spec = AttnSpec(window=4096)
    assert len(core.visible_blocks(spec, S, S, 512, 512)) == visited


def _forward_all_blocks(q, k, v, spec, q_offset, kv_len):
    """The unfolded forward as it was before it skipped blocks: every kv
    block for every q block, future blocks dropped by a select."""
    B, Sq, H, D = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    BQ, BK = min(spec.q_block, Sq), min(spec.kv_block, Skv)
    NQ, NK = Sq // BQ, Skv // BK
    qg = core._split_blocks(q.reshape(B, Sq, KV, G, D), NQ, BQ)
    kb, vb = core._split_blocks(k, NK, BK), core._split_blocks(v, NK, BK)

    def outer(_, qi):
        qblk, i = qi
        qpos = q_offset + i * BQ + jnp.arange(BQ)

        def inner(c, kj):
            kblk, vblk, j = kj
            c2 = core._block_update(c, qblk, kblk, vblk, qpos,
                                    j * BK + jnp.arange(BK), spec, kv_len)
            if spec.causal:
                valid = (j * BK) <= (q_offset + i * BQ + BQ - 1)
                c2 = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(valid, n, o), c2, c)
            return c2, None

        init = (jnp.full((B, BQ, KV, G), core.NEG_INF, jnp.float32),
                jnp.zeros((B, BQ, KV, G), jnp.float32),
                jnp.zeros((B, BQ, KV, G, D), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(inner, init, (kb, vb, jnp.arange(NK)))
        return None, acc / jnp.maximum(l, 1e-30)[..., None]

    _, outs = jax.lax.scan(outer, None, (qg, jnp.arange(NQ)))
    return outs.swapaxes(0, 1).reshape(B, Sq, H, D)


@pytest.mark.parametrize("spec, q_offset, kv_len",
                         [p for p in SPECS if not p.values[0].folded])
def test_visible_blocks_forward_equals_all_blocks(spec, q_offset, kv_len):
    """A skipped block contributed exact zeros: the forward that visits
    only the visible blocks equals the one that computed all of them."""
    q, k, v = rand_qkv(jax.random.PRNGKey(6), 1, 128, 4, 2, 16,
                       q_offset=q_offset)
    out = blocked_attention(q, k, v, spec, q_offset, kv_len)
    old = _forward_all_blocks(q, k, v, spec, q_offset, kv_len)
    assert jnp.max(jnp.abs(out - old)) < 1e-6


@settings(max_examples=15, deadline=None)
@given(
    b=st.integers(1, 2),
    nq=st.sampled_from([1, 2, 4]),
    kv=st.sampled_from([1, 2]),
    g=st.sampled_from([1, 3]),
    d=st.sampled_from([8, 32]),
    causal=st.booleans(),
    folded=st.booleans(),
)
def test_shape_sweep(b, nq, kv, g, d, causal, folded):
    S = 32 * nq
    q, k, v = rand_qkv(jax.random.PRNGKey(5), b, S, kv * g, kv, d)
    spec = AttnSpec(causal=causal, q_block=32, kv_block=32, folded=folded)
    out = blocked_attention(q, k, v, spec)
    exp = attention_ref(q, k, v, spec)
    assert out.shape == exp.shape
    assert jnp.max(jnp.abs(out - exp)) < 3e-5


def test_bf16_inputs():
    q, k, v = rand_qkv(jax.random.PRNGKey(3), 2, 128, 4, 2, 32,
                       jnp.bfloat16)
    spec = AttnSpec(q_block=64, kv_block=64)
    out = blocked_attention(q, k, v, spec)
    exp = attention_ref(q, k, v, spec)
    assert out.dtype == jnp.bfloat16
    assert jnp.max(jnp.abs(out.astype(jnp.float32)
                           - exp.astype(jnp.float32))) < 0.03


def test_decode_kv_len_mask():
    """kv_len masking for cache-backed attention."""
    q, k, v = rand_qkv(jax.random.PRNGKey(4), 1, 64, 2, 2, 16)
    spec = AttnSpec(causal=False, q_block=64, kv_block=64)
    out = blocked_attention(q, k, v, spec, 0, jnp.int32(32))
    exp = attention_ref(q, k[:, :32], v[:, :32],
                        AttnSpec(causal=False, q_block=64, kv_block=32))
    assert jnp.max(jnp.abs(out - exp)) < 2e-5
