"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps +
hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _prop import given, settings, st   # hypothesis or graceful skip

from repro.kernels import ops, ref
from repro.kernels.pattern_summary import TILE, row_targets


# -- flash attention -----------------------------------------------------------

@pytest.mark.parametrize("B,S,H,KV,D", [
    (1, 128, 4, 4, 64), (2, 256, 6, 2, 64), (1, 256, 8, 1, 128),
    (2, 128, 2, 2, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_shapes_dtypes(B, S, H, KV, D, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, D), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, D), dtype)
    out = ops.flash_attention(q, k, v, block_q=64, block_k=64)
    exp = ref.attention_oracle(q, k, v)
    tol = 0.035 if dtype == jnp.bfloat16 else 2e-5
    assert out.shape == exp.shape and out.dtype == dtype
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                 - exp.astype(jnp.float32)))) < tol


@pytest.mark.parametrize("kw", [dict(window=100), dict(softcap=20.0),
                                dict(causal=False),
                                dict(window=64, softcap=10.0)])
def test_flash_variants(kw):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 256, 4, 32))
    k = jax.random.normal(ks[1], (2, 256, 2, 32))
    v = jax.random.normal(ks[2], (2, 256, 2, 32))
    out = ops.flash_attention(q, k, v, block_q=64, block_k=64, **kw)
    exp = ref.attention_oracle(q, k, v, **kw)
    assert float(jnp.max(jnp.abs(out - exp))) < 2e-5


# -- SSD scan -------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,P,G,N,chunk", [
    (1, 64, 2, 16, 1, 8, 16), (2, 128, 4, 32, 2, 16, 32),
    (1, 128, 4, 64, 4, 32, 64), (1, 32, 2, 16, 2, 16, 32),
])
def test_ssd_shapes(B, S, H, P, G, N, chunk):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.uniform(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, G, N))
    Cm = jax.random.normal(ks[4], (B, S, G, N))
    out = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    exp = ref.ssd_oracle(x, dt, A, Bm, Cm)
    scale = float(jnp.max(jnp.abs(exp))) + 1e-6
    assert float(jnp.max(jnp.abs(out - exp))) / scale < 2e-5


def test_ssd_matches_model_chunked_path():
    """Pallas kernel == the model's XLA chunked implementation."""
    from repro.models.ssm import ssd_chunked
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    B, S, H, P, G, N = 2, 128, 4, 32, 2, 16
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.uniform(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, G, N))
    Cm = jax.random.normal(ks[4], (B, S, G, N))
    y1 = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=32)
    y2, _ = ssd_chunked(x, dt, A, Bm, Cm, 32)
    assert float(jnp.max(jnp.abs(y1 - y2))) < 2e-4


# -- pattern summary -------------------------------------------------------------

def _kernel(u):
    """The kernel on (E, n) rows, with the targets the backend passes."""
    u = np.asarray(u, np.float32)
    return np.asarray(ops.pattern_summary(jnp.asarray(u),
                                          jnp.asarray(row_targets(u))),
                      np.float64)


def test_pattern_summary_basic(rng):
    E, n = 16, 256
    u = np.clip(rng.normal(0.5, 0.3, (E, n)), 0, 1)
    u[:, :40] = 0
    u[3, 100:180] = 0
    u[5] = 0
    out = _kernel(u)
    exp = ref.pattern_summary_oracle(u)
    np.testing.assert_allclose(out, exp, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 12), st.integers(0, 3), st.data())
def test_pattern_summary_property(e_rows, zero_blocks, data):
    n = 128
    rng = np.random.default_rng(data.draw(st.integers(0, 10_000)))
    u = np.clip(rng.normal(0.4, 0.3, (e_rows, n)), 0, 1)
    for _ in range(zero_blocks):
        i = rng.integers(0, e_rows)
        a = rng.integers(0, n - 2)
        b = rng.integers(a + 1, n)
        u[i, a:b] = 0
    out = _kernel(u)
    exp = ref.pattern_summary_oracle(u)
    np.testing.assert_allclose(out, exp, atol=2e-5)
    # mu bounded, counts are whole samples inside the row
    assert (out[:, 0] >= -1e-6).all() and (out[:, 0] <= 1 + 1e-6).all()
    assert (out[:, 2] > 0).all() and (out[:, 2] <= n).all()
    np.testing.assert_array_equal(out[:, 2], np.rint(out[:, 2]))


@pytest.mark.parametrize("n,edge", [(20_000, TILE), (20_000, 2 * TILE),
                                    (16_500, TILE)])
def test_pattern_summary_multi_tile_matches_numpy(n, edge):
    """Rows spanning several sample tiles (prefix state carried between
    tiles) give the numpy backend's results: moments to 1e-5, counts
    exactly -- including all-zero, single-sample and full-window rows and
    zero runs that straddle the tile edge at ``edge``."""
    from repro.summarize import get_backend
    rng = np.random.default_rng((n, edge))
    u = np.clip(rng.normal(0.45, 0.3, (11, n)), 0, 1).astype(np.float32)
    u[0] = 0.0                                  # all-zero
    u[1] = 0.0
    u[1, n // 2] = 0.7                          # single sample
    u[2] = 0.5                                  # full window
    u[3, edge - 40:edge + 60] = 0.0             # gap across a tile edge
    u[4, :edge + 5] = 0.0                       # leading zeros past a tile
    u[5, edge - 3:] = 0.0                       # trailing zeros from a tile
    u[6, 50:n - 50] = 0.0                       # two bursts, equal-ish mass
    out = _kernel(u)
    exp = get_backend("numpy").batch_stats(u)
    np.testing.assert_allclose(out[:, :2], exp[:, :2], atol=1e-5)
    np.testing.assert_array_equal(out[:, 2], exp[:, 2])


def test_pattern_summary_exact_mass_fraction_matches_numpy():
    """A region holding exactly 80% of a long row's mass is feasible, as
    the numpy backend's f64 target with its 1e-9 slack says.  Samples are
    multiples of 1/128, so the region's prefix sums (under 2**17) are exact
    in f32, while the row total passes 2**17 and its f32 sum is not: only
    the host's f64 total puts the line where numpy puts it."""
    from repro.summarize import get_backend
    n, sizes = 200_000, range(32_000, 36_000, 500)
    rng = np.random.default_rng(80)
    u = np.zeros((len(sizes), n), np.float32)
    for i, b in enumerate(sizes):
        tail = rng.integers(96, 129, b) / 128.0   # region B: 20% of the mass
        u[i, :4 * b] = rng.permutation(np.tile(tail, 4))  # A: exactly 80%
        u[i, n - b:] = tail                       # a long zero gap between
    out = _kernel(u)
    exp = get_backend("numpy").batch_stats(u)
    np.testing.assert_array_equal(exp[:, 2], [4 * b for b in sizes])
    np.testing.assert_allclose(out[:, :2], exp[:, :2], atol=1e-5)
    np.testing.assert_array_equal(out[:, 2], exp[:, 2])
