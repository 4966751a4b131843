"""Optimizer / data / checkpoint / compression / MoE substrate tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.checkpoint import Checkpointer
from repro.configs.registry import ARCHS, reduced
from repro.data.pipeline import DataConfig, DataLoader, SyntheticLM
from repro.models import moe as M
from repro.optim.adamw import AdamW, OptConfig, lr_schedule
from repro.optim.compress import dequantize_int8, quantize_int8


# -- AdamW ---------------------------------------------------------------------

def test_adamw_quadratic_convergence():
    opt = AdamW(OptConfig(lr_peak=0.1, warmup_steps=1, total_steps=400,
                          weight_decay=0.0, clip_norm=0.0))
    params = {"w": jnp.array([3.0, -2.0])}
    state = opt.init(params)
    for _ in range(300):
        g = {"w": 2 * params["w"]}       # d/dw ||w||^2
        params, state, _ = opt.update(g, state, params)
    assert float(jnp.abs(params["w"]).max()) < 1e-2


def test_adamw_decay_mask():
    opt = AdamW(OptConfig(weight_decay=0.5, lr_peak=0.1, warmup_steps=1))
    params = {"mlp": {"wi": jnp.ones((4, 4))},
              "ln": {"scale": jnp.ones((4,))}}
    state = opt.init(params)
    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    p2, _, _ = opt.update(zero, state, params)
    assert float(p2["mlp"]["wi"][0, 0]) < 1.0     # decayed
    assert float(p2["ln"]["scale"][0]) == 1.0     # masked


def test_grad_clip_and_metrics():
    opt = AdamW(OptConfig(clip_norm=1.0, lr_peak=0.1, warmup_steps=1))
    params = {"w": jnp.zeros(3)}
    state = opt.init(params)
    _, _, m = opt.update({"w": jnp.full(3, 100.0)}, state, params)
    assert m["grad_norm"] > 100.0


def test_lr_schedule():
    c = OptConfig(lr_peak=1.0, warmup_steps=10, total_steps=100,
                  min_lr_ratio=0.1)
    assert float(lr_schedule(c, jnp.int32(5))) == pytest.approx(0.5)
    assert float(lr_schedule(c, jnp.int32(10))) == pytest.approx(1.0)
    assert float(lr_schedule(c, jnp.int32(100))) == pytest.approx(0.1)


def test_master_weights_bf16_params():
    cfg = reduced(ARCHS["granite-34b"]).with_overrides(
        param_dtype="bfloat16", dtype="bfloat16")
    from repro.models.transformer import Transformer
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = AdamW(OptConfig())
    state = opt.init(params)
    masters = jax.tree_util.tree_leaves(state["master"])
    assert all(m.dtype == jnp.float32 for m in masters)


# -- data -------------------------------------------------------------------------

def test_data_determinism_and_sharding():
    cfg = reduced(ARCHS["granite-34b"])
    d0 = SyntheticLM(cfg, DataConfig(batch=2, seq_len=32, shard=0))
    d0b = SyntheticLM(cfg, DataConfig(batch=2, seq_len=32, shard=0))
    d1 = SyntheticLM(cfg, DataConfig(batch=2, seq_len=32, shard=1))
    b0, b0b, b1 = d0.batch_at(5), d0b.batch_at(5), d1.batch_at(5)
    np.testing.assert_array_equal(b0["tokens"], b0b["tokens"])
    assert not np.array_equal(b0["tokens"], b1["tokens"])
    # labels are next tokens
    assert (b0["labels"] < cfg.vocab_size).all()


def test_synthetic_lm_follows_its_recurrence():
    """Between restarts every row follows tok[t+1] = (31*tok[t] + 17*m) % V
    for one m in 1..7, and restarts are about 5% of positions."""
    cfg = reduced(ARCHS["granite-34b"])
    V = cfg.vocab_size
    b = SyntheticLM(cfg, DataConfig(batch=8, seq_len=512)).batch_at(3)
    x = np.concatenate([b["tokens"], b["labels"][:, -1:]], axis=1)
    np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])
    for row in x.astype(np.int64):
        d = (row[1:] - 31 * row[:-1]) % V
        vals, counts = np.unique(d, return_counts=True)
        assert vals[counts.argmax()] in {17 * m % V for m in range(1, 8)}
        assert 0.88 < counts.max() / len(d) <= 1.0


def test_dataloader_prefetch_and_anchor():
    cfg = reduced(ARCHS["granite-34b"])
    src = SyntheticLM(cfg, DataConfig(batch=2, seq_len=16))
    loader = DataLoader(src)
    b1 = loader.next()
    b2 = loader.next()
    assert b1["tokens"].shape == (2, 16)
    assert not np.array_equal(b1["tokens"], b2["tokens"])
    loader.close()


# -- checkpoint --------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
    ck.save(10, tree, extra={"note": "x"}, async_=False)
    ck.save(20, tree, async_=True)
    ck.wait()
    assert ck.steps() == [10, 20]
    restored, meta = ck.restore(20, tree)
    assert meta["step"] == 20
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(tree["a"]))
    assert restored["b"]["c"].dtype == jnp.bfloat16


def test_checkpoint_gc(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        ck.save(s, {"x": jnp.zeros(2)}, async_=False)
    assert ck.steps() == [3, 4]


# -- compression --------------------------------------------------------------------

def test_int8_quant_roundtrip():
    x = jnp.asarray(np.random.default_rng(0).normal(0, 1, 128),
                    jnp.float32)
    q, s = quantize_int8(x)
    err = jnp.abs(dequantize_int8(q, s) - x)
    assert float(err.max()) <= float(s) * 0.5 + 1e-6


def test_error_feedback_unbiased():
    """With error feedback, the accumulated applied signal tracks the true
    gradient sum (compression noise does not accumulate)."""
    rng = np.random.default_rng(1)
    g_true = jnp.asarray(rng.normal(0, 1, 64), jnp.float32)
    residual = jnp.zeros(64)
    applied = jnp.zeros(64)
    for _ in range(50):
        gf = g_true + residual
        q, s = quantize_int8(gf)
        deq = dequantize_int8(q, s)
        residual = gf - deq
        applied += deq
    drift = jnp.abs(applied / 50 - g_true)
    assert float(drift.max()) < 0.01


# -- MoE local dispatch ----------------------------------------------------------------

def _moe_cfg():
    return reduced(ARCHS["deepseek-v2-lite-16b"])


def test_moe_gates_and_capacity():
    cfg = _moe_cfg().with_overrides(capacity_factor=float(8))
    key = jax.random.PRNGKey(0)
    p = M.init_moe(key, cfg)
    x = jax.random.normal(key, (2, 16, cfg.d_model))
    y, stats = M.apply_moe(p, x, cfg)
    assert y.shape == x.shape
    assert jnp.all(jnp.isfinite(y))
    E = cfg.num_experts
    counts = stats[:E]
    assert float(counts.sum()) == 2 * 16 * cfg.top_k   # no drops at cf=E


@pytest.mark.parametrize("which", ["moe", "mlp"])
def test_gated_weights_init_at_fan_in_d(which):
    """Gate and up projections are drawn at std 1/sqrt(d_model), like every
    other d -> ff projection; the (d, 2, ff) layout does not make 2 the
    fan-in."""
    from repro.models import layers as L
    cfg = _moe_cfg()
    d = cfg.d_model
    p = (M.init_moe(jax.random.PRNGKey(0), cfg) if which == "moe"
         else L.init_mlp(jax.random.PRNGKey(0), d, 256, "swiglu", False))
    std = float(jnp.std(p["wi"]))
    # truncated at 2 sigma: the sample std is about 0.88 / sqrt(d)
    assert 0.8 / d ** 0.5 < std < 0.95 / d ** 0.5, std


def test_moe_dropping_reduces_tokens():
    cfg = _moe_cfg().with_overrides(capacity_factor=0.25)
    key = jax.random.PRNGKey(0)
    p = M.init_moe(key, cfg)
    x = jax.random.normal(key, (2, 32, cfg.d_model))
    y, _ = M.apply_moe(p, x, cfg)
    assert jnp.all(jnp.isfinite(y))


def test_moe_grad_flows_to_router_and_experts():
    cfg = _moe_cfg()
    key = jax.random.PRNGKey(0)
    p = M.init_moe(key, cfg)
    x = jax.random.normal(key, (1, 16, cfg.d_model))

    def loss(p):
        y, _ = M.apply_moe(p, x, cfg)
        return jnp.sum(y ** 2)

    g = jax.grad(loss)(p)
    assert float(jnp.abs(g["router"]).sum()) > 0
    assert float(jnp.abs(g["wi"]).sum()) > 0
    assert float(jnp.abs(g["wo"]).sum()) > 0
