"""Distributed execution tests (subprocess: device count locks at first jax
init, so multi-device runs get their own interpreter with 8 host devices).

These EXECUTE (not just compile): sharded train step on a (2,4) mesh must
match the single-device step bit-for-bit-ish, including the MoE shard_map
expert-parallel path; elastic checkpoint restore re-shards to a different
mesh."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def run_sub(code: str, timeout=540):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(REPO / "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    out = run_sub("""
        import jax, jax.numpy as jnp
        from repro.configs.registry import ARCHS, reduced
        from repro.dist.sharding import DistCtx
        from repro.launch.mesh import make_mesh
        from repro.models.transformer import Transformer
        from repro.models.io import synth_batch
        from repro.optim.adamw import AdamW, OptConfig
        from repro.train.step import make_train_step

        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = reduced(ARCHS["granite-34b"], d_model=64).with_overrides(
            num_heads=4, num_kv_heads=4, vocab_size=512)
        batch = synth_batch(cfg, "train", 4, 32)
        opt = AdamW(OptConfig())

        # single device
        m1 = Transformer(cfg)
        p1 = m1.init(jax.random.PRNGKey(0))
        s1 = opt.init(p1)
        step1 = jax.jit(make_train_step(m1, opt))
        p1b, _, met1 = step1(p1, s1, batch)

        # sharded
        dist = DistCtx.from_mesh(mesh)
        m2 = Transformer(cfg, dist=dist)
        p2 = m2.init(jax.random.PRNGKey(0))
        ps = dist.params_shardings(p2)
        p2 = jax.device_put(p2, ps)
        s2 = opt.init(p2)
        bs = dist.batch_shardings(batch)
        batch2 = jax.device_put(batch, bs)
        step2 = jax.jit(make_train_step(m2, opt),
                        in_shardings=(ps, None, bs))
        p2b, _, met2 = step2(p2, s2, batch2)

        d = jax.tree_util.tree_map(
            lambda a, b: float(jnp.max(jnp.abs(a - b))), p1b, p2b)
        mx = max(jax.tree_util.tree_leaves(d))
        print("loss1", float(met1["loss"]), "loss2", float(met2["loss"]),
              "maxdiff", mx)
        assert abs(float(met1["loss"]) - float(met2["loss"])) < 1e-3
        assert mx < 5e-3
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_moe_expert_parallel_matches_local():
    out = run_sub("""
        import jax, jax.numpy as jnp
        from repro.configs.registry import ARCHS, reduced
        from repro.dist.sharding import DistCtx
        from repro.launch.mesh import make_mesh
        from repro.models import moe as M

        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = reduced(ARCHS["deepseek-v2-lite-16b"], d_model=64)
        cfg = cfg.with_overrides(num_experts=8, top_k=2,
                                 capacity_factor=8.0)
        key = jax.random.PRNGKey(0)
        p = M.init_moe(key, cfg)
        x = jax.random.normal(key, (8, 16, cfg.d_model))

        y_local, stats_local = M.apply_moe(p, x, cfg, dist=None)

        dist = DistCtx.from_mesh(mesh)
        def f(p, x):
            y, stats = M.apply_moe(p, x, cfg, dist=dist)
            return y, stats
        y_ep, stats_ep = jax.jit(f)(p, x)
        err = float(jnp.max(jnp.abs(y_local - y_ep)))
        # stats: local capacity differs (per-shard tokens), compare mean prob
        E = cfg.num_experts
        perr = float(jnp.max(jnp.abs(stats_local[E:] - stats_ep[E:])))
        print("err", err, "perr", perr)
        assert err < 5e-4 and perr < 1e-3
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_elastic_checkpoint_restore_new_mesh(tmp_path):
    out = run_sub(f"""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.ckpt.checkpoint import Checkpointer
        from repro.launch.mesh import make_mesh

        tree = {{"w": jnp.arange(64.0).reshape(8, 8)}}
        mesh1 = make_mesh((2, 4), ("data", "model"))
        sh1 = {{"w": NamedSharding(mesh1, P("data", "model"))}}
        t1 = jax.device_put(tree, sh1)
        ck = Checkpointer("{tmp_path}")
        ck.save(1, t1, async_=False)

        # 'failure': restore onto a smaller mesh (2 hosts dropped)
        mesh2 = make_mesh((2, 2), ("data", "model"),
                          devices=jax.devices()[:4])
        sh2 = {{"w": NamedSharding(mesh2, P("data", "model"))}}
        t2, meta = ck.restore(1, tree, sh2)
        assert t2["w"].sharding == sh2["w"]
        import numpy as np
        np.testing.assert_array_equal(np.asarray(t2["w"]),
                                      np.asarray(tree["w"]))
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_grad_compression_psum():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.optim.compress import psum_compressed

        mesh = make_mesh((8,), ("pod",))
        g = jnp.asarray(np.random.default_rng(0).normal(
            0, 1, (8, 32)), jnp.float32)

        def body(gl):
            out_bf16, _ = psum_compressed({"g": gl[0]}, "pod", "bf16")
            out_int8, _ = psum_compressed({"g": gl[0]}, "pod", "int8")
            exact, _ = psum_compressed({"g": gl[0]}, "pod", "none")
            return out_bf16["g"], out_int8["g"], exact["g"]

        f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("pod"),
                                  out_specs=P()))
        b16, i8, exact = f(g)
        e1 = float(jnp.max(jnp.abs(b16 - exact)))
        e2 = float(jnp.max(jnp.abs(i8 - exact)))
        print("bf16 err", e1, "int8 err", e2)
        assert e1 < 0.02 and e2 < 0.05
        print("OK")
    """)
    assert "OK" in out
