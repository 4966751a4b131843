"""Compile the main path's kernels and train step for a described TPU v5e
(``v5e:2x2``) without a chip: what Mosaic or XLA:TPU refuses here -- an
unlowerable primitive, more fast memory than a kernel may use, a step that
does not fit the chip's 16 GiB -- fails before any chip time is spent.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler library at a time, and every test worker
imports this file.  Nothing here runs a kernel; results are checked
elsewhere (tests/test_kernels.py in interpret mode, chip_smoke.py on the
chip).
"""
import os

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

HBM_BYTES = 16 * 2**30          # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile cannot be read back without the chip:
    # keep it out of any persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("n", [2048, 200_000])
def test_pattern_summary_compiles(one_chip, n):
    from repro.kernels.ops import pattern_summary
    u = _spec((64, n), jnp.float32, one_chip)
    target = _spec((64,), jnp.float32, one_chip)
    compiled = pattern_summary.lower(u, target, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles(one_chip):
    from repro.kernels.ops import flash_attention
    q = _spec((1, 2048, 8, 128), jnp.bfloat16, one_chip)
    compiled = flash_attention.lower(q, q, q, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_starcoder2_one_layer_train_step_fits(one_chip):
    """The fused train step of one ``starcoder2-3b`` layer at published
    widths, batch 1 x 2048 (a fleet worker of chip_smoke.py), fits one
    chip: its arguments plus temporaries stay under 16 GiB."""
    from repro.configs.registry import ARCHS
    from repro.models.transformer import Transformer
    from repro.optim.adamw import AdamW, OptConfig
    from repro.train.step import make_train_step
    cfg = ARCHS["starcoder2-3b"].with_overrides(num_layers=1)
    model, opt = Transformer(cfg), AdamW(OptConfig())
    place = lambda t: jax.tree_util.tree_map(            # noqa: E731
        lambda s: _spec(s.shape, s.dtype, one_chip), t)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt_state = place(jax.eval_shape(opt.init, params))
    params = place(params)
    batch = {k: _spec((1, 2048), jnp.int32, one_chip)
             for k in ("tokens", "labels")}
    step = jax.jit(make_train_step(model, opt), donate_argnums=(0, 1))
    mem = step.lower(params, opt_state, batch).compile().memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < HBM_BYTES, used
