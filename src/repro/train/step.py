"""Train / prefill / serve step builders (the programs the dry-run lowers and
the train loop executes)."""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models.transformer import Transformer
from repro.optim.adamw import AdamW

#: named scopes of the step programs: every operation's ``op_name`` is
#: ``.../jvp(fwd)/...`` (forward), ``.../transpose(jvp(fwd))/...`` (backward,
#: with rematerialized forward under ``rematted_computation``) or
#: ``.../opt/...`` (optimizer update), so a device trace can be split by phase
FWD_SCOPE, OPT_SCOPE = "fwd", "opt"


def _scoped_loss(model: Transformer):
    def loss(params, batch):
        with jax.named_scope(FWD_SCOPE):
            return model.loss(params, batch)
    return loss


def _scoped_update(opt: AdamW, grads, opt_state, params):
    with jax.named_scope(OPT_SCOPE):
        return opt.update(grads, opt_state, params)


def make_train_step(model: Transformer, opt: AdamW, accum_steps: int = 1):
    """accum_steps > 1: gradient accumulation over micro-batches via
    lax.scan — per-device activation memory scales with the micro-batch
    (HBM-fit lever for the big archs; EXPERIMENTS §Perf H5). The global
    batch is split on the leading axis; grads are averaged."""
    grad_fn = jax.value_and_grad(_scoped_loss(model), has_aux=True)

    def train_step(params, opt_state, batch):
        if accum_steps == 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            def micro(b):
                return jax.tree_util.tree_map(
                    lambda x: x.reshape((accum_steps,
                                         x.shape[0] // accum_steps)
                                        + x.shape[1:]), b)

            def body(acc, mb):
                (l, m), g = grad_fn(params, mb)
                acc = jax.tree_util.tree_map(jnp.add, acc, g)
                return acc, (l, m)

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            gsum, (losses, ms) = jax.lax.scan(body, zeros, micro(batch))
            grads = jax.tree_util.tree_map(
                lambda g: g / accum_steps, gsum)
            loss = losses.mean()
            metrics = jax.tree_util.tree_map(lambda x: x.mean(), ms)
        new_params, new_state, opt_metrics = _scoped_update(
            opt, grads, opt_state, params)
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        metrics["loss"] = loss
        return new_params, new_state, metrics
    return train_step


def make_split_train_step(model: Transformer, opt: AdamW):
    """Two separately-jittable halves of the fused step — (grad, opt) — for
    instrumented loops that want a real host-visible fence between the
    fwd+bwd dispatch and the optimizer update (``train.step`` vs
    ``optimizer.step`` phases).  Numerically identical to
    ``make_train_step(accum_steps=1)``; slightly slower (two dispatches,
    grads round-trip through HBM)."""
    grad_fn = jax.value_and_grad(_scoped_loss(model), has_aux=True)

    def grad_step(params, batch):
        (loss, metrics), grads = grad_fn(params, batch)
        metrics = dict(metrics)
        metrics["loss"] = loss
        return grads, metrics

    def opt_step(grads, opt_state, params):
        new_params, new_state, opt_metrics = _scoped_update(
            opt, grads, opt_state, params)
        return new_params, new_state, opt_metrics

    return grad_step, opt_step


def make_prefill_step(model: Transformer):
    def prefill_step(params, batch):
        hidden, _, cache = model.forward(params, batch, collect_cache=True)
        last_logits = model.logits(params, hidden[:, -1:, :])
        return last_logits, cache
    return prefill_step


def make_serve_step(model: Transformer):
    def serve_step(params, cache, batch, pos):
        return model.decode_step(params, cache, batch, pos)
    return serve_step
