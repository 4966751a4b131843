#!/usr/bin/env python3
"""Bring-up smoke test on the TPU: the watched trainer, the daemon's
compiled summarize kernel and the EROICA diagnosis loop, each driven once
through its normal entry points at published model widths (random weights
from a fixed seed; only depth is cut).

  python chip_smoke.py             one chip: the chip count, summarize,
                                   train and fleet phases
  python chip_smoke.py --chips 4   four chips: the sharded train step and
                                   the expert-parallel MoE layer, each
                                   against its one-chip reference

Exits non-zero, printing no result, when jax finds no TPU or any phase
fails.  Every line of standard output names the device it ran on, and the
last one is ``{"ok": true, "device": {"platform", "kind", "count"}}``.  The
trainer's own log goes to standard error.  Everything runs in this one
process: a chip belongs to one process at a time.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import math
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

GiB = 2 ** 30


# -- one chip -----------------------------------------------------------------

def seeded_rows(n: int, rows: int = 64, seed: int = 0) -> np.ndarray:
    """Utilization rows in [0, 1] with zero bursts, plus the edge rows:
    all-zero (0), a single sample (1) and a full-window region (2)."""
    rng = np.random.default_rng((seed, n))
    u = np.clip(rng.normal(0.45, 0.3, (rows, n)), 0, 1).astype(np.float32)
    for i in range(3, rows, 4):
        a = int(rng.integers(0, n))
        u[i, a:int(rng.integers(a, n)) + 1] = 0.0
    u[0] = 0.0
    u[1] = 0.0
    u[1, n * 3 // 5] = 0.7
    u[2] = 0.5
    return u


def phase_summarize(say, sizes=(2048, 200_000)):
    """The daemon's auto backend is the compiled kernel, and it gives the
    numpy backend's results: moments to 1e-5, counts exactly."""
    from repro.summarize import get_backend
    be = get_backend()
    if be.name != "pallas" or be.interpret():
        raise RuntimeError(
            f"auto summarize backend is {be.name!r} (interpret="
            f"{getattr(be, 'interpret', lambda: None)()}): a TPU process "
            "must run the compiled pallas kernel")
    ref = get_backend("numpy")
    for n in sizes:
        u = seeded_rows(n)
        t0 = time.perf_counter()
        be.batch_stats(u)
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = be.batch_stats(u)
        warm = time.perf_counter() - t0
        want = ref.batch_stats(u)
        err = float(np.abs(out[:, :2] - want[:, :2]).max())
        if err > 1e-5 or not np.array_equal(out[:, 2], want[:, 2]):
            bad = np.flatnonzero(out[:, 2] != want[:, 2])
            raise AssertionError(
                f"pallas != numpy at n={n}: max moment error {err:.2e}, "
                f"count mismatch rows {bad.tolist()}")
        say(f"summarize n={n} rows={len(u)}: first call {first:.3f} s "
            f"(compile included), warm call {warm:.4f} s, compile about "
            f"{first - warm:.3f} s; max |mean,std - numpy| {err:.1e}, "
            f"counts equal")


def phase_train(say, cfg, batch: int, seq: int, steps: int):
    """``Trainer.run`` with its PerfTracker, as ``repro.launch.train``
    builds them (its default window and detector); at half the steps the
    data loader slows down, as ``--inject-slow-dataloader`` does, by about
    three warm steps."""
    import jax
    import jax.numpy as jnp
    from repro.core.mitigation import Action
    from repro.data.pipeline import DataConfig
    from repro.optim.adamw import OptConfig
    from repro.train.loop import TrainConfig, Trainer, gemm_fraction

    trainer = Trainer(cfg, DataConfig(batch=batch, seq_len=seq),
                      OptConfig(warmup_steps=steps // 3, total_steps=steps),
                      TrainConfig(steps=steps, log_every=1, remat="full",
                                  perftracker=True))
    half = steps // 2
    orig_next = trainer.loader.next
    fault = {}

    def degrading_next():
        if trainer.loader.step == half:
            warm = float(np.median([h["step_s"]
                                    for h in trainer.history[1:]]))
            fault.update(warm=warm, delay=3.0 * warm)
            trainer.loader.source.data.delay_s = fault["delay"]
        return orig_next()
    trainer.loader.next = degrading_next
    trainer._next, _ = trainer.pt.wrap(degrading_next, lambda: None)

    with contextlib.redirect_stdout(sys.stderr):
        params, opt_state = trainer.run()
        tail = trainer.pt.flush()
    losses = [h["loss"] for h in trainer.history]
    if len(losses) != steps or not all(map(math.isfinite, losses)):
        raise AssertionError(f"losses not all finite: {losses}")
    results = [r for r in (trainer.last_diagnosis, tail) if r is not None]
    named = [d for r in results for d in r.diagnoses
             if "dataloader" in d.abnormality.function
             and "C2P1" in d.hint]
    actions = [p.action for _, p in trainer.mitigations]
    if not named or Action.MIGRATE_DATALOADER not in actions:
        raise AssertionError(
            f"no C2P1 dataloader diagnosis with migrate_dataloader: "
            f"diagnoses {[r.functions() for r in results]}, "
            f"actions {[a.value for a in actions]}")
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    tokens = batch * seq
    say(f"train {cfg.name} {cfg.num_layers} layers batch {batch}x{seq} "
        f"remat=full: median warm step {fault['warm']:.4f} s "
        f"({tokens / fault['warm']:.0f} tokens/s), first step (compile "
        f"included) {trainer.history[0]['step_s']:.1f} s, peak_bytes_in_use "
        f"{peak / GiB:.2f} GiB, losses {losses[0]:.3f} -> {losses[-1]:.3f}")
    say(f"train: loader delay {fault['delay']:.3f} s from step {half + 1}; "
        f"diagnosis {named[0].abnormality.function} ({named[0].hint}); "
        f"planned {', '.join(sorted({a.value for a in actions}))}")
    batch0 = {k: jnp.asarray(v)
              for k, v in trainer.source.batch_at(0).items()}
    gemm = gemm_fraction(
        trainer._jit_step.lower(params, opt_state, batch0).compile())
    say(f"train: gemm_frac of the fused step {gemm:.3f} (HLO cost model "
        f"against the {dev.device_kind} peaks)")


def phase_fleet(say, cfg, batch: int, seq: int, workers: int = 4):
    """Differential observability over real jitted steps: ``ScenarioRunner``
    in-process with a ``TrainerWorkload`` fleet, one worker's data loader
    burning CPU; the pipeline summarizes with the auto (compiled) backend."""
    from repro.core.mitigation import Action
    from repro.data.pipeline import DataConfig
    from repro.online import ScenarioRunner, ScheduledFault
    from repro.optim.adamw import OptConfig
    from repro.train.loop import TrainConfig
    from repro.train.workload import (DataloaderBurn, TrainerWorkload,
                                      default_trainer_detector_cfg)
    ipw, n_win = 8, 7
    setup = (cfg, DataConfig(batch=batch, seq_len=seq),
             OptConfig(warmup_steps=2, total_steps=10_000),
             TrainConfig(log_every=10_000, perftracker=False))
    wl = TrainerWorkload(n_workers=workers, setup=setup)
    try:
        res = ScenarioRunner(
            None, [ScheduledFault(DataloaderBurn(workers=(1,)), 2, n_win)],
            n_windows=n_win, iters_per_window=ipw,
            detector_cfg=default_trainer_detector_cfg(ipw), workload=wl,
            summarize_backend=None).run()
        base = wl.base_iter_s
        gemm = wl.workers[0].trainer.bundle.gemm_frac
    finally:
        wl.close()
    loader = [i for i in res.incidents if i.function == "dataloader.next"]
    if not loader or any(set(i.workers) != {1} for i in loader) \
            or not any(Action.MIGRATE_DATALOADER in [p.action
                                                     for p in i.plans]
                       for i in loader):
        raise AssertionError(
            "expected dataloader.next on worker 1 only with "
            "migrate_dataloader; got "
            f"{[(i.function, i.workers, [p.action.value for p in i.plans]) for i in res.incidents]}")
    say(f"fleet {workers} x {cfg.name} {cfg.num_layers} layer batch "
        f"{batch}x{seq}: median warm iteration {base:.4f} s, gemm_frac "
        f"{gemm:.3f}; incidents "
        f"{[(i.function, list(i.workers)) for i in res.incidents]}; "
        f"dataloader.next on worker 1 only -> migrate_dataloader")


def phase_chips(say):
    """The PCI count that caps trainer processes agrees with jax."""
    import jax
    from repro.online.scenario import accelerator_chips
    pci, seen = accelerator_chips(), len(jax.devices())
    if pci != seen:
        raise AssertionError(f"PCI scan counts {pci} TPU chips, jax {seen}")
    say(f"chips: the PCI scan and jax both count {pci}")


def run_one_chip(say):
    import jax
    from repro.configs.registry import ARCHS
    from repro.summarize import get_backend
    sc2 = ARCHS["starcoder2-3b"]
    phases = [
        ("chips", lambda: phase_chips(say)),
        ("summarize", lambda: phase_summarize(say)),
        ("train", lambda: phase_train(
            say, sc2.with_overrides(num_layers=4), 4, 4096, 30)),
        ("fleet", lambda: phase_fleet(
            say, sc2.with_overrides(num_layers=1), 1, 2048)),
    ]
    ok = run_phases(say, phases)
    shapes = sorted(get_backend("pallas").shapes)
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0)
    say(f"pattern_summary compiled {len(shapes)} shapes over the run: "
        f"{shapes}; process peak_bytes_in_use {peak / GiB:.2f} GiB")
    return ok


# -- four chips ---------------------------------------------------------------

def _host_tree(tree):
    import jax
    return jax.tree_util.tree_map(lambda a: np.asarray(jax.device_get(a)),
                                  tree)


def phase_sharded_step(say, cfg, batch: int, seq: int):
    """One train step on a (data=2, model=2) mesh of the four chips against
    the same step on one chip (tests/test_dist.py tolerances)."""
    import jax
    from repro.dist.sharding import DistCtx
    from repro.launch.mesh import make_mesh
    from repro.models.io import synth_batch
    from repro.models.transformer import Transformer
    from repro.optim.adamw import AdamW, OptConfig
    from repro.train.step import make_train_step

    opt = AdamW(OptConfig())
    batch_np = _host_tree(synth_batch(cfg, "train", batch, seq))

    m1 = Transformer(cfg, remat="full")
    p1 = m1.init(jax.random.PRNGKey(0))
    s1 = opt.init(p1)
    step1 = jax.jit(make_train_step(m1, opt), donate_argnums=(0, 1))
    p1, s1, met1 = step1(p1, s1, batch_np)
    loss1, ref = float(met1["loss"]), _host_tree(p1)
    del p1, s1, met1
    gc.collect()

    dist = DistCtx.from_mesh(make_mesh((2, 2), ("data", "model")))
    m2 = Transformer(cfg, dist=dist, remat="full")
    p2 = m2.init(jax.random.PRNGKey(0))
    ps = dist.params_shardings(p2)
    p2 = jax.device_put(p2, ps)
    s2 = opt.init(p2)
    bs = dist.batch_shardings(batch_np)
    step2 = jax.jit(make_train_step(m2, opt), in_shardings=(ps, None, bs),
                    donate_argnums=(0, 1))
    p2, s2, met2 = step2(p2, s2, jax.device_put(batch_np, bs))
    loss2 = float(met2["loss"])
    diff = max(float(np.max(np.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(ref),
        jax.tree_util.tree_leaves(_host_tree(p2))))
    ok = abs(loss1 - loss2) < 1e-3 and diff < 5e-3
    say(f"sharded step {cfg.name} {cfg.num_layers} layers batch "
        f"{batch}x{seq} {cfg.param_dtype}: loss one chip {loss1:.6f}, "
        f"(data=2, model=2) {loss2:.6f}, |dloss| {abs(loss1 - loss2):.2e} "
        f"(< 1e-3), max |dparam| {diff:.2e} (< 5e-3)")
    if not ok:
        raise AssertionError("sharded step outside tolerance")


def phase_moe(say, cfg, batch: int, seq: int):
    """The expert-parallel MoE layer (shard_map over the mesh) against the
    local one (tests/test_dist.py tolerances)."""
    import jax
    import jax.numpy as jnp
    from repro.dist.sharding import DistCtx
    from repro.launch.mesh import make_mesh
    from repro.models import moe as M

    key = jax.random.PRNGKey(0)
    p = M.init_moe(key, cfg)
    x = jax.random.normal(key, (batch, seq, cfg.d_model), jnp.float32)
    y_local, st_local = jax.jit(lambda p, x: M.apply_moe(p, x, cfg))(p, x)
    dist = DistCtx.from_mesh(make_mesh((2, 2), ("data", "model")))
    y_ep, st_ep = jax.jit(lambda p, x: M.apply_moe(p, x, cfg, dist=dist))(
        p, x)
    E = cfg.num_experts
    err = float(np.max(np.abs(np.asarray(y_local) - np.asarray(y_ep))))
    perr = float(np.max(np.abs(np.asarray(st_local)[E:]
                               - np.asarray(st_ep)[E:])))
    say(f"moe {cfg.name} d_model {cfg.d_model} d_ff {cfg.d_ff} "
        f"{E} experts top-{cfg.top_k}, x {batch}x{seq}: max |y_ep - "
        f"y_local| {err:.2e} (< 5e-4), router prob err {perr:.2e} (< 1e-3)")
    if not (err < 5e-4 and perr < 1e-3):
        raise AssertionError("expert-parallel MoE outside tolerance")


def run_four_chips(say):
    import jax
    from repro.configs.registry import ARCHS
    # f32 numerics, so the one-chip comparison is held to the f32
    # tolerances of tests/test_dist.py; every width is the published one.
    # Full-precision f32 matmuls (the TPU default rounds their inputs to
    # bf16), as on the CPU those tolerances were set on.
    f32 = dict(dtype="float32", param_dtype="float32")
    sc2 = ARCHS["starcoder2-3b"].with_overrides(num_layers=2, **f32)
    ds = ARCHS["deepseek-v2-lite-16b"].with_overrides(
        num_experts=8, capacity_factor=8.0, **f32)
    with jax.default_matmul_precision("highest"):
        return run_phases(say, [
            ("sharded", lambda: phase_sharded_step(say, sc2, 8, 2048)),
            ("moe", lambda: phase_moe(say, ds, 8, 512)),
        ])


# -- driver -------------------------------------------------------------------

def run_phases(say, phases) -> bool:
    ok = True
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:
            traceback.print_exc()
            ok = False
            say(f"[{name}] FAILED after {time.perf_counter() - t0:.1f} s")
            continue
        gc.collect()
        say(f"[{name}] passed in {time.perf_counter() - t0:.1f} s")
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from repro.launch.cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        print(f"no TPU: jax found {d0.platform} devices; this smoke test "
              "runs on the chip only", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} chips; jax found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    tag = f"[{d0.platform} {d0.device_kind} x{len(devices)}]"

    def say(msg: str) -> None:
        print(f"{tag} {msg}", flush=True)

    say(f"jax {jax.__version__}, compile cache {cache}")
    ok = run_four_chips(say) if args.chips == 4 else run_one_chip(say)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
