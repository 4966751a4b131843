"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b --reduced \
      --steps 100 --ckpt-dir /tmp/ckpt

``--mesh DATAxMODEL`` (e.g. ``2x2`` on a four-chip host) shards the job
over the devices jax reports, as they are.  The jax compile cache goes
where ``JAX_COMPILATION_CACHE_DIR`` says, else to ``.jax_cache/`` at the
checkout root (``repro.launch.cache``).
"""
from __future__ import annotations

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--remat", default="none",
                    choices=["none", "dots", "full"])
    ap.add_argument("--mesh", default="",
                    help="DATAxMODEL over all local devices, e.g. 2x2")
    ap.add_argument("--grad-compress", default="none",
                    choices=["none", "bf16", "int8"])
    ap.add_argument("--no-perftracker", action="store_true")
    ap.add_argument("--inject-slow-dataloader", type=float, default=0.0,
                    help="seconds of injected storage latency per batch "
                         "after step N/2 (reproduces case C2P1 online)")
    args = ap.parse_args()

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    from repro.configs.registry import ARCHS, reduced
    from repro.data.pipeline import DataConfig
    from repro.dist.sharding import DistCtx
    from repro.launch.mesh import parse_mesh
    from repro.optim.adamw import OptConfig
    from repro.train.loop import TrainConfig, Trainer

    cfg = ARCHS[args.arch]
    if args.reduced:
        cfg = reduced(cfg)

    dist = None
    if args.mesh:
        dist = DistCtx.from_mesh(parse_mesh(args.mesh))

    data = DataConfig(batch=args.batch, seq_len=args.seq)
    tc = TrainConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                     ckpt_every=args.ckpt_every, remat=args.remat,
                     perftracker=not args.no_perftracker)
    opt = OptConfig(lr_peak=args.lr, warmup_steps=max(10, args.steps // 20),
                    total_steps=args.steps)
    trainer = Trainer(cfg, data, opt, tc, dist=dist)

    if args.inject_slow_dataloader:
        half = args.steps // 2
        orig_next = trainer.loader.next

        def degrading_next():
            if trainer.loader.step >= half:
                trainer.loader.source.data.delay_s = \
                    args.inject_slow_dataloader
            return orig_next()
        trainer.loader.next = degrading_next
        if trainer.pt:
            trainer._next, _ = trainer.pt.wrap(degrading_next, lambda: None)

    trainer.run()
    if trainer.pt:
        res = trainer.pt.flush()
        if res is not None:
            print(res.report())


if __name__ == "__main__":
    main()
