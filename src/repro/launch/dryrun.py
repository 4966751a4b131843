"""Multi-pod dry-run: lower + compile every (architecture x input-shape) cell
on the production meshes and derive roofline terms from the compiled
artifacts. See DESIGN.md §4/§6 and EXPERIMENTS.md §Dry-run.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-2b \
      --shape train_4k --mesh single
"""
import argparse
import json
import os
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import shapes_for
from repro.configs.registry import ARCHS, get_arch, get_shape
from repro.dist.sharding import DistCtx
from repro.launch import analysis as an
from repro.launch import hlo_cost
from repro.launch.mesh import make_production_mesh
from repro.models import io as mio
from repro.models.transformer import Transformer
from repro.optim.adamw import AdamW, OptConfig
from repro.train.step import make_prefill_step, make_serve_step, \
    make_train_step

DEFAULT_OUT = Path("reports/dryrun")


def _replicated(mesh):
    return NamedSharding(mesh, P())


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               remat: str = "none", folded: bool = False,
               pad_heads: bool = False, zero1_moe: bool = False,
               serve_no_fsdp: bool = False, accum: int = 1):
    """Builds and lowers the cell's program. Returns (lowered, meta)."""
    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    dist = DistCtx.from_mesh(mesh)
    if zero1_moe:
        dist.zero1_moe = True
    if serve_no_fsdp and shape.kind == "decode":
        # serving: weights are read-only — replicate over DP, shard over TP
        # only (llama4's 400B stays FSDP: 50 GB/chip replicated won't fit)
        dist.fsdp = False
    model = Transformer(cfg, dist=dist,
                        remat=remat if shape.kind == "train" else "none",
                        folded=folded, pad_heads=pad_heads)
    specs = mio.input_specs(cfg, shape)
    params_spec = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    ps = dist.params_shardings(params_spec)
    bs = dist.batch_shardings(specs)

    with mesh:
        if shape.kind == "train":
            opt = AdamW(OptConfig())
            opt_spec = jax.eval_shape(opt.init, params_spec)
            # opt state always fully sharded (ZeRO); with zero1_moe the
            # PARAMS are dp-replicated but m/v/master stay dp-sharded
            opt_dist = DistCtx.from_mesh(mesh)
            osh = opt.state_shardings(opt_dist.params_shardings(params_spec),
                                      _replicated(mesh))
            step = make_train_step(model, opt, accum_steps=accum)
            jitted = jax.jit(step, in_shardings=(ps, osh, bs),
                             out_shardings=(ps, osh, None))
            lowered = jitted.lower(params_spec, opt_spec, specs)
        elif shape.kind == "prefill":
            step = make_prefill_step(model)
            jitted = jax.jit(step, in_shardings=(ps, bs))
            lowered = jitted.lower(params_spec, specs)
        else:  # decode
            B = shape.global_batch
            cache_spec = jax.eval_shape(
                lambda: model.init_cache(B, shape.seq_len))
            cs = dist.cache_shardings(cache_spec, B)
            pos_spec = jax.ShapeDtypeStruct((), jnp.int32)
            step = make_serve_step(model)
            jitted = jax.jit(step, in_shardings=(ps, cs, bs,
                                                 _replicated(mesh)),
                             out_shardings=(None, cs))
            lowered = jitted.lower(params_spec, cache_spec, specs, pos_spec)

    meta = {"cfg": cfg, "shape": shape, "mesh": mesh,
            "devices": mesh.size, "params_spec": params_spec}
    return lowered, meta


def analyse(lowered, meta, compile_s: float):
    compiled = lowered.compile()
    cfg, shape = meta["cfg"], meta["shape"]
    n_dev = meta["devices"]

    raw_cost = {}
    try:
        raw_cost = dict(compiled.cost_analysis())
    except Exception as e:  # pragma: no cover
        raw_cost = {"error": str(e)}

    mem = {}
    try:
        ma = compiled.memory_analysis()
        mem = {k: int(getattr(ma, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")}
        mem["total_per_device"] = (mem["argument_size_in_bytes"]
                                   + mem["temp_size_in_bytes"])
    except Exception as e:  # pragma: no cover
        mem = {"error": str(e)}

    text = compiled.as_text()
    cost = hlo_cost.expanded_cost(text, n_dev)
    coll = an.CollectiveStats(bytes_by_op=dict(cost.coll_bytes),
                              count_by_op={k: int(v) for k, v in
                                           cost.coll_counts.items()})
    mf = an.model_flops(cfg, shape)
    terms = an.roofline({"flops": cost.flops, "bytes accessed": cost.bytes},
                        coll, n_dev, mf)
    counts = cfg.param_counts()
    return {
        "arch": cfg.name,
        "shape": shape.name,
        "kind": shape.kind,
        "mesh": "x".join(str(s) for s in meta["mesh"].devices.shape),
        "devices": n_dev,
        "compile_s": round(compile_s, 1),
        "hlo_text_bytes": len(text),
        "unknown_trip_loops": cost.unknown_trip_loops,
        "params_total": counts["total"],
        "params_active": counts["active"],
        "memory": mem,
        "raw_cost_flops": float(raw_cost.get("flops", -1.0)),
        "raw_cost_bytes": float(raw_cost.get("bytes accessed", -1.0)),
        "roofline": terms,
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: Path,
             remat: str, folded: bool, force: bool, tag: str = "",
             pad_heads: bool = False, zero1_moe: bool = False,
             serve_no_fsdp: bool = False, accum: int = 1) -> dict:
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    suffix = f"__{tag}" if tag else ""
    out = out_dir / mesh_tag / f"{arch}__{shape_name}{suffix}.json"
    if out.exists() and not force:
        res = json.loads(out.read_text())
        print(f"[skip] {mesh_tag} {arch} {shape_name} (cached)")
        return res
    out.parent.mkdir(parents=True, exist_ok=True)
    t0 = time.time()
    lowered, meta = lower_cell(arch, shape_name, multi_pod, remat, folded,
                               pad_heads, zero1_moe, serve_no_fsdp, accum)
    t_lower = time.time() - t0
    t1 = time.time()
    res = analyse(lowered, meta, t_lower)
    res["lower_s"] = round(t_lower, 1)
    res["compile_s"] = round(time.time() - t1, 1)
    res["remat"] = remat
    res["folded"] = folded
    res["pad_heads"] = pad_heads
    res["zero1_moe"] = zero1_moe
    res["serve_no_fsdp"] = serve_no_fsdp
    res["accum"] = accum
    out.write_text(json.dumps(res, indent=1))
    r = res["roofline"]
    print(f"[ok] {mesh_tag} {arch} {shape_name}{suffix}: "
          f"dominant={r['dominant']} "
          f"tc={r['t_compute_s']:.4f}s tm={r['t_memory_s']:.4f}s "
          f"tcoll={r['t_collective_s']:.4f}s "
          f"useful={r['useful_flops_ratio']:.3f} "
          f"roofline={r['roofline_fraction']:.3f} "
          f"(lower {res['lower_s']}s compile {res['compile_s']}s)",
          flush=True)
    return res


def main():
    # 512 placeholder host devices for the production mesh; set before the
    # first device query (importing jax does not create a backend), so
    # importing this module never reshapes devices
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                               + os.environ.get("XLA_FLAGS", ""))
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=str(DEFAULT_OUT))
    ap.add_argument("--remat", default="full",
                    choices=["none", "dots", "full"])
    ap.add_argument("--folded", action="store_true",
                    help="balanced causal folding in blocked attention")
    ap.add_argument("--pad-heads", action="store_true",
                    help="phantom-head TP padding (uneven head counts)")
    ap.add_argument("--zero1-moe", action="store_true",
                    help="ZeRO-1 expert weights (no per-layer FSDP gathers)")
    ap.add_argument("--serve-no-fsdp", action="store_true",
                    help="decode cells: replicate weights over DP")
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient-accumulation micro-batches (train)")
    ap.add_argument("--tag", default="", help="result filename suffix")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    out_dir = Path(args.out)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    cells = []
    if args.all:
        for name, cfg in ARCHS.items():
            for shp in shapes_for(cfg):
                cells.append((name, shp.name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    failures = []
    for multi in meshes:
        for arch, shp in cells:
            try:
                run_cell(arch, shp, multi, out_dir, args.remat, args.folded,
                         args.force, args.tag, args.pad_heads,
                         args.zero1_moe, args.serve_no_fsdp, args.accum)
            except Exception as e:
                mesh_tag = "2x16x16" if multi else "16x16"
                print(f"[FAIL] {mesh_tag} {arch} {shp}: {e}", flush=True)
                failures.append((mesh_tag, arch, shp, traceback.format_exc()))
    if failures:
        flog = out_dir / "failures.log"
        flog.parent.mkdir(parents=True, exist_ok=True)
        with open(flog, "a") as f:
            for mesh_tag, arch, shp, tb in failures:
                f.write(f"==== {mesh_tag} {arch} {shp}\n{tb}\n")
        print(f"{len(failures)} failures -> {flog}")
        raise SystemExit(1)
    print("dry-run complete")


if __name__ == "__main__":
    main()
