#!/usr/bin/env python3
"""Readings that the limits of a training cell's numbers are set from.

  python3 bench/calibrate.py --workload <name> --seeds 12 --faults 3

In one process on the chip, at the cell's own sizes: the program's first
steps from each of ``--seeds`` seeds through the cell's own job, each
compared with the plain reference (the lower readings); the fp8 control in
the program's place and the program with half of each batch left out,
each on ``--faults`` of those seeds (the upper readings).  A step that
returns its state unchanged reads 1 on ``grad_gap``, ``change_gap`` and
``change_median_gap`` (half of the slices read 1 and the rest under it)
by construction and needs no run.
Where the cell compares the summarize step, whole runs of the cell with
its control (the plain reference on rows rounded to bfloat16) in the
program's place, on ``--control-runs`` seeds (``--faults`` by default),
give that step's upper readings through the cell's own check; its lower
readings are those of the cell's ordinary runs.  Prints one JSON object of
all readings.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def _over_median(grad):
    """Each slice's reference gradient over the median slice's."""
    import numpy as np
    med = float(np.median(np.concatenate([np.ravel(v)
                                          for v in grad.values()])))
    return {k: [float(x) / med for x in np.ravel(v)]
            for k, v in grad.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 1000)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window of the control runs (run_seconds)")
    ap.add_argument("--control-runs", type=int, default=None,
                    help="runs with the summarize control (--faults)")
    args = ap.parse_args(argv)
    import contextlib
    import importlib
    from bench import compare, faults, spec
    from bench import reference as R
    from bench.jobs import common
    from bench.main import devices_for
    cell = spec.cell(args.workload)
    common.enable_cache()
    devs = devices_for(cell.chips)
    if devs is None:
        return 2
    kind = importlib.import_module(f"bench.jobs.{cell.traffic['job']}")
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    summary = {}
    if "summarize_moment_gap" in cell.limits:
        seconds = args.seconds or spec.benchmark()["run_seconds"]
        with faults.summary_control(), \
                contextlib.redirect_stdout(sys.stderr):
            runs = args.faults if args.control_runs is None \
                else args.control_runs
            for s in seeds[:runs]:
                out = common.run(cell, devs, s, seconds, False,
                                 t0=time.perf_counter())
                summary[s] = {k: c["value"] for k, c in out["checks"].items()
                              if k.startswith("summarize")}
                print(f"summarize control, seed {s}: {summary[s]}",
                      file=sys.stderr)
    job = kind.Job(cell)
    as_list = (lambda x: x if isinstance(x, list) else [x])
    prog, half = {}, {}
    t0 = time.perf_counter()
    for s in seeds:
        job.load(s)
        prog[s] = as_list(job.check_steps())
    with faults.half_batch():
        for s in seeds[:args.faults]:
            job.load(s)
            half[s] = as_list(job.check_steps())
    job.close()
    t_prog = time.perf_counter() - t0

    def numbers(got_by_shard, s, mm=None):
        if mm is not None:
            got_by_shard = common.reference(cell, s, len(got_by_shard), mm)
        out = {}
        for got, want in zip(got_by_shard, refs[s]):
            for k, v in compare.training_numbers(got, want).items():
                out[k] = max(out.get(k, 0.0), v)
        return out

    def worst(got_by_shard, s, n=4):
        """The slices of widest gap, each with its gap, on each shard."""
        out = []
        for got, want in zip(got_by_shard, refs[s]):
            row = {}
            for k, keep in (("grad", None),
                            ("change", compare.moving(want["grad"]))):
                gaps = compare.slice_gaps(got[k], want[k], keep)
                row[k] = sorted(((g, name) for name, g in gaps.items()),
                                reverse=True)[:n]
            out.append(row)
        return out

    t0 = time.perf_counter()
    refs = {s: common.reference(cell, s, len(prog[s])) for s in seeds}
    t_ref = (time.perf_counter() - t0) / len(seeds)
    result = {"workload": cell.name, "seeds": seeds,
              "program": {s: numbers(prog[s], s) for s in seeds},
              "half_batch": {s: numbers(half[s], s) for s in half},
              "control_fp8": {s: numbers(prog[s], s, mm=R.mm_fp8)
                              for s in seeds[:args.faults]},
              "frozen_step": {"grad_gap": 1.0, "change_gap": 1.0,
                              "change_median_gap": 1.0},
              "summarize_control_bf16": summary,
              "losses": {s: [g["loss"] for g in prog[s]] for s in seeds},
              "widest_slices": {s: worst(prog[s], s) for s in seeds},
              "grad_over_median": {s: _over_median(refs[s][0]["grad"])
                                   for s in seeds[:1]},
              "program_s": t_prog, "reference_s_per_seed": t_ref}
    for kind in ("program", "half_batch", "control_fp8"):
        rows = result[kind]
        for k in ("loss_gap", "grad_gap", "change_gap",
                  "change_median_gap"):
            vals = [r[k] for r in rows.values()]
            print(f"{kind:12s} {k:11s} min {min(vals):.4g} "
                  f"max {max(vals):.4g}", file=sys.stderr)
    print(json.dumps(result, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
