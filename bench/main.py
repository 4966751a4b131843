#!/usr/bin/env python3
"""Runs one cell of the benchmark once.

  python3 bench/main.py --workload <cell> --seed <n> --seconds <s> \
      --trace <0|1>

The cell, its configuration, traffic and limits are found by name
(``bench/spec.py``).  With ``--trace 0`` the result carries the cell's
end-to-end metrics; with ``--trace 1`` the window is traced and the result
carries its per-layer metrics, the device's busy time and a breakdown.
The last line of standard output is one JSON object; the numbers that
decide ``correct`` are the last lines of standard error and the last key
of that object.  Exits non-zero and prints no result when jax finds no TPU
or fewer chips than the cell needs.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()      # set-up is counted from here

import argparse               # noqa: E402
import contextlib             # noqa: E402
import json                   # noqa: E402
import sys                    # noqa: E402
from pathlib import Path      # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def devices_for(chips: int):
    """The chips the cell runs on, or None (with the reason on standard
    error) when jax finds no TPU or too few."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"no TPU: jax found {devs[0].platform} devices; the "
              "benchmark runs on the chip only", file=sys.stderr)
        return None
    if len(devs) < chips:
        print(f"the cell needs {chips} chips; jax found {len(devs)}",
              file=sys.stderr)
        return None
    return devs[:chips]


def main(argv=None) -> int:
    args = parse(argv)
    from bench import spec
    cell = spec.cell(args.workload)
    from bench.jobs import common
    common.enable_cache()
    devs = devices_for(cell.chips)
    if devs is None:
        return 2
    with contextlib.redirect_stdout(sys.stderr):
        result = common.run(cell, devs, args.seed, args.seconds,
                            bool(args.trace), t0=T0)
    for line in result.pop("_lines"):
        print(line, file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
