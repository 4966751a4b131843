"""Cells of the benchmark at a size the CPU runs in seconds: the same
jobs, configurations and traffic files, with the widths, depth, batch
and sequence cut down and float32 numerics."""
from __future__ import annotations

import json
import time

from bench import spec

TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, intermediate_size=256, vocab_size=512,
            dtype="float32", param_dtype="float32")


def cell(name: str) -> spec.Cell:
    real = spec.cell(name)
    c = dict(real.config, **TINY)
    c["num_hidden_layers"] = min(2, c["num_hidden_layers"])
    tr = dict(real.traffic, batch=2, seq_len=64, reference_q_block=32)
    if tr["job"] == "fleet":
        tr.update(workers=2, min_incidents=2, iters_per_window=2,
                  lead_windows=1, cycle_windows=3, fault_windows=2)
    return spec.Cell(name=name, chips=1, config=c, traffic=tr,
                     limits=real.limits, end_to_end=real.end_to_end,
                     per_layer=real.per_layer)


def run(c: spec.Cell, seed: int = 2 ** 31 + 11, seconds: float = 0.5):
    """One run of the cell on the CPU, as ``bench/main.py`` makes it on the
    chip, past its look for a chip."""
    import jax
    from bench.jobs import common
    out = common.run(c, jax.devices()[:1], seed, seconds, False,
                     t0=time.perf_counter())
    out.pop("_lines")
    json.dumps(out)
    return out
