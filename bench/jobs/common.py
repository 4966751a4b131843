"""What every job shares: the compile cache, the seeded feed, the count
of compilations, the outcome of a run and its assembly into the result."""
from __future__ import annotations

import functools
import importlib
import json
import time
from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import Dict, List, Optional

import numpy as np

from bench import compare, peaks, spec, tracing


def enable_cache() -> str:
    """The program's compile cache (``JAX_COMPILATION_CACHE_DIR``, else
    ``.jax_cache/`` in the checkout), holding every program however quick
    its compile, so that a second run compiles nothing."""
    import jax
    from repro.launch.cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return where


class CompileCounter:
    """Counts programs lowered or compiled while it is open."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.names: List[str] = []
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    @property
    def count(self) -> int:
        return len(self.names)

    def _event(self, event, duration, **kw):
        if self.on and event in self.EVENTS:
            self.names.append(str(kw.get("fun_name", "?")))


class Feed:
    """Seeded rows of tokens, for the program's prefetching ``DataLoader``:
    row ``step`` of shard ``shard`` is the same for every run of a seed."""

    def __init__(self, seed: int, batch: int, seq_len: int, vocab: int,
                 shard: int = 0):
        self.seed, self.batch, self.seq_len = int(seed), batch, seq_len
        self.vocab, self.shard = vocab, shard
        self.data = SimpleNamespace(prefetch=2, delay_s=0.0)

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng([self.seed, int(step), self.shard, 17])
        x = rng.integers(0, self.vocab, (self.batch, self.seq_len + 1),
                         dtype=np.int32)
        return {"tokens": np.ascontiguousarray(x[:, :-1]),
                "labels": np.ascontiguousarray(x[:, 1:])}


def model_config(c: dict):
    """The program's configuration for ``c``: its registered architecture
    ``program_arch`` at the sizes and settings ``c`` states."""
    from repro.configs.registry import ARCHS
    base = ARCHS[c["program_arch"]]
    kinds = {"mlp": base.mlp, "norm": base.norm}
    if any(kinds[k] != c[k] for k in kinds) or base.family != "dense" \
            or c["o_proj_bias"] or base.local_global:
        raise ValueError(f"{c['name']}: the program's {c['program_arch']} "
                         f"is not the dense decoder the file states")
    return base.with_overrides(
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        norm_eps=c["norm_epsilon"], use_bias=c["mlp_bias"],
        qkv_bias=c["qkv_bias"],
        tie_embeddings=c["tie_word_embeddings"], rope_theta=c["rope_theta"],
        sliding_window=c["sliding_window"], dtype=c["dtype"],
        param_dtype=c["param_dtype"])


def check_layout(model, c: dict) -> None:
    """The program's parameter shapes are the harness's layout."""
    import jax
    from bench import weights as W
    prog = {k: tuple(v.shape) for k, v in W.flatten(
        jax.eval_shape(model.init, jax.random.PRNGKey(0))).items()}
    mine = W.layout(c)
    if prog != mine:
        raise ValueError(f"parameter layout differs: program {prog}, "
                         f"harness {mine}")


@functools.lru_cache(maxsize=8)
def _reference(config: str, lr_peak: float, warmup_steps: int, mm,
               q_block: int):
    """One compiled reference per configuration and precision: runs of a
    cell in one process share it."""
    from bench import reference as R
    return R.Reference(json.loads(config),
                       R.AdamW(lr_peak=lr_peak, warmup_steps=warmup_steps),
                       mm=mm, q_block=q_block)


def reference(cell, seed: int, shards: int = 1, mm=None) -> List[dict]:
    """What the comparison reads of the plain reference following the
    cell's first steps from ``seed``, on each of the first ``shards``
    shards of the feed."""
    from bench import reference as R
    tr, c = cell.traffic, cell.config
    ref = _reference(json.dumps(c, sort_keys=True), tr["lr_peak"],
                     tr["warmup_steps"], mm or R.mm_f32,
                     tr.get("reference_q_block", 1024))
    out = []
    for shard in range(shards):
        feed = Feed(seed, tr["batch"], tr["seq_len"], c["vocab_size"], shard)
        out.append(ref.follow(seed, [feed.batch_at(i)
                                     for i in range(tr["check_steps"])],
                              compare.slice_norms, compare.change_norms))
    return out


def setup_line(marks) -> str:
    """Seconds of each set-up phase, from (name, clock) marks."""
    parts = [f"{name} {b - a:.1f} s" for (_, a), (name, b)
             in zip(marks, marks[1:])]
    return "set-up: " + ", ".join(parts)


def memory_peak(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


@dataclass
class Outcome:
    """What a job measured in one run."""
    e2e: Dict[str, float]
    attempted: int
    failed: int
    numbers: Dict[str, float]
    memory_peak: int
    counters: Dict[str, object] = field(default_factory=dict)
    lines: List[str] = field(default_factory=list)


@dataclass
class Context:
    """What a per-layer metric reader is given."""
    cell: spec.Cell
    outcome: Outcome
    trace: Optional[tracing.Summary]
    peak: dict
    chips: int


def run(cell: spec.Cell, devs, seed: int, seconds: float, trace: bool,
        t0: float) -> dict:
    job = importlib.import_module(f"bench.jobs.{cell.traffic['job']}")
    out: Outcome = job.run(cell, devs, seed, seconds, trace, t0)
    ok, checks = compare.check(out.numbers, cell.limits)
    kind = devs[0].device_kind
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": out.memory_peak}
    result = {"correct": bool(ok and out.failed == 0),
              "attempted": int(out.attempted), "failed": int(out.failed)}
    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            v = out.e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        t_read = time.perf_counter()
        summ = tracing.summarize(tracing.load(), chips=len(devs))
        ctx = Context(cell, out, summ, peaks.peak(kind), len(devs))
        for m in cell.per_layer:
            v = spec.reader(m["name"], cell.root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=summ.busy_s, window_s=summ.window_s)
        result["breakdown"] = summ.breakdown()
        tracing.remove()
        out.lines.append(f"trace read in {time.perf_counter() - t_read:.1f} s")
    result.update(metrics=metrics, device=device, checks=checks)
    spare = {k: v for k, v in out.numbers.items() if k not in cell.limits}
    if spare:
        out.lines.append(f"read, not compared: {spare}")
    result["_lines"] = out.lines + compare.lines(checks)
    return result
