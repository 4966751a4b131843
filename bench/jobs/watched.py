"""One training job watched by PerfTracker, as ``repro.launch.train`` runs
it: ``Trainer.run`` with the fused step under the tracer's fenced
``train.step`` phase, the wrapped loader, the tracker's windows with their
in-line diagnosis, and ``_maybe_mitigate`` after every step.

Set-up builds the trainer once, gives it the seeded weights and feed, and
drives its first ``check_steps`` steps through ``Trainer.run`` itself,
keeping what the comparison with the reference reads.  Two timed calls of
a fixed number of warm steps follow, and one call of the summarize kernel
at the shape the tracker's diagnosis pads its rows to, so that nothing the
window runs compiles there.  The window then runs the steps that fill
``--seconds`` at the faster call's step time (the tracker's first
profiling window, with its fenced steps, falls in set-up), from the same
trainer and state.
``Trainer.run`` cannot be bounded by time, so the harness hands it the
warmed state through ``init_state`` and times from there to the end of its
last step on the device.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import compare, tracing
from bench import weights as W
from bench.jobs import common


def _trainer_class():
    from repro.train.loop import Trainer

    class Handover(Trainer):
        """``Trainer.run`` starting from the state the harness hands it."""
        handover = None
        started = 0.0

        def init_state(self, resume: bool = True):
            params, opt_state, start = self.handover
            self.handover = None
            self.started = time.perf_counter()
            return params, opt_state, start

    return Handover


class Job:
    """The watched trainer of a cell, built once."""

    def __init__(self, cell):
        from repro.data.pipeline import DataConfig
        from repro.optim.adamw import OptConfig
        from repro.train.loop import TrainConfig
        self.c, self.tr = c, tr = cell.config, cell.traffic
        self.B, self.S = tr["batch"], tr["seq_len"]
        self.oc = OptConfig(lr_peak=tr["lr_peak"],
                            warmup_steps=tr["warmup_steps"],
                            total_steps=tr["total_steps"])
        self.trainer = _trainer_class()(
            common.model_config(c), DataConfig(batch=self.B, seq_len=self.S),
            self.oc,
            TrainConfig(steps=1, log_every=10 ** 9, remat=tr["remat"],
                        perftracker=True, pt_window_s=tr["window_s"]))
        common.check_layout(self.trainer.model, c)
        self.trainer.loader.close()
        self.state = None
        # count the tracker's diagnoses (profiling windows it closed)
        pt = self.trainer.pt
        finish = pt._finish_window
        self.diagnoses = 0

        def counted():
            self.diagnoses += 1
            return finish()
        pt._finish_window = counted

    def load(self, seed: int) -> None:
        """The seeded weights, fresh optimizer state and the seed's feed."""
        import jax
        self.state = None
        gc.collect()
        self.seed, self.step = seed, 0
        self.feed = common.Feed(seed, self.B, self.S, self.c["vocab_size"])
        params = W.make(self.c, seed)
        self.state = (params, jax.jit(self.trainer.opt.init)(params))

    def run(self, steps: int) -> float:
        """``steps`` steps through ``Trainer.run`` from the current state;
        returns the seconds from the handover to the end of the last step
        on the device (outside the tracker's windows the steps run
        ahead of the host)."""
        import jax
        from repro.data.pipeline import DataLoader
        t = self.trainer
        t.source = self.feed
        t.loader = DataLoader(self.feed, start_step=self.step)
        t._next, t._opt_anchor = t.pt.wrap(t.loader.next, lambda: None)
        t.handover = (*self.state, self.step)
        self.state = jax.block_until_ready(t.run(steps=steps))
        self.end = time.perf_counter()
        self.step += steps
        return self.end - t.started

    def check_steps(self) -> dict:
        """The first steps from the seed, one ``Trainer.run`` each, and
        what the comparison reads of them."""
        got = {"loss": []}
        for k in range(self.tr["check_steps"]):
            self.run(1)
            got["loss"].append(float(self.trainer.history[-1]["loss"]))
            if k == 0:
                # AdamW's first moment after one step is (1 - b1) g
                m = compare.slice_norms(W.flatten(self.state[1]["m"]))
                got["grad"] = {p: v / (1.0 - self.oc.b1)
                               for p, v in m.items()}
        init = W.make(self.c, self.seed)
        got["change"] = compare.change_norms(
            W.flatten(self.state[1]["master"]), W.flatten(init))
        return got

    def close(self) -> None:
        self.state = self.trainer = None
        gc.collect()


def run(cell, devs, seed, seconds, trace, t0):
    marks = [("start", t0)]
    job = Job(cell)
    marks.append(("build", time.perf_counter()))
    job.load(seed)
    marks.append(("weights", time.perf_counter()))
    prog = job.check_steps()
    marks.append(("check steps", time.perf_counter()))
    tr = cell.traffic
    step_s = min(job.run(tr["warm_steps"]) / tr["warm_steps"]
                 for _ in range(2))
    from repro.summarize import get_backend
    rows = np.random.default_rng(seed).random(tr["summarize_shape"])
    get_backend().batch_stats(rows.astype(np.float32))
    marks.append(("warm-up", time.perf_counter()))
    setup_diagnoses = job.diagnoses
    n = max(1, round(seconds / step_s))
    counter = common.CompileCounter()
    counter.on = True
    with tracing.capture(trace):
        with tracing.annotate(tracing.WINDOW):
            elapsed = job.run(n)
    counter.on = False
    t_start = job.trainer.started
    peak = common.memory_peak(devs)
    B, S = job.B, job.S
    tokens = n * B * S
    e2e = {"tokens_per_s": tokens / elapsed, "setup_s": t_start - t0}
    diagnoses = job.diagnoses - setup_diagnoses
    shapes = sorted(get_backend("pallas").shapes)
    job.close()

    t_ref = time.perf_counter()
    want, = common.reference(cell, seed)
    numbers = compare.training_numbers(prog, want)
    lines = [common.setup_line(marks),
             f"window: {n} steps of {B}x{S} in {elapsed:.3f} s "
             f"(step {step_s:.4f} s at warm-up), "
             f"{counter.count} programs compiled in the window "
             f"{counter.names}; tracker diagnoses: "
             f"{setup_diagnoses} in set-up, {diagnoses} in the window; "
             f"summarize kernel shapes {shapes}",
             f"losses program {prog['loss']} reference {want['loss']}",
             f"reference followed {cell.traffic['check_steps']} steps in "
             f"{time.perf_counter() - t_ref:.1f} s"]
    return common.Outcome(
        e2e=e2e, attempted=n, failed=0, numbers=numbers, memory_peak=peak,
        counters={"seq_len": S}, lines=lines)
