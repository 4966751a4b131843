"""A fleet of trainers under EROICA's online diagnosis, with a seeded fault
cycle: ``ScenarioRunner`` in-process over a ``TrainerWorkload`` (every
worker a real ``Trainer`` running its split, fenced step with the tracer
recording each phase), the pipeline summarizing each window on the chip,
localizing, and attaching a ranked mitigation ladder to each incident.

Set-up builds the fleet once (one compiled step bundle shared by every
worker), gives every worker the seeded weights and its own shard of the
seeded feed, and drives each worker's first ``check_steps`` steps through
the worker's own step call, keeping what the comparison reads.  One fault
cycle through the pipeline warms every program the window runs and times a
cycle.  The window is then a whole number of rounds of cycles, each round
faulting every worker once in an order drawn from the seed.

``diagnosis_s`` is the mean over the window's incidents of the time from
the start of the faulted worker's first iteration with the fault to the
return of the first window tick whose report names ``dataloader.next`` on
exactly that worker.  An incident counts in ``failed`` when no report
names it so while the fault lasts, or when the pipeline's incident for it
does not put ``migrate_dataloader`` among its plans.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import numpy as np

from bench import compare, faultcycle, summary_ref, tracing
from bench import weights as W
from bench.jobs import common

FUNCTION = "dataloader.next"


class _Recorder:
    """Host clocks around the layers the pipeline calls, and the rows the
    summarize kernel was given, by wrapping the instances' methods."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.window_start: Dict[int, Dict[int, float]] = {}
        self.tick_end: Dict[int, float] = {}
        self.localize: List[float] = []
        self.summarize: List[tuple] = []
        self._window = -1
        self._loc_t0 = 0.0

    def span(self, fn, name):
        return tracing.wrap(fn, name) if self.traced else fn

    def install(self, wl, runner, backend):
        run_window = wl.run_window

        def window(i, faults, iters, rates):
            self._window = i
            self.window_start[i] = {}
            return run_window(i, faults, iters, rates)
        wl.run_window = self.span(window, "bench.workload.run_window")
        for tw in wl.workers:
            self._worker(tw)
        if self.traced:
            b = wl.workers[0].trainer.bundle
            b.grad_step = tracing.wrap(b.grad_step, "bench.step.grad")
            b.opt_step = tracing.wrap(b.opt_step, "bench.step.opt")
        pipe = runner.pipeline
        tick = self.span(pipe.window_tick, "bench.pipeline.window_tick")

        def window_tick(*a, **kw):
            rep = tick(*a, **kw)
            self.tick_end[rep.index] = time.perf_counter()
            return rep
        pipe.window_tick = window_tick
        loc = pipe.service.localizer
        localize = self.span(loc.localize, "bench.localize")

        def timed_localize(*a, **kw):
            self._loc_t0 = time.perf_counter()
            return localize(*a, **kw)
        loc.localize = timed_localize
        on_window = self.span(pipe.incidents.on_window, "bench.plan")

        def timed_on_window(*a, **kw):
            out = on_window(*a, **kw)
            self.localize.append(time.perf_counter() - self._loc_t0)
            return out
        pipe.incidents.on_window = timed_on_window
        batch_stats = self.span(backend.batch_stats, "bench.summarize")

        def recorded(u):
            out = batch_stats(u)
            self.summarize.append((np.array(u, copy=True),
                                   np.array(out, copy=True)))
            return out
        backend.batch_stats = recorded

    def _worker(self, tw):
        run_window = tw.run_window

        def window(iters, rate=None):
            self.window_start[self._window][tw.worker] = time.perf_counter()
            return run_window(iters, rate=rate)
        tw.run_window = window
        if self.traced:
            t = tw.trainer
            t.loader.next = tracing.wrap(t.loader.next, "bench.feed.next")

    def restore(self, wl, runner, backend):
        pipe = runner.pipeline
        for obj in [wl, pipe, pipe.service.localizer, pipe.incidents,
                    backend] + list(wl.workers):
            for k in ("run_window", "window_tick", "localize", "on_window",
                      "batch_stats"):
                obj.__dict__.pop(k, None)


def _runner(wl, tr, incidents, n_windows):
    from repro.online import ScenarioRunner, ScheduledFault
    from repro.train.workload import (DataloaderBurn,
                                      default_trainer_detector_cfg)
    ipw = tr["iters_per_window"]
    sched = [ScheduledFault(DataloaderBurn(workers=(i.worker,),
                                           factor=tr["burn_factor"]),
                            i.start_window, i.end_window)
             for i in incidents]
    return ScenarioRunner(
        None, sched, n_windows=n_windows, iters_per_window=ipw,
        detector_cfg=default_trainer_detector_cfg(ipw), workload=wl,
        summarize_backend=None)


def _named(rep, worker: int) -> bool:
    return any(d.abnormality.function == FUNCTION
               and list(d.abnormality.workers) == [worker]
               for d in rep.diagnoses)


def _planned(incidents, span, worker: int) -> bool:
    """An incident of the loader, live at some pipeline time within
    ``span`` (the fault's windows) and holding ``worker``, puts
    ``migrate_dataloader`` among its plans."""
    from repro.core.mitigation import Action
    t0, t1 = span
    return any(inc.function == FUNCTION and worker in inc.workers_seen
               and inc.opened_at <= t1
               and (inc.resolved_at is None or inc.resolved_at >= t0)
               and Action.MIGRATE_DATALOADER in [p.action for p in inc.plans]
               for inc in incidents)


def account(incidents, reports, pipeline_incidents, window_start,
            tick_end):
    """Per incident: (diagnosis seconds or None, windows to the naming
    report or None).  An incident counts only when a report while its
    fault lasts names it on its worker alone, and a pipeline incident of
    the loader live while the fault lasts plans ``migrate_dataloader`` for
    that worker."""
    out = []
    for inc in incidents:
        during = [r for r in reports
                  if inc.start_window <= r.index < inc.end_window]
        hit = next((r for r in during if _named(r, inc.worker)), None)
        if hit is None or not _planned(pipeline_incidents,
                                       (during[0].t, during[-1].t),
                                       inc.worker):
            out.append((None, None))
            continue
        onset = window_start[inc.start_window][inc.worker]
        out.append((tick_end[hit.index] - onset,
                    hit.index - inc.start_window + 1))
    return out


def tally(acc):
    """(mean diagnosis seconds over the named incidents or None, incidents
    failed, windows to each naming)."""
    named = [a for a in acc if a[0] is not None]
    mean = float(np.mean([a[0] for a in named])) if named else None
    return mean, len(acc) - len(named), [a[1] for a in named]


class Job:
    """The fleet of a cell, built once: every worker a ``Trainer`` sharing
    one compiled step bundle."""

    def __init__(self, cell):
        from repro.data.pipeline import DataConfig
        from repro.optim.adamw import OptConfig
        from repro.train.loop import TrainConfig
        from repro.train.workload import TrainerWorkload
        self.c, self.tr = c, tr = cell.config, cell.traffic
        self.B, self.S, self.nw = tr["batch"], tr["seq_len"], tr["workers"]
        self.oc = OptConfig(lr_peak=tr["lr_peak"],
                            warmup_steps=tr["warmup_steps"],
                            total_steps=tr["total_steps"])
        self.wl = TrainerWorkload(
            n_workers=self.nw, rate_hz=tr["sample_rate_hz"],
            setup=(common.model_config(c),
                   DataConfig(batch=self.B, seq_len=self.S), self.oc,
                   TrainConfig(log_every=10 ** 9, perftracker=False,
                               remat=tr["remat"])))
        self.wl._ensure_workers()
        common.check_layout(self.wl.workers[0].trainer.model, c)

    def load(self, seed: int) -> None:
        """Every worker: the seeded weights, fresh optimizer state and its
        own shard of the seed's feed."""
        import jax
        from repro.data.pipeline import DataLoader
        self.seed = seed
        for tw in self.wl.workers:
            tw.trainer.loader.close()
            tw.params = tw.opt_state = None
            gc.collect()
            feed = common.Feed(seed, self.B, self.S, self.c["vocab_size"],
                               shard=tw.worker)
            tw.trainer.source = feed
            tw.trainer.loader = DataLoader(feed)
            tw.params = W.make(self.c, seed)
            tw.opt_state = jax.jit(tw.trainer.opt.init)(tw.params)
            tw.trainer._iter = 0

    def check_steps(self) -> List[dict]:
        """Each worker's first steps from the seed, through the worker's
        own step call, and what the comparison reads of them."""
        init = W.flatten(W.make(self.c, self.seed))
        out = []
        for tw in self.wl.workers:
            got = {"loss": []}
            for k in range(self.tr["check_steps"]):
                tw.step()
                got["loss"].append(float(tw.last_metrics["loss"]))
                if k == 0:
                    m = compare.slice_norms(W.flatten(tw.opt_state["m"]))
                    got["grad"] = {p: v / (1.0 - self.oc.b1)
                                   for p, v in m.items()}
            got["change"] = compare.change_norms(
                W.flatten(tw.opt_state["master"]), init)
            out.append(got)
        return out

    def close(self) -> None:
        self.wl.close()
        self.wl = None
        gc.collect()


def run(cell, devs, seed, seconds, trace, t0):
    from repro.summarize import get_backend
    tr = cell.traffic
    marks = [("start", t0)]
    job = Job(cell)
    marks.append(("build", time.perf_counter()))
    job.load(seed)
    marks.append(("weights", time.perf_counter()))
    prog = job.check_steps()
    marks.append(("check steps", time.perf_counter()))
    wl, nw, B, S = job.wl, job.nw, job.B, job.S
    ipw, lead, cyc, fw = (tr["iters_per_window"], tr["lead_windows"],
                          tr["cycle_windows"], tr["fault_windows"])
    backend = get_backend()
    # one cycle warms every program the window runs, and times a cycle
    warm = faultcycle.schedule(seed, 1, nw, lead, cyc, fw)
    wr = _runner(wl, tr, warm, lead + cyc)
    tw0 = time.perf_counter()
    wr.run()
    cycle_s = (time.perf_counter() - tw0) / (lead + cyc) * cyc
    marks.append(("warm-up cycle", time.perf_counter()))
    cycles = faultcycle.cycle_count(seconds, cycle_s, nw,
                                    tr["min_incidents"])
    incidents = faultcycle.schedule(seed, cycles, nw, lead, cyc, fw)
    n_windows = lead + cycles * cyc
    runner = _runner(wl, tr, incidents, n_windows)
    rec = _Recorder(trace)
    rec.install(wl, runner, backend)
    counter = common.CompileCounter()
    counter.on = True
    with tracing.capture(trace):
        with tracing.annotate(tracing.WINDOW):
            t_start = time.perf_counter()
            res = runner.run()
            t_end = time.perf_counter()
    counter.on = False
    peak = common.memory_peak(devs)
    rec.restore(wl, runner, backend)
    reports = res.reports
    acc = account(incidents, reports, res.incidents, rec.window_start,
                  rec.tick_end)
    mean, failed, windows = tally(acc)
    tokens = nw * n_windows * ipw * B * S
    e2e = {"fleet_tokens_per_s": tokens / (t_end - t_start),
           "setup_s": t_start - t0}
    if mean is not None:
        e2e["diagnosis_s"] = mean
    shapes = [u.shape for u, _ in rec.summarize]
    ticks = len(res.reports)
    res_incidents = res.incidents
    del wl, runner, wr, res
    job.close()

    t_ref = time.perf_counter()
    numbers: Dict[str, float] = {}
    for got, want in zip(prog, common.reference(cell, seed, len(prog))):
        for k, v in compare.training_numbers(got, want).items():
            numbers[k] = max(numbers.get(k, 0.0), v)
    moment_gap, count_miss = 0.0, 0
    for u, got in rec.summarize:
        m, miss = summary_ref.gaps(u, got)
        moment_gap, count_miss = max(moment_gap, m), count_miss + miss
    numbers.update(summarize_moment_gap=moment_gap,
                   summarize_count_mismatch=float(count_miss))
    lines = [common.setup_line(marks),
             f"window: {cycles} incidents over {n_windows} windows of "
             f"{nw} workers x {ipw} iterations in {t_end - t_start:.3f} s "
             f"(cycle {cycle_s:.3f} s at warm-up), {counter.count} programs "
             f"compiled in the window {counter.names}",
             "incidents (worker, start window, seconds, windows): "
             + str([(i.worker, i.start_window,
                     None if a[0] is None else round(a[0], 4), a[1])
                    for i, a in zip(incidents, acc)]),
             f"reference followed {len(prog)} workers x "
             f"{tr['check_steps']} steps and {len(shapes)} summarize calls "
             f"({sum(u.shape[0] for u, _ in rec.summarize)} rows) in "
             f"{time.perf_counter() - t_ref:.1f} s"]
    if failed:
        named_on = [(r.index, round(r.t, 2),
                     [d.abnormality.workers.tolist() for d in r.diagnoses
                      if d.abnormality.function == FUNCTION])
                    for r in reports]
        lines.append(f"loader named on, by window (index, pipeline "
                     f"time, workers): {named_on}")
        lines.append("loader incidents (opened, resolved, workers, plans): "
                     + str([(round(i.opened_at, 2), i.resolved_at and
                             round(i.resolved_at, 2), i.workers_seen,
                             [p.action.value for p in i.plans])
                            for i in res_incidents
                            if i.function == FUNCTION]))
    return common.Outcome(
        e2e=e2e, attempted=len(incidents), failed=failed, numbers=numbers,
        memory_peak=peak,
        counters={"seq_len": S, "ticks": ticks, "summarize_shapes": shapes,
                  "localize_s": rec.localize,
                  "diagnosis_windows": windows},
        lines=lines)
