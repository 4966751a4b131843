"""The program's own measurements on the profiler's clock: named scopes in
both step programs, host spans at the layer boundaries, and the timing
fields filled from those spans."""
import glob
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS, reduced

PHASES = ("fwd", "recompute", "bwd", "opt")


def _phases_of(op_name: str):
    """Every phase whose rule an operation's scope path meets; a sound
    split gives exactly one."""
    rules = {
        "fwd": "jvp(fwd)" in op_name and "transpose(" not in op_name,
        "recompute": "transpose(jvp(fwd))" in op_name
        and "rematted_computation" in op_name,
        "bwd": "transpose(jvp(fwd))" in op_name
        and "rematted_computation" not in op_name,
        "opt": "/opt/" in op_name,
    }
    return [p for p in PHASES if rules[p]]


def _matmul_op_names(hlo_text: str):
    return re.findall(r" (?:dot|convolution)\([^\n]*?op_name=\"([^\"]*)\"",
                      hlo_text)


@pytest.fixture(scope="module")
def tiny_step():
    from repro.models.transformer import Transformer
    from repro.optim.adamw import AdamW, OptConfig
    cfg = reduced(ARCHS["starcoder2-3b"], layers=2, d_model=64, vocab=256)
    model = Transformer(cfg, remat="full")
    opt = AdamW(OptConfig())
    params = model.init(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
             "labels": jnp.zeros((2, 16), jnp.int32)}
    return model, opt, params, opt.init(params), batch


def test_step_scopes_split_every_matmul(tiny_step):
    """Compiled with ``remat`` full, every matmul of the fused step and of
    both split halves falls in exactly one of forward, recompute,
    backward and optimizer, and the fused step has all three of the
    first; the optimizer's operations carry its scope."""
    from repro.train.step import make_split_train_step, make_train_step
    model, opt, params, opt_state, batch = tiny_step
    fused = jax.jit(make_train_step(model, opt)).lower(
        params, opt_state, batch).compile().as_text()
    grad_fn, opt_fn = make_split_train_step(model, opt)
    grads = jax.eval_shape(grad_fn, params, batch)[0]
    halves = [jax.jit(grad_fn).lower(params, batch).compile().as_text(),
              jax.jit(opt_fn).lower(grads, opt_state,
                                    params).compile().as_text()]
    seen = set()
    for text in [fused] + halves:
        for name in _matmul_op_names(text):
            got = _phases_of(name)
            assert len(got) == 1, (name, got)
            seen.add(got[0])
    assert {"fwd", "recompute", "bwd"} <= seen
    for text in (fused, halves[1]):
        assert re.search(r'op_name="jit\([a-z_]+\)/opt/', text)


#: the attention core's einsums (``repro.models.attention_core``): scores
#: (and the backward's dp), probabilities by values (and dq), and the
#: backward's dk and dv
CORE_EINSUMS = ("btkgd,bskd->btkgs", "btkgs,bskd->btkgd", "btkgs,btkgd->bskd")


def test_attention_core_carries_its_scope_in_every_phase(tiny_step):
    """Every matmul of the attention core carries an ``attn`` component in
    its scope path, in the forward, the recompute and the backward, and
    still falls in exactly one phase; no other matmul carries it."""
    from repro.train.step import make_train_step
    model, opt, params, opt_state, batch = tiny_step
    fused = jax.jit(make_train_step(model, opt)).lower(
        params, opt_state, batch).compile().as_text()
    seen = set()
    for name in _matmul_op_names(fused):
        core = any(f"/{e}/" in name for e in CORE_EINSUMS)
        assert ("attn" in name.split("/")) == core, name
        if core:
            got = _phases_of(name)
            assert len(got) == 1, (name, got)
            seen.add(got[0])
    assert seen == {"fwd", "recompute", "bwd"}


def test_cached_step_keeps_its_own_scopes(tmp_path):
    """With the program's compile cache, a step compiled under named scopes
    is not loaded from the entry of the same computation compiled without
    them, whose scope paths it would then carry into a profile.  Checked
    in a fresh interpreter, which has not yet opened a compile cache."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.launch.cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

        def make(scoped):
            def step(w, x):
                def loss(w):
                    y = jnp.sum(jnp.tanh(x @ w) ** 2)
                    return y
                if scoped:
                    inner = loss
                    def loss(w):
                        with jax.named_scope("fwd"):
                            return inner(w)
                return jax.grad(loss)(w)
            return step
        w, x = jnp.ones((32, 32)), jnp.ones((4, 32))
        jax.jit(make(False)).lower(w, x).compile()
        exe = jax.jit(make(True)).lower(w, x).compile()
        text = exe.runtime_executable().hlo_modules()[0].to_string()
        assert "jvp(fwd)" in text
        print("OK")
    """)
    src = str(Path(__file__).resolve().parent.parent / "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       env={**os.environ, "PYTHONPATH": src,
                            "JAX_PLATFORMS": "cpu",
                            "JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert r.returncode == 0 and "OK" in r.stdout, r.stdout + r.stderr[-2000:]


# -- host spans in a CPU capture ----------------------------------------------

class _Span:
    def __init__(self, e, line):
        self.name, self.line = e.name, line
        self.start, self.end = float(e.start_ns), float(e.end_ns)
        self.stats = dict(e.stats)

    def inside(self, other) -> bool:
        return (self.line == other.line and other.start <= self.start
                and self.end <= other.end and self is not other)


def _capture(directory, fn):
    """Runs ``fn`` under a profiler session; returns its result and the
    ``repro.`` host spans of the trace."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(directory), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    f, = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                   recursive=True)
    spans = [_Span(e, (plane.name, line.name))
             for plane in ProfileData.from_file(f).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("repro.")]
    return out, spans


def _named(spans, name):
    return [s for s in spans if s.name == name]


def _holder(span, spans, name):
    """The span ``name`` that holds ``span``."""
    out = [s for s in _named(spans, name) if span.inside(s)]
    assert len(out) == 1, (span.name, name, len(out))
    return out[0]


def test_fleet_window_spans_nest(tmp_path):
    """One window of a two-worker ``TrainerWorkload`` under the scenario
    runner: the window's spans nest as the layers call each other, carry
    their worker and window, and the report's timing fields are the
    elapsed times of their spans."""
    from repro.online import ScenarioRunner
    from repro.train.workload import (TrainerWorkload,
                                      default_trainer_detector_cfg,
                                      tiny_train_setup)
    wl = TrainerWorkload(n_workers=2, setup=tiny_train_setup(),
                         warmup_iters=2)
    wl._ensure_workers()              # compile outside the capture
    runner = ScenarioRunner(None, [], n_windows=1, iters_per_window=2,
                            detector_cfg=default_trainer_detector_cfg(2),
                            workload=wl)
    try:
        res, spans = _capture(tmp_path, runner.run)
    finally:
        wl.close()
    win, = _named(spans, "repro.scenario.window")
    assert win.stats["window"] == 0
    work, = _named(spans, "repro.workload.window")
    assert work.inside(win) and work.stats["window"] == 0
    anchors, = _named(spans, "repro.pipeline.anchors")
    assert anchors.inside(win)
    workers = _named(spans, "repro.worker.window")
    assert sorted(s.stats["worker"] for s in workers) == [0, 1]
    for w in workers:
        assert w.inside(work)
        stop = [s for s in _named(spans, "repro.tracer.stop")
                if s.inside(w)]
        assert len(stop) == 1 and stop[0].stats["worker"] == w.stats[
            "worker"]
        pack, = [s for s in _named(spans, "repro.tracer.pack")
                 if s.inside(stop[0])]
    iters = _named(spans, "repro.train.iteration")
    assert len(iters) == 4
    for it in iters:
        w = _holder(it, spans, "repro.worker.window")
        assert it.stats["worker"] == w.stats["worker"]
    for phase in ("loader", "grad", "fence", "opt"):
        got = _named(spans, f"repro.train.{phase}")
        assert len(got) == 4, phase
        for s in got:
            _holder(s, spans, "repro.train.iteration")
    tick, = _named(spans, "repro.pipeline.tick")
    assert tick.inside(win)
    for stage in ("summarize", "ema", "localize", "incidents"):
        s, = _named(spans, f"repro.pipeline.{stage}")
        assert s.inside(tick), stage
    rep, = res.reports
    for field, stage in (("summarize_s", "summarize"),
                         ("localize_s", "localize")):
        s, = _named(spans, f"repro.pipeline.{stage}")
        assert getattr(rep, field) == pytest.approx(
            (s.end - s.start) / 1e9, abs=2e-4), field


def test_tracker_window_spans_and_counters(tmp_path, monkeypatch):
    """A tracker's profiling window is one span from its trigger to the
    end of its in-line diagnosis, the diagnosis's stages are spans inside
    it, its timing is read from them, and the tracker's own state counts
    the trigger (the detector's ``triggers``) and the diagnosis
    (``results``)."""
    from repro.core.detector import Trigger
    from repro.instrument import hooks
    pt = hooks.PerfTracker(hooks.PerfTrackerConfig(window_s=0.0))
    detector = pt.service.detector
    triggers = iter([Trigger("slowdown", 0.0, 1.0, 0.5)])

    def feed(name, t):               # the detector's bookkeeping, forced
        trig = next(triggers, None)
        if trig is not None:
            detector.triggers.append(trig)
        return trig
    monkeypatch.setattr(detector, "feed", feed)
    loader_next, opt_step = pt.wrap(lambda: None, lambda: None)

    def window():
        opt_step()                   # the trigger opens a window
        assert (len(detector.triggers), len(pt.results)) == (1, 0)
        for _ in range(hooks.MIN_WINDOW_ITERS):
            loader_next()
            opt_step()
    _, spans = _capture(tmp_path, window)
    assert (len(detector.triggers), len(pt.results)) == (1, 1)
    win, = _named(spans, "repro.tracker.window")
    diag, = _named(spans, "repro.tracker.diagnose")
    assert diag.inside(win)
    timing = pt.results[-1].timing
    for stage in ("summarize", "transport", "localize"):
        s, = _named(spans, f"repro.service.{stage}")
        assert s.inside(diag), stage
        assert timing[f"{stage}_s"] == pytest.approx(
            (s.end - s.start) / 1e9, abs=2e-4), stage


def test_trainer_run_marks_each_step(tmp_path):
    """``Trainer.run`` marks every step, and in it the loader and the
    step's dispatch."""
    from repro.data.pipeline import DataConfig
    from repro.optim.adamw import OptConfig
    from repro.train.loop import TrainConfig, Trainer
    cfg = reduced(ARCHS["starcoder2-3b"], layers=1, d_model=64, vocab=256)
    tr = Trainer(cfg, DataConfig(batch=2, seq_len=16), OptConfig(),
                 TrainConfig(steps=3, log_every=100, perftracker=False))
    _, spans = _capture(tmp_path, tr.run)
    steps = _named(spans, "repro.train.step")
    assert len(steps) == 3
    assert [s.stats["step_num"] for s in steps] == [0, 1, 2]
    for name in ("repro.train.loader", "repro.train.fused"):
        got = _named(spans, name)
        assert len(got) == 3
        for s in got:
            _holder(s, spans, "repro.train.step")
    assert np.isfinite(tr.history[-1]["loss"])


def test_spans_leave_jax_backends_alone():
    """A span imports ``jax.profiler`` and initializes no backend, so a
    parent process that must leave the chip to its children can use it.
    Checked in a fresh interpreter."""
    code = textwrap.dedent("""
        from jax._src import xla_bridge
        from repro.instrument.spans import span, step_span
        with span("repro.test", worker=1, window=2) as s:
            with step_span("repro.test.step", 3):
                pass
        assert s.seconds >= 0.0
        assert not xla_bridge.backends_are_initialized()
        print("OK")
    """)
    src = str(Path(__file__).resolve().parent.parent / "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       env={**os.environ, "PYTHONPATH": src})
    assert r.returncode == 0 and "OK" in r.stdout, r.stdout + r.stderr
