"""Production training loop: jit'd train step with sharded state, PerfTracker
attached (import-only anchors), async checkpointing, elastic restart, and a
mitigation hook (``_maybe_mitigate``: consumes PerfTracker diagnoses as they
land, records the planned actions, and fronts REPLACE_HOSTS/CHECKPOINT_NOW
plans with an immediate checkpoint save — it does not re-mesh by itself).

``train_iteration`` is the fully-instrumented single step the
``TrainerWorkload`` (``repro.train.workload``) drives: every phase of a real
jit'd step — ``dataloader.next`` / ``train.step`` (fwd+bwd, fenced with
``block_until_ready``) / ``optimizer.step`` / ``ckpt.save`` — is recorded
as a Tracer event, and the fused fwd+bwd span is additionally split into
``xla.gemm`` / ``xla.other`` sub-events by the compiled module's HLO cost
model (XLA fuses ops, so the host never sees per-op boundaries; the
roofline split is the cost-model attribution DESIGN.md §11 describes).
"""
from __future__ import annotations

import gc
import time
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.ckpt.checkpoint import Checkpointer, CheckpointError
from repro.core.events import Kind
from repro.core.mitigation import Action, plan_mitigations
from repro.data.pipeline import DataConfig, DataLoader, SyntheticLM
from repro.dist.sharding import DistCtx
from repro.instrument.hooks import PerfTracker, PerfTrackerConfig
from repro.instrument.spans import span, step_span
from repro.models.transformer import Transformer
from repro.optim.adamw import AdamW, OptConfig
from repro.train.step import make_split_train_step, make_train_step
from repro.train.workload import default_trainer_detector_cfg


def gemm_fraction(compiled) -> Optional[float]:
    """Share of a compiled step the HLO cost model gives to matmuls: its
    FLOPs and bytes against the roofline peaks of the device it runs on
    (``hlo_cost.PEAKS``).  It sets where the fenced ``train.step`` span is
    cut into ``xla.gemm`` / ``xla.other``; the ratio is identical across
    same-program workers, so differential localization does not hinge on
    the constants.  None when the module has no cost at all."""
    from repro.launch.hlo_cost import expanded_cost, peak_rates
    flops_s, bytes_s = peak_rates(jax.devices()[0].device_kind)
    cost = expanded_cost(compiled.as_text(), num_devices=1)
    t_gemm, t_other = cost.flops / flops_s, cost.bytes / bytes_s
    if t_gemm + t_other <= 0.0:
        return None
    return min(0.95, max(0.05, t_gemm / (t_gemm + t_other)))


@contextmanager
def _noop_phase(name, kind=None, depth=1, fence=None, resource=""):
    yield


@dataclass
class StepBundle:
    """Compiled split-step executables shared across same-shape trainers.

    ``grad_step`` is the AOT-compiled fwd+bwd (compiled once via
    ``jit.lower(...).compile()`` so the same compile also yields the HLO
    text for cost attribution); ``opt_step`` is the jitted optimizer
    update with donated inputs.  An in-process fleet of identical tiny
    trainers assigns one bundle to every ``Trainer.bundle`` and compiles
    exactly once."""
    grad_step: Callable
    opt_step: Callable
    gemm_frac: Optional[float]      # None = the module has no cost


@dataclass
class TrainConfig:
    steps: int = 50
    log_every: int = 10
    ckpt_every: int = 0              # 0 = off
    ckpt_dir: str = ""
    remat: str = "none"
    folded: bool = False
    perftracker: bool = True
    pt_window_s: float = 1.0
    seed: int = 0


class Trainer:
    def __init__(self, cfg: ModelConfig, data: DataConfig,
                 opt_cfg: OptConfig, tc: TrainConfig,
                 dist: Optional[DistCtx] = None):
        self.cfg, self.data_cfg, self.tc = cfg, data, tc
        self.dist = dist
        self.model = Transformer(cfg, dist=dist, remat=tc.remat,
                                 folded=tc.folded)
        self.opt = AdamW(opt_cfg)
        self.source = SyntheticLM(cfg, data)
        self.loader = DataLoader(self.source)
        step_fn = make_train_step(self.model, self.opt)
        self._jit_step = jax.jit(step_fn, donate_argnums=(0, 1))
        self.pt: Optional[PerfTracker] = None
        if tc.perftracker:
            # real iteration times are noisy: the thresholds made for them
            self.pt = PerfTracker(PerfTrackerConfig(
                window_s=tc.pt_window_s,
                detector=default_trainer_detector_cfg(6),
                family="moe" if cfg.is_moe else "dense"))
            self._next, self._opt_anchor = self.pt.wrap(
                self.loader.next, lambda: None)
        else:
            self._next, self._opt_anchor = self.loader.next, lambda: None
        self.ckpt = Checkpointer(tc.ckpt_dir) if tc.ckpt_dir else None
        self.history: list = []
        self.mitigations: list = []
        self.last_diagnosis = None       # most recent consumed PT result
        # split-step bundle for the instrumented train_iteration path
        # (built lazily on first use; assignable so an in-process fleet of
        # identical trainers shares one compile)
        self.bundle: Optional[StepBundle] = None
        self._step_resource = "cpu" if jax.default_backend() == "cpu" else ""
        self._iter = 0
        # live fault-injection hooks (repro.train.workload perturbs the
        # REAL loop for end-to-end diagnosis scenarios); all off by default
        self.data_burn_s = 0.0           # CPU spin inside dataloader.next
        self.step_pad_s = 0.0            # stall inside train.step
        self.gc_pause_s = 0.0            # gc.collect + stall, every
        self.gc_every = 1                # gc_every iterations

    # ------------------------------------------------------------------
    def init_state(self, resume: bool = True):
        params = self.model.init(jax.random.PRNGKey(self.tc.seed))
        opt_state = self.opt.init(params)
        start = 0
        if self.ckpt and resume:
            latest = self.ckpt.latest_step()
            if latest is not None:
                shardings = None
                if self.dist is not None and self.dist.mesh is not None:
                    # every leaf needs a REAL sharding (a None leaf would
                    # break tree_map structure matching in restore): scalar
                    # opt state rides the mesh replicated
                    ps = self.dist.params_shardings(params)
                    shardings = {"params": ps,
                                 "opt": self.opt.state_shardings(
                                     ps, self.dist.replicated())}
                (params, opt_state), meta = self._restore(
                    latest, params, opt_state, shardings)
                start = meta["step"]
        return params, opt_state, start

    def _restore(self, step, params, opt_state, shardings=None):
        tree, meta = self.ckpt.restore(step, {"params": params,
                                              "opt": opt_state},
                                       shardings=shardings)
        return (tree["params"], tree["opt"]), meta

    # ------------------------------------------------------------------
    def ensure_bundle(self, params, batch) -> StepBundle:
        """Build (or return) the compiled split-step bundle.

        AOT path: one ``jit.lower(...).compile()`` yields both the
        executable and the optimized HLO text, so cost attribution never
        costs a second compile."""
        if self.bundle is None:
            grad_fn, opt_fn = make_split_train_step(self.model, self.opt)
            compiled = jax.jit(grad_fn).lower(params, batch).compile()
            self.bundle = StepBundle(
                grad_step=compiled,
                opt_step=jax.jit(opt_fn, donate_argnums=(0, 1, 2)),
                gemm_frac=gemm_fraction(compiled))
        return self.bundle

    def train_iteration(self, params, opt_state, tracer=None):
        """One fully-instrumented iteration of the REAL loop.

        Identical math to ``run()``'s fused step, but split so every phase
        is a genuine host-visible span: ``dataloader.next`` (PYTHON),
        ``train.step`` (fwd+bwd, fenced on the grads, split into
        ``xla.gemm``/``xla.other`` depth-2 sub-events by the HLO cost
        model), ``optimizer.step`` (fenced on the new params), and
        ``ckpt.save`` when a checkpoint interval hits.  ``tracer`` may be
        None or inactive — the loop then runs unobserved (the overhead
        benchmark's baseline).  Every phase is also a host span on the
        profiler's clock: ``repro.train.iteration`` (worker, iteration)
        holding ``repro.train.loader`` (batch, host-to-device copy and any
        injected burn), ``.grad`` (dispatch), ``.fence``, ``.opt``, and
        ``.ckpt`` / ``.gc`` when they run.
        Returns ``(params, opt_state, metrics)``.
        """
        ph = tracer.phase if tracer is not None else _noop_phase
        it = span("repro.train.iteration",
                  worker=tracer.worker if tracer is not None else 0,
                  iteration=self._iter)
        with span("repro.train.loader"), ph("dataloader.next", Kind.PYTHON):
            batch_np = self.loader.next()
            batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
            if self.data_burn_s > 0.0:    # injected fault: CPU-burning loader
                deadline = time.perf_counter() + self.data_burn_s
                x = 1.0
                while time.perf_counter() < deadline:
                    x = x * 1.0000001 + 1.0
        res = self._step_resource
        with span("repro.train.grad"):
            bundle = self.ensure_bundle(params, batch)
            t0 = time.perf_counter()
            grads, metrics = bundle.grad_step(params, batch)
            if self.step_pad_s > 0.0:     # injected fault: slow device step
                time.sleep(self.step_pad_s)
        with span("repro.train.fence"):
            jax.block_until_ready(grads)
        t1 = time.perf_counter()
        if tracer is not None and tracer.active:
            tracer.add_event("train.step", Kind.GPU, t0, t1, depth=1,
                             resource=res)
            if bundle.gemm_frac is not None:
                cut = t0 + (t1 - t0) * bundle.gemm_frac
                tracer.add_event("xla.gemm", Kind.GPU, t0, cut, depth=2,
                                 resource=res)
                tracer.add_event("xla.other", Kind.GPU, cut, t1, depth=2,
                                 resource=res)
        with span("repro.train.opt"), ph("optimizer.step", Kind.GPU,
                                         resource=res,
                                         fence=lambda: new_params):
            new_params, new_opt, opt_metrics = bundle.opt_step(
                grads, opt_state, params)
        self._iter += 1
        if self.ckpt and self.tc.ckpt_every \
                and self._iter % self.tc.ckpt_every == 0:
            with span("repro.train.ckpt"), ph("ckpt.save", Kind.PYTHON):
                self.ckpt.save(self._iter, {"params": new_params,
                                            "opt": new_opt})
        if self.gc_pause_s > 0.0 and self._iter % max(1, self.gc_every) == 0:
            # injected fault: unsynchronized gc stall (C2P3 stand-in)
            with span("repro.train.gc"), ph("runtime.gc", Kind.PYTHON):
                gc.collect()
                time.sleep(self.gc_pause_s)
        it.end()
        m = dict(metrics)
        m.update(opt_metrics)
        return new_params, new_opt, m

    # ------------------------------------------------------------------
    def run(self, steps: Optional[int] = None):
        params, opt_state, start = self.init_state()
        n = steps or self.tc.steps
        ph = self.pt.tracer.phase if self.pt else _noop_phase
        t_log, step_log = time.perf_counter(), start
        for step in range(start, start + n):
            one = step_span("repro.train.step", step)
            with span("repro.train.loader"):
                batch_np = self._next()
                batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
            # the dispatch; inside a tracker window also the fence
            with span("repro.train.fused"), ph(
                    "train.step", Kind.GPU, depth=1,
                    fence=lambda: metrics["loss"]):
                params, opt_state, metrics = self._jit_step(
                    params, opt_state, batch)
            self._opt_anchor()
            if (step + 1) % self.tc.log_every == 0 or step == start:
                m = {k: float(v) for k, v in metrics.items()}
                # host seconds per step since the previous entry (the
                # float() above waits for this step's result)
                now = time.perf_counter()
                m["step_s"] = (now - t_log) / (step + 1 - step_log)
                t_log, step_log = now, step + 1
                self.history.append({"step": step + 1, **m})
                print(f"step {step+1:5d} loss {m['loss']:.4f} "
                      f"nll {m['nll']:.4f} gnorm {m['grad_norm']:.3f} "
                      f"lr {m['lr']:.2e}", flush=True)
            if self.ckpt and self.tc.ckpt_every \
                    and (step + 1) % self.tc.ckpt_every == 0:
                self.ckpt.save(step + 1, {"params": params,
                                          "opt": opt_state})
            params, opt_state = self._maybe_mitigate(params, opt_state,
                                                     step + 1)
            one.end()
        if self.ckpt:
            self.ckpt.save(start + n, {"params": params, "opt": opt_state},
                           async_=False)
        self.loader.close()
        return params, opt_state

    # ------------------------------------------------------------------
    def _maybe_mitigate(self, params, opt_state, step: int):
        """PerfTracker output drives fault tolerance (DESIGN.md §4).
        Returns the (possibly rolled-back) live state."""
        if not self.pt or not self.pt.results:
            return params, opt_state
        res = self.pt.results.pop()
        self.last_diagnosis = res
        plans = plan_mitigations(res.diagnoses, fleet_size=1)
        for p in plans:
            if p.action == Action.NONE:
                continue
            self.mitigations.append((step, p))
            print(f"[perftracker] step {step}: {res.trigger.reason if res.trigger else '?'} -> "
                  f"{p.action.value}: {p.detail}", flush=True)
            # both actions begin with an immediate checkpoint: replace
            # re-meshes from it, checkpoint_now protects against the
            # widespread-hardware abnormality getting worse
            if p.action in (Action.REPLACE_HOSTS, Action.CHECKPOINT_NOW) \
                    and self.ckpt:
                self.ckpt.save(step, {"params": params, "opt": opt_state})
            # rollback is REAL (DESIGN.md §14): restore the latest valid
            # on-disk step into the live loop; with nothing usable on
            # disk the state is honestly left as-is (no faked cure)
            if p.action == Action.ROLLBACK_TO_CHECKPOINT and self.ckpt:
                latest = self.ckpt.latest_step()
                if latest is not None:
                    try:
                        (params, opt_state), meta = self._restore(
                            latest, params, opt_state)
                        self._iter = meta["step"]
                        print(f"[perftracker] rolled back to step "
                              f"{meta['step']}", flush=True)
                    except CheckpointError as e:
                        print(f"[perftracker] rollback failed: {e}",
                              flush=True)
        return params, opt_state
