"""ISSUE 7: the EROICA loop over REAL jit'd training jobs (DESIGN.md §11).

Four layers of coverage:

  * the instrumented ``Trainer.train_iteration`` itself — loss decreases,
    the checkpoint save/resume round-trip (through the fixed shardings
    path), tracer phase events present and ordered with HLO-cost
    sub-events nested inside the fenced ``train.step`` span, and the
    explicit per-resource stream set (satellite: no aliased gpu_sm /
    pcie_tx / membw streams);
  * in-process ``TrainerWorkload`` scenarios — each live fault
    (dataloader burn / step throttle / GC pause) detected and localized
    to the right function on the right workers, with the paper-playbook
    mitigation plan on the ladder;
  * fleet/wire byte-parity of the diagnosis over real trainer profiles;
  * ``@pytest.mark.train`` multi-process integration — the acceptance
    bar: >= 3 fault scenarios against real trainer processes over the
    socket transport, each producing a localized incident with no
    ``FleetSimulator`` involvement anywhere.
"""
import time
from dataclasses import replace

import numpy as np
import pytest

from repro.core.mitigation import Action
from repro.core.service import PerfTrackerService
from repro.online import ScenarioRunner, ScheduledFault
from repro.train.loop import Trainer
from repro.train.workload import (DataloaderBurn, GcPause, StepThrottle,
                                  TrainerWorkload,
                                  default_trainer_detector_cfg,
                                  tiny_train_setup)

pytestmark = pytest.mark.train

IPW = 8                       # iterations per profiling window
N_WIN = 7                     # fault active for windows [2, 7)

#: functions a degraded-step incident may localize to — all phases of the
#: fenced train.step span (the HLO sub-events split it by cost)
STEP_FUNCTIONS = {"train.step", "xla.gemm", "xla.other", "optimizer.step"}


@pytest.fixture(scope="module")
def wl4():
    wl = TrainerWorkload(n_workers=4)
    wl._ensure_workers()
    yield wl
    wl.close()


def _scenario(wl, fault):
    return ScenarioRunner(
        None, [ScheduledFault(fault, 2, N_WIN)], n_windows=N_WIN,
        iters_per_window=IPW,
        detector_cfg=default_trainer_detector_cfg(IPW), workload=wl)


def _incident(result, functions, workers, action=None):
    """The incident localizing ``functions`` (str or set) that implicates
    every worker in ``workers`` (and, when given, whose plan ladder holds
    ``action``).  Extra noise incidents are tolerated — the scenario's
    contract is that the GENUINE one exists."""
    fns = {functions} if isinstance(functions, str) else set(functions)
    for inc in result.incidents:
        if inc.function in fns and set(workers) <= set(inc.workers) \
                and (action is None
                     or action in [p.action for p in inc.plans]):
            return inc
    raise AssertionError(
        f"no incident for {sorted(fns)} on {workers} with {action}; got "
        f"{[(i.function, i.workers, [p.action for p in i.plans]) for i in result.incidents]}")


# -- the instrumented real loop ----------------------------------------------

def test_train_iteration_loss_decreases():
    mc, dc, oc, tc = tiny_train_setup()
    tr = Trainer(mc, dc, oc, tc)
    params, opt_state, start = tr.init_state()
    assert start == 0
    losses = []
    for _ in range(30):
        params, opt_state, m = tr.train_iteration(params, opt_state)
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5])
    tr.loader.close()


def test_checkpoint_save_resume_roundtrip(tmp_path):
    mc, dc, oc, tc = tiny_train_setup()
    tc = replace(tc, ckpt_every=5, ckpt_dir=str(tmp_path))
    tr = Trainer(mc, dc, oc, tc)
    params, opt_state, _ = tr.init_state()
    for _ in range(10):
        params, opt_state, _ = tr.train_iteration(params, opt_state)
    tr.ckpt.wait()
    tr.loader.close()
    # a fresh trainer resumes from the iteration-10 save via the (fixed)
    # shardings-threaded restore path
    tr2 = Trainer(mc, dc, oc, tc)
    p2, o2, start2 = tr2.init_state()
    assert start2 == 10
    assert int(o2["step"]) == 10
    np.testing.assert_array_equal(np.asarray(p2["embed"]["table"]),
                                  np.asarray(params["embed"]["table"]))
    tr2.loader.close()


def test_tracer_phases_present_and_ordered(wl4):
    tw = wl4.workers[0]
    _, prof = tw.run_window(3)
    # satellite: the stream set is explicit — only the real cpu sampler,
    # no aliased hardware streams
    assert set(prof.streams) == {"cpu"}
    top = sorted((e for e in prof.events if e.depth == 1),
                 key=lambda e: e.start)
    assert [e.name for e in top] == \
        ["dataloader.next", "train.step", "optimizer.step"] * 3
    for a, b in zip(top, top[1:]):
        assert a.end <= b.start + 1e-9
    # HLO-cost attribution: depth-2 sub-events split each fenced
    # train.step span, gemm first, boundaries inside the parent
    assert tw.trainer.bundle.gemm_frac is not None
    steps = [e for e in top if e.name == "train.step"]
    gemm = sorted((e for e in prof.events if e.name == "xla.gemm"),
                  key=lambda e: e.start)
    other = sorted((e for e in prof.events if e.name == "xla.other"),
                   key=lambda e: e.start)
    assert len(gemm) == len(other) == len(steps) == 3
    for s, g, o in zip(steps, gemm, other):
        assert g.depth == o.depth == 2
        assert s.start <= g.start < g.end <= o.start < o.end <= s.end
    # anchors are measured wall durations covering each full iteration
    spans = [top[3 * i + 2].end - top[3 * i].start for i in range(3)]
    assert all(d > 0 for d in spans)


def test_default_tracer_streams_cpu_only():
    from repro.instrument.tracer import Tracer
    tr = Tracer(worker=0, rate_hz=200.0)
    tr.start_window()
    time.sleep(0.02)
    prof = tr.stop_window()
    assert set(prof.streams) == {"cpu"}


# -- in-process fault scenarios ----------------------------------------------

def test_dataloader_burn_localizes_and_plans_migration(wl4):
    res = _scenario(wl4, DataloaderBurn(workers=(1,))).run()
    _incident(res, "dataloader.next", (1,), Action.MIGRATE_DATALOADER)


def test_step_throttle_localizes_to_step_phase(wl4):
    res = _scenario(wl4, StepThrottle(workers=(2,))).run()
    _incident(res, STEP_FUNCTIONS, (2,), Action.REPLACE_HOSTS)


def test_gc_pause_on_subset_plans_gc_synchronization(wl4):
    res = _scenario(wl4, GcPause(workers=(0, 1, 2))).run()
    _incident(res, "runtime.gc", (0, 1, 2), Action.SYNCHRONIZE_GC)


def test_param_corruption_resolved_by_real_rollback(wl4):
    """DESIGN.md §14 on the REAL trainer: a live numerics fault (corrupted
    params, NaN planted) diverges actual jit'd training; the numerics
    incident's ROLLBACK_TO_CHECKPOINT rung restores the window-0 on-disk
    checkpoint into the running trainers (parameter-equality verified) and
    the incident resolves because the loss genuinely came back."""
    from repro.ckpt import RecoveryManager
    from repro.train.workload import ParamCorruption
    n_win = 8
    # save only at window 0: the periodic cadence must not checkpoint the
    # corrupted state the rollback is supposed to erase
    rec = RecoveryManager.for_workload(wl4, save_every=n_win)
    fault = ParamCorruption(workers=(1,), nan=True)
    r = ScenarioRunner(
        None, [ScheduledFault(fault, 2, n_win,
                              cures=(Action.ROLLBACK_TO_CHECKPOINT,))],
        n_windows=n_win, iters_per_window=IPW,
        detector_cfg=default_trainer_detector_cfg(IPW), workload=wl4,
        mitigation=True, recovery=rec)
    res = r.run()
    inc = next(i for i in res.incidents
               if i.channel == "numerics" and i.applied)
    assert inc.state == "resolved"
    assert inc.applied[0][1].action is Action.ROLLBACK_TO_CHECKPOINT
    # the rollback was REAL: a step restored from disk, verified equal to
    # the saved arrays, with the diverged iterations honestly discarded
    m = next(m for m in r.engine.log
             if m.plan.action is Action.ROLLBACK_TO_CHECKPOINT)
    assert not m.rollback_failed and m.rollback_verified
    assert m.restored_step is not None and m.lost_steps > 0
    # and the live params really are healthy again (the NaN is gone)
    import jax
    for tw in wl4.workers:
        for leaf in jax.tree_util.tree_leaves(tw.params):
            assert np.isfinite(np.asarray(jax.device_get(leaf))).all()


# -- fleet/wire parity on real profiles ---------------------------------------

def _assert_identical(a, b):
    assert a.functions() == b.functions()
    for aa, bb in zip((d.abnormality for d in a.diagnoses),
                      (d.abnormality for d in b.diagnoses)):
        np.testing.assert_array_equal(aa.workers, bb.workers)
        np.testing.assert_array_equal(aa.patterns, bb.patterns)
        np.testing.assert_array_equal(aa.d_expect, bb.d_expect)
        np.testing.assert_array_equal(aa.delta, bb.delta)


def test_fleet_wire_parity_on_trainer_profiles(wl4):
    wd = wl4.run_window(0, [DataloaderBurn(workers=(1,))], IPW, None)
    svc = PerfTrackerService(family="host", summarize_backend="numpy")
    fleet = svc.diagnose_profiles(wd.profiles, mode="fleet")
    assert "dataloader.next" in fleet.functions()
    _assert_identical(fleet, svc.diagnose_profiles(wd.profiles, mode="wire"))


# -- multi-process socket integration (the acceptance bar) --------------------

MP_CASES = [
    pytest.param(DataloaderBurn(workers=(1,)), "dataloader.next", (1,),
                 Action.MIGRATE_DATALOADER, id="dataloader-burn"),
    pytest.param(StepThrottle(workers=(2,)), STEP_FUNCTIONS, (2,),
                 Action.REPLACE_HOSTS, id="step-throttle"),
    pytest.param(GcPause(workers=(0, 1, 2)), "runtime.gc", (0, 1, 2),
                 Action.SYNCHRONIZE_GC, id="gc-pause"),
]


@pytest.mark.timeout(600)
@pytest.mark.parametrize("fault,functions,workers,action", MP_CASES)
def test_multiprocess_trainer_scenario(fault, functions, workers, action):
    """Real trainer processes over the socket transport: spawned children
    run actual jit'd training, upload patterns + measured anchors, and the
    parent (no simulator, no model) diagnoses end-to-end."""
    wl = TrainerWorkload(n_workers=4)
    r = _scenario(wl, fault)
    res = r.run_multiprocess(n_procs=2, window_timeout=240.0)
    ws = res.wire_summary()
    assert ws["expected"] == 4 * N_WIN
    assert ws["delivered"] == ws["expected"]
    _incident(res, functions, workers, action)


# -- one process per chip ------------------------------------------------------

def _fake_host(monkeypatch, root, tpus, nodes=(), accel=0):
    """A host with ``tpus`` TPU v5e functions on its PCI bus (in IOMMU
    groups 0..tpus-1) and a gVNIC, of which this process sees the vfio
    nodes of the groups ``nodes`` and ``accel`` /dev/accel nodes."""
    import repro.online.scenario as scenario
    pci, dev = root / "pci", root / "dev"
    (dev / "vfio").mkdir(parents=True)
    (dev / "vfio" / "vfio").touch()
    for i, ident in enumerate(["0x0063"] * tpus + ["0x0042"]):
        d = pci / f"0000:00:{i:02x}.0"
        d.mkdir(parents=True)
        (d / "vendor").write_text("0x1ae0\n")
        (d / "device").write_text(ident + "\n")
        (root / "groups" / str(i)).mkdir(parents=True)
        (d / "iommu_group").symlink_to(root / "groups" / str(i))
    for g in nodes:
        (dev / "vfio" / str(g)).touch()
    for n in range(accel):
        (dev / f"accel{n}").touch()
    monkeypatch.setattr(scenario, "_PCI_DEVICES", str(pci))
    monkeypatch.setattr(scenario, "_DEV", str(dev))


def test_trainer_mp_refuses_more_processes_than_chips(monkeypatch,
                                                      tmp_path):
    """Every trainer child needs a chip of its own: on a one-chip host a
    second trainer process is refused before anything is spawned."""
    _fake_host(monkeypatch, tmp_path, 1, nodes=(0,))
    r = _scenario(TrainerWorkload(n_workers=4), DataloaderBurn(workers=(1,)))
    with pytest.raises(RuntimeError, match="need a chip each"):
        r.run_multiprocess(n_procs=2)


@pytest.mark.parametrize("tpus,nodes,accel,chips", [
    (4, (0, 1, 2, 3), 0, 4),        # a whole four-chip host over vfio
    (4, (3,), 0, 1),                # one chip of four exposed to a sandbox
    (4, (), 4, 4),                  # chips on the accel driver
    (0, (), 0, 0),                  # no TPU: the gVNIC is not a chip
])
def test_chip_count_ignores_the_jax_backend(monkeypatch, tmp_path, tpus,
                                            nodes, accel, chips):
    """Chips are counted from the PCI bus and the device nodes this
    process sees, not through a jax backend: a process whose backend is
    the CPU, as one whose TPU init failed would be, still counts them.
    (That counting starts no backend is checked by
    test_multiprocess_parents_stay_off_jax.)"""
    import jax
    from repro.online.scenario import accelerator_chips
    assert jax.default_backend() == "cpu"
    _fake_host(monkeypatch, tmp_path, tpus, nodes, accel)
    assert accelerator_chips() == chips


@pytest.mark.timeout(600)
def test_multiprocess_parents_stay_off_jax():
    """Neither multiprocess parent (simulator or trainer children)
    initializes a jax backend: on a chip it would hold the chip its
    children need.  Checked in a fresh interpreter."""
    import os
    import subprocess
    import sys
    import textwrap
    from pathlib import Path
    code = textwrap.dedent("""
        from jax._src import xla_bridge
        from repro.core.simulation import SimConfig
        from repro.online import ScenarioRunner, ScheduledFault
        from repro.train.workload import (DataloaderBurn, TrainerWorkload,
                                          default_trainer_detector_cfg,
                                          tiny_train_setup)
        sim = ScenarioRunner(SimConfig(n_workers=4, window_s=0.5), [],
                             n_windows=2).run_multiprocess(n_procs=2)
        assert sim.wire_summary()["delivered"] == 8
        assert not xla_bridge.backends_are_initialized()
        wl = TrainerWorkload(n_workers=2, setup=tiny_train_setup(),
                             warmup_iters=2)
        res = ScenarioRunner(
            None, [ScheduledFault(DataloaderBurn(workers=(1,)), 1, 2)],
            n_windows=2, iters_per_window=3,
            detector_cfg=default_trainer_detector_cfg(3),
            workload=wl).run_multiprocess(n_procs=1, window_timeout=240.0)
        assert res.wire_summary()["delivered"] == 4
        assert not xla_bridge.backends_are_initialized()
        print("OK")
    """)
    src = str(Path(__file__).resolve().parent.parent / "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=540,
                       env={**os.environ, "PYTHONPATH": src})
    assert r.returncode == 0 and "OK" in r.stdout, r.stdout + r.stderr
