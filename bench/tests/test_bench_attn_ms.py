"""The reader of the attention core's device time: operations whose scope
path holds an ``attn`` component, in every phase, per step, on small
synthetic traces."""
from types import SimpleNamespace as NS

import pytest

from bench import program_spans as P
from bench import spec
from bench import tracing as T

E = T.Event
NAME = "attn_ms.watched"
STEP = "jit(train_step)/"


def _ctx(trace):
    return NS(cell=spec.cell("sc2-watched-steady"),
              outcome=NS(e2e={}, counters={}), trace=trace, peak={}, chips=1)


def _summary(lo=0, hi=1000):
    return T.Summary(window_s=(hi - lo) / 1e9, busy_s=0.0, chips=1, lo=lo,
                     hi=hi, host=[], events={})


def test_attn_ms_is_declared_for_the_watched_cell():
    b = {m["name"]: m for m in spec.benchmark()["per_layer"]}
    assert b[NAME]["workloads"] == ["sc2-watched-steady"]
    assert b[NAME]["moves"] == "tokens_per_s"
    assert NAME in [m["name"]
                    for m in spec.cell("sc2-watched-steady").per_layer]


def test_attn_ms_sums_the_attn_scope_in_every_phase(monkeypatch):
    host = [E("repro.train.step", 0, 500), E("repro.train.step", 500, 1000)]
    ops = {"/device:TPU:0": [
        # the loop's own event spans its body: not counted twice
        P.Op("%while.1", 0, 300, STEP + "jvp(fwd)/closed_call/attn/while"),
        P.Op("%fusion.1", 0, 100, STEP + "jvp(fwd)/closed_call/attn/"
             "while/body/btkgd,bskd->btkgs/dot_general"),
        P.Op("%fusion.2", 100, 300, STEP + "jvp(fwd)/closed_call/"
             "bsd,dhx->bshx/dot_general"),
        P.Op("%fusion.3", 300, 350, STEP + "transpose(jvp(fwd))/checkpoint/"
             "rematted_computation/attn/while/body/exp"),
        P.Op("%fusion.4", 350, 600, STEP + "transpose(jvp(fwd))/checkpoint/"
             "attn/while/body/btkgs,btkgd->bskd/dot_general"),
        # a fused operation whose later path lies in the core
        P.Op("%fusion.5", 600, 700, STEP + "transpose(jvp(fwd))/checkpoint/"
             "add_any;checkpoint/attn/while/body/mul"),
        # a name that only contains the scope's letters
        P.Op("%fusion.6", 700, 800, STEP + "jvp(fwd)/attn_out/mul"),
        P.Op("%fusion.7", 800, 900, STEP + "opt/mul"),
        # past the window's end: clipped
        P.Op("%fusion.8", 950, 1100, STEP + "jvp(fwd)/attn/dot_general")]}
    monkeypatch.setattr(P, "host_spans", lambda: host)
    monkeypatch.setattr(P, "device_ops", lambda: ops)
    got = spec.reader(NAME)(_ctx(_summary()))
    assert got == pytest.approx((100 + 50 + 250 + 100 + 50) / 2 / 1e6)


def test_attn_ms_is_none_without_the_scope(monkeypatch):
    """The program before the core had the scope: nothing to read, and
    no raise."""
    host = [E("repro.train.step", 0, 1000)]
    ops = {"/device:TPU:0": [
        P.Op("%fusion.1", 0, 100, STEP + "jvp(fwd)/closed_call/while/body/"
             "btkgd,bskd->btkgs/dot_general"),
        P.Op("%fusion.2", 100, 200, "")]}
    monkeypatch.setattr(P, "host_spans", lambda: host)
    monkeypatch.setattr(P, "device_ops", lambda: ops)
    read = spec.reader(NAME)
    assert read(_ctx(_summary())) is None
    assert read(_ctx(None)) is None
    monkeypatch.setattr(P, "host_spans", lambda: [])
    ops["/device:TPU:0"].append(P.Op("%fusion.3", 200, 300, STEP + "attn/x"))
    assert read(_ctx(_summary())) is None
