"""The reduction of the program's own spans and scopes: device idle time
put down to the innermost program span holding it, device time split by
training phase from scope paths, and the readers of both, on small
synthetic traces."""
import pytest

from bench import program_spans as P
from bench import spec
from bench import tracing as T

E = T.Event


def test_innermost_span_wins_over_nested_and_overlapping_spans():
    spans = [E("repro.scenario.window", 0, 100),
             E("repro.train.iteration", 10, 60),
             E("repro.train.loader", 10, 30),
             E("repro.pipeline.tick", 70, 95),
             # overlaps the tick without nesting: the later one wins
             E("repro.pipeline.summarize", 90, 110)]
    assert P.innermost(spans) == [
        (0, 10, "repro.scenario.window"),
        (10, 30, "repro.train.loader"),
        (30, 60, "repro.train.iteration"),
        (60, 70, "repro.scenario.window"),
        (70, 90, "repro.pipeline.tick"),
        (90, 110, "repro.pipeline.summarize")]


def test_idle_goes_interval_by_interval_and_unheld_idle_is_kept_apart():
    spans = [E("repro.train.iteration", 100, 200),
             E("repro.train.loader", 100, 150)]
    # one gap across the loader, the iteration and past both, and one
    # gap that no span holds
    got = P.attribute([(120, 230), (300, 340)], spans)
    assert got == {"repro.train.loader": 30, "repro.train.iteration": 50,
                   None: 30 + 40}


def _summary(devices, lo=0, hi=1000):
    return T.Summary(window_s=(hi - lo) / 1e9, busy_s=0.0,
                     chips=len(devices), lo=lo, hi=hi, host=[],
                     events=devices)


def test_idle_by_cause_adds_up_to_the_device_idle_share():
    dev = [E("%fusion.1", 0, 100), E("%fusion.2", 400, 500),
           E("%fusion.3", 600, 900)]
    spans = [E("repro.scenario.window", 0, 1000),
             E("repro.workload.window", 0, 550),
             E("repro.train.loader", 100, 300),
             E("repro.train.fence", 300, 380),
             E("repro.pipeline.tick", 560, 620),
             E("repro.service.localize", 940, 960)]
    by = P.idle_by_cause(_summary({"/device:TPU:0": dev}), spans)
    # idle: 100-400, 500-600, 900-1000
    assert by["loader"] == pytest.approx(20.0)
    # fence 300-380, the workload window 380-400 and 500-550
    assert by["step_host"] == pytest.approx(8.0 + 2.0 + 5.0)
    # the scenario window 550-560, the tick 560-600, and after 900
    # everything but the service span
    assert by["pipeline"] == pytest.approx(1.0 + 4.0 + 8.0)
    assert by["other"] == pytest.approx(2.0)
    assert None not in by
    idle = 100.0 * (1 - T.busy_ns(dev, 0, 1000) / 1000)
    assert sum(by.values()) == pytest.approx(idle)


def test_idle_with_no_program_span_is_unheld_and_averaged_over_chips():
    devs = {"/device:TPU:0": [E("%fusion.1", 0, 500)],
            "/device:TPU:1": [E("%fusion.1", 0, 1000)]}
    spans = [E("repro.train.loader", 500, 700)]
    by = P.idle_by_cause(_summary(devs), spans)
    assert by == {"loader": pytest.approx(10.0),
                  None: pytest.approx(15.0)}
    assert P.idle_by_cause(_summary(devs), []) is None
    assert P.idle_by_cause(_summary(devs),
                           [E("repro.train.loader", 2000, 3000)]) is None


@pytest.mark.parametrize("scope, phase", [
    ("jit(train_step)/jvp(fwd)/while/body/closed_call/bsd,dkx->bskx", "fwd"),
    ("jit(train_step)/transpose(jvp(fwd))/while/body/closed_call/"
     "checkpoint/rematted_computation/bsd,dkx->bskx", "recompute"),
    ("jit(train_step)/transpose(jvp(fwd))/while/body/closed_call/"
     "checkpoint/bsd,dkx->bskx", "bwd"),
    ("jit(train_step)/opt/mul", "opt"),
    ("jit(opt_step)/opt/sqrt", "opt"),
    # a fused operation: paths joined by ';', the later ones relative
    ("jit(train_step)/transpose(jvp(fwd))/while/body/closed_call/"
     "checkpoint/reshape;checkpoint/transpose", "bwd"),
    ("jit(grad_step)/jvp(fwd)/tanh;jvp(fwd)/mul", "fwd"),
    # paths in two phases: counted apart, in none of the four (the id is
    # the one this case had when such an operation took the optimizer's)
    pytest.param("jit(train_step)/transpose(jvp(fwd))/add_any;opt/mul",
                 "mixed",
                 id="jit(train_step)/transpose(jvp(fwd))/add_any;opt/mul-opt"),
    ("jit(train_step)/transpose(jvp(fwd))/while/body/closed_call/"
     "checkpoint/rematted_computation/dot;checkpoint/transpose", "mixed"),
    ("jit(train_step)/while/body/dynamic_update_slice;jvp(fwd)/mul", "fwd"),
    ("jit(train_step)/convert_element_type", None),
    ("jit(train_step)/transpose(jvp(loss))/dot_general", None),
    ("", None),
])
def test_phase_of_a_scope_path(scope, phase):
    assert P.phase_of(scope) == phase


def test_device_time_by_phase_per_step():
    ops = {"/device:TPU:0": [
        P.Op("%while.1", 0, 600, "jit(train_step)/jvp(fwd)/while"),
        P.Op("%fusion.1", 0, 200, "jit(train_step)/jvp(fwd)/dot"),
        P.Op("%fusion.2", 200, 500, "jit(train_step)/transpose(jvp(fwd))/"
             "checkpoint/rematted_computation/dot"),
        P.Op("%fusion.3", 500, 1100,
             "jit(train_step)/transpose(jvp(fwd))/dot"),
        P.Op("%fusion.4", 1100, 1200, "jit(train_step)/opt/mul"),
        P.Op("%copy.1", 1200, 1300, ""),
        P.Op("%fusion.6", 1300, 1500,
             "jit(train_step)/transpose(jvp(fwd))/add_any;opt/mul"),
        P.Op("%fusion.5", 5000, 6000, "jit(train_step)/opt/mul")]}
    by = P.ms_by_phase(ops, 0, 2000, steps=2)
    assert by == {"fwd": pytest.approx(100e-6),
                  "recompute": pytest.approx(150e-6),
                  "bwd": pytest.approx(300e-6),
                  "opt": pytest.approx(50e-6),
                  "mixed": pytest.approx(100e-6),
                  None: pytest.approx(50e-6)}


def test_a_trace_with_no_scope_paths_gives_no_phase_rather_than_a_guess():
    # a program without the step's scopes: autodiff's own names alone
    # give no phase
    ops = {"/device:TPU:0": [
        P.Op("%fusion.1", 0, 200, ""),
        P.Op("%fusion.2", 200, 400,
             "jit(train_step)/transpose(jvp(loss))/checkpoint/"
             "rematted_computation/dot_general")]}
    assert P.ms_by_phase(ops, 0, 1000, steps=1) is None
    scoped = {"/device:TPU:0": [P.Op("%fusion.3", 0, 10,
                                     "jit(train_step)/jvp(fwd)/dot")]}
    assert P.ms_by_phase(scoped, 0, 1000, steps=0) is None
    assert P.ms_by_phase({}, 0, 1000, steps=3) is None


def _ctx(cell, trace):
    from types import SimpleNamespace as NS
    return NS(cell=spec.cell(cell), outcome=NS(e2e={}, counters={}),
              trace=trace, peak={}, chips=1)


NEW = {"sc2-watched-steady": ["fwd_ms.watched", "recompute_ms.watched",
                              "bwd_ms.watched", "opt_ms.watched",
                              "tracker_windows.watched"],
       "sc2-fleet4-loaderburn": ["idle_loader.fleet",
                                 "idle_step_host.fleet",
                                 "idle_pipeline.fleet", "tick_ms.fleet"]}


@pytest.mark.parametrize("cell", sorted(NEW))
def test_the_new_metrics_are_declared_for_their_cell_alone(cell):
    b = {m["name"]: m for m in spec.benchmark()["per_layer"]}
    for name in NEW[cell]:
        assert b[name]["workloads"] == [cell]
        assert name in [m["name"] for m in spec.cell(cell).per_layer]


def test_readers_of_the_watched_step(monkeypatch):
    host = [E("repro.train.step", 0, 400), E("repro.train.step", 400, 800),
            E("repro.tracker.window", 350, 700),
            E("repro.train.step", 2000, 2100)]
    ops = {"/device:TPU:0": [
        P.Op("%fusion.1", 0, 100, "jit(train_step)/jvp(fwd)/dot"),
        P.Op("%fusion.2", 100, 200, "jit(train_step)/transpose(jvp(fwd))/"
             "rematted_computation/dot"),
        P.Op("%fusion.3", 200, 600,
             "jit(train_step)/transpose(jvp(fwd))/dot"),
        P.Op("%fusion.4", 600, 700, "jit(train_step)/opt/add")]}
    monkeypatch.setattr(P, "host_spans", lambda: host)
    monkeypatch.setattr(P, "device_ops", lambda: ops)
    ctx = _ctx("sc2-watched-steady", _summary({}, lo=0, hi=1000))
    read = {n: spec.reader(n)(ctx) for n in NEW["sc2-watched-steady"]}
    assert read == {"fwd_ms.watched": pytest.approx(50e-6),
                    "recompute_ms.watched": pytest.approx(50e-6),
                    "bwd_ms.watched": pytest.approx(200e-6),
                    "opt_ms.watched": pytest.approx(50e-6),
                    "tracker_windows.watched": pytest.approx(50.0)}


def test_readers_of_the_fleet(monkeypatch):
    host = [E("repro.scenario.window", 0, 1000),
            E("repro.train.loader", 100, 400),
            E("repro.pipeline.tick", 800, 900),
            E("repro.pipeline.tick", 900, 1200)]
    dev = {"/device:TPU:0": [E("%fusion.1", 0, 100),
                             E("%fusion.2", 400, 800)]}
    monkeypatch.setattr(P, "host_spans", lambda: host)
    ctx = _ctx("sc2-fleet4-loaderburn", _summary(dev))
    read = {n: spec.reader(n)(ctx) for n in NEW["sc2-fleet4-loaderburn"]}
    assert read == {"idle_loader.fleet": pytest.approx(30.0),
                    "idle_step_host.fleet": 0.0,
                    "idle_pipeline.fleet": pytest.approx(20.0),
                    "tick_ms.fleet": pytest.approx(200e-6)}


def test_readers_return_nothing_without_the_programs_spans(monkeypatch):
    """A program that writes no spans or scopes (as before them) gives
    each new reader nothing to read, and none of them raises."""
    monkeypatch.setattr(P, "host_spans", lambda: [])
    monkeypatch.setattr(P, "device_ops", lambda: {"/device:TPU:0": [
        P.Op("%fusion.1", 0, 100, "")]})
    for cell, names in NEW.items():
        ctx = _ctx(cell, _summary({"/device:TPU:0": [E("%f.1", 0, 10)]}))
        for n in names:
            assert spec.reader(n)(ctx) is None, n


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8] fusion()" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "repro.train.loader" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(step)" } }
}
"""


def test_host_spans_are_read_from_a_recorded_trace(tmp_path):
    from jax.profiler import ProfileData
    assert P.host_spans(tmp_path) == []
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    assert P.host_spans(tmp_path) == [E("repro.train.loader", 4000, 8000)]


OPS = """
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "repro.train.step" } }
}
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 7000000 duration_ps: 1000000
             stats { metadata_id: 7 str_value: "jit(train_step)/opt/mul" } }
    events { metadata_id: 3 offset_ps: 8000000 duration_ps: 1000000
             stats { metadata_id: 8 int64_value: 5 } }
    events { metadata_id: 4 offset_ps: 9000000 duration_ps: 2000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 8000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8] fusion()"
    stats { metadata_id: 7 str_value: "jit(train_step)/jvp(fwd)/dot" } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = f32[8] fusion()" } }
  event_metadata { key: 3 value { id: 3 name: "%copy.3 = f32[8] copy()" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.4 = f32[8] fusion()"
    stats { metadata_id: 7 ref_value: 9 } } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 8 value { id: 8 name: "run_id" } }
  stat_metadata { key: 9 value { id: 9
    name: "jit(train_step)/transpose(jvp(fwd))/dot" } }
}
planes {
  id: 3 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 8000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_train_step" } }
}
"""


def test_scope_paths_are_read_from_the_event_or_its_metadata(tmp_path):
    """The device planes of a recorded trace, read from its protobuf: an
    operation's scope path comes from its own stats, else its
    metadata's, a string stat either given or interned; host planes, and
    device planes with no operations line, are skipped; an operation with
    no scope path keeps an empty one."""
    from jax.profiler import ProfileData
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(OPS))
    assert P.device_ops(tmp_path) == {"/device:TPU:0": [
        P.Op("%fusion.1", 1000, 6000, "jit(train_step)/jvp(fwd)/dot"),
        P.Op("%fusion.2", 8000, 9000, "jit(train_step)/opt/mul"),
        P.Op("%copy.3", 9000, 10000, ""),
        P.Op("%fusion.4", 10000, 12000,
             "jit(train_step)/transpose(jvp(fwd))/dot")]}
    assert P.device_ops(tmp_path / "none") == {}


def test_profile_data_hides_metadata_stats():
    """Why the device planes are read from the protobuf: ``ProfileData``
    gives an event its own stats alone, so an operation whose scope path
    sits in its metadata, as on the chip, shows none there."""
    from jax.profiler import ProfileData
    data = ProfileData.text_proto_to_serialized_xspace(OPS)
    shown = {e.name.split(" = ")[0]: dict(e.stats)
             for p in ProfileData.from_serialized_xspace(data).planes
             if p.name == "/device:TPU:0"
             for line in p.lines if line.name == "XLA Ops"
             for e in line.events}
    assert "tf_op" not in shown["%fusion.1"]
    assert shown["%fusion.2"]["tf_op"] == "jit(train_step)/opt/mul"
    assert P.read_ops(data)["/device:TPU:0"][0].scope == (
        "jit(train_step)/jvp(fwd)/dot")
