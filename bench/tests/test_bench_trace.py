"""The reduction from a trace to busy time, kernel time, exposed
collective time and labelled idle gaps, on small synthetic traces."""
import pytest

from bench import tracing as T

E = T.Event


def test_busy_union_merges_nested_and_overlapping_and_clips():
    evs = [E("%while.1", 0, 100), E("%fusion.1", 10, 40),
           E("%fusion.2", 40, 90), E("%copy.1", 95, 130),
           E("%fusion.3", 200, 260)]
    assert T.union(evs, 0, 250) == [(0, 130), (200, 250)]
    assert T.busy_ns(evs, 0, 250) == 130 + 50
    assert T.gaps(evs, -10, 300) == [(-10, 0), (130, 200), (260, 300)]


def test_leaves_drop_the_loops_and_calls_that_span_their_body():
    evs = [E("%while.1", 0, 100), E("%fusion.1", 10, 40),
           E("%fusion.2", 40, 90), E("%call.4", 100, 130),
           E("%fusion.3", 100, 120), E("%all-reduce.1", 110, 200)]
    assert [e.name for e in T.leaves(evs)] == [
        "%fusion.1", "%fusion.2", "%fusion.3", "%all-reduce.1"]


def _summary(events, host=(), lo=0, hi=1000):
    return T.Summary(window_s=(hi - lo) / 1e9, busy_s=0.0, chips=1,
                     events={"/device:TPU:0": list(events)}, lo=lo, hi=hi,
                     host=list(host))


def test_kernel_time_counts_its_operations_by_name_only():
    s = _summary([E("%pattern_summary.2", 0, 30),
                  E("%pattern_summary.3", 30, 130),
                  E("%fusion.9", 130, 400),
                  E("%while.2", 500, 700), E("%pattern_summary.7", 550, 560)])
    sec, n = s.op_seconds(("%pattern_summary",))
    assert n == 3
    assert sec == pytest.approx(140e-9)


def test_exposed_collective_time_is_what_no_compute_hides():
    evs = [E("%fusion.1", 0, 100), E("%all-reduce.1", 50, 150),
           E("%fusion.2", 120, 130), E("%all-gather.3", 200, 260)]
    # all-reduce 50..150: hidden 50..100 and 120..130 -> exposed 40;
    # all-gather fully exposed -> 60
    assert T.exposed_collective_ns(evs, 0, 1000) == 100
    assert _summary(evs).exposed_collective_s() == pytest.approx(100e-9)


def test_idle_gaps_are_named_by_the_innermost_host_span():
    dev = [E("%fusion.1", 0, 100), E("%fusion.2", 400, 500),
           E("%fusion.3", 520, 900)]
    host = [E(T.WINDOW, 0, 1000), E("bench.workload.run_window", 0, 1000),
            E("bench.feed.next", 150, 350)]
    s = _summary(dev, host)
    gaps = s.breakdown()["idle_gaps"]
    assert gaps[0] == ["bench.feed.next", 300e-9]
    assert gaps[1] == ["bench.workload.run_window", pytest.approx(100e-9)]
    ops = s.breakdown()["device_ops"]
    assert ops[0] == ["%fusion.3", pytest.approx(380e-9)]


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 7000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 8000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8] fusion()" } }
  event_metadata { key: 2 value { id: 2
    name: "%pattern_summary.2 = f32[8] custom-call()" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step(1)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 100000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.feed.next" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(step)" } }
}
"""


def test_a_recorded_trace_reduces_to_busy_time_and_a_breakdown(tmp_path):
    from jax.profiler import ProfileData
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    tr = T.load(tmp_path)
    assert [e.name for e in tr.host] == ["bench.window", "bench.feed.next"]
    s = T.summarize(tr)
    assert s.window_s == pytest.approx(10e-6)
    assert s.busy_s == pytest.approx(6e-6)
    assert s.op_seconds(("%pattern_summary",)) == (pytest.approx(1e-6), 1)
    b = s.breakdown()
    assert b["device_ops"][0] == ["%fusion.1", pytest.approx(5e-6)]
    assert b["idle_gaps"][0] == ["bench.feed.next", pytest.approx(2e-6)]
