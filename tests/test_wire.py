"""ISSUE 4: real wire transport for the PerfTracker daemon (DESIGN.md §8).

Three layers of coverage:

  * framing/queue/collector units — length-prefixed reassembly at hostile
    recv boundaries, the bounded drop-oldest send queue, and window
    assembly under injected loss/duplication at the framing layer;
  * the service wire path — ``diagnose_profiles(mode="wire")`` over real
    Unix-socket connections, partial-window degradation, and transport
    counters surfaced in the report;
  * ``@pytest.mark.wire`` multi-process integration — ``n_procs`` spawned
    daemon processes reproduce the in-process fleet mode's confirmed
    culprit sets across the six-fault matrix, with and without 10%
    injected upload loss (the CI ``wire`` job runs exactly these).
"""
import threading
import time

import numpy as np
import pytest

from repro.core import faults as F
from repro.core.daemon import PerfTrackerDaemon, summarize_and_upload
from repro.core.events import FunctionEvent, Kind, SampleStream, WorkerProfile
from repro.core.localizer import Localizer
from repro.core.service import PerfTrackerService
from repro.core.simulation import (ALLGATHER, DATALOADER_STACK, FORWARD_STACK,
                                   GC_STACK, GEMM, FleetSimulator, SimConfig)
from repro.online import (EmaPatternAggregator, EscalationPolicy,
                          ScenarioRunner, ScheduledFault)
from repro.summarize import PatternAggregator, summarize_fleet
from repro.transport import (DaemonServer, FrameDecoder, LoopbackWire,
                             SendQueue, WindowCollector, WireClient,
                             decode_frames, encode_frame)
from repro.transport import framing


# -- framing ------------------------------------------------------------------

def test_frame_roundtrip():
    msgs = [framing.hello_msg(3),
            framing.window_start_msg(2, rates=[250.0, 2000.0]),
            {"t": "upload", "window": 1, "worker": 7, "seq": 0,
             "payload": b"\x00\x01\xffbinary", "summarize_s": 0.25,
             "raw_bytes": 12345}]
    blob = b"".join(encode_frame(m) for m in msgs)
    assert decode_frames(blob) == msgs


def test_frame_decoder_survives_any_recv_boundary():
    msgs = [framing.bye_msg(w) for w in range(5)]
    blob = b"".join(encode_frame(m) for m in msgs)
    # feed one byte at a time: every frame must pop exactly once, at the
    # arrival of its final byte
    dec = FrameDecoder()
    got = []
    for i in range(len(blob)):
        got += list(dec.feed(blob[i:i + 1]))
    assert got == msgs
    assert dec.pending_bytes == 0


def test_frame_decoder_multiple_frames_single_feed():
    msgs = [framing.hello_msg(w) for w in range(4)]
    dec = FrameDecoder()
    got = list(dec.feed(b"".join(encode_frame(m) for m in msgs)))
    assert got == msgs


def test_decode_frames_rejects_trailing_partial():
    blob = encode_frame(framing.hello_msg(0)) + b"\x00\x00"
    with pytest.raises(ValueError):
        decode_frames(blob)


def test_frame_decoder_rejects_oversized_length():
    bad = (framing.MAX_FRAME_BYTES + 1).to_bytes(4, "big") + b"x"
    with pytest.raises(ValueError):
        list(FrameDecoder().feed(bad))


def test_encode_frame_rejects_oversized_body():
    with pytest.raises(ValueError):
        encode_frame({"t": "upload",
                      "payload": b"x" * (framing.MAX_FRAME_BYTES + 1)})


# -- bounded send queue (backpressure policy) ---------------------------------

def test_send_queue_drops_oldest_upload():
    q = SendQueue(max_uploads=3)
    for i in range(5):
        q.put({"seq": i})
    assert q.dropped == 2
    got = [q.pop()[1]["seq"] for _ in range(3)]
    assert got == [2, 3, 4]          # oldest evicted, newest kept


def test_send_queue_never_drops_control_frames():
    q = SendQueue(max_uploads=2)
    q.put({"t": "hello"}, droppable=False)
    for i in range(6):
        q.put({"seq": i})
    q.put({"t": "window_end"}, droppable=False)
    kinds = []
    while (item := q.pop()) is not None:
        kinds.append(item[0])
    assert kinds == [False, True, True, False]
    assert q.dropped == 4


def _upload(worker, window_s=1.0, beta=0.5):
    """A tiny real PatternUpload."""
    n = 100
    prof = WorkerProfile(
        worker=worker, window=(0.0, window_s),
        events=[FunctionEvent("matmul", Kind.GPU, 0.0, beta * window_s,
                              worker)],
        streams={"gpu_sm": SampleStream(n / window_s, 0.0,
                                        np.full(n, 0.8))})
    return summarize_and_upload(prof, backend="numpy")


def test_client_backpressure_drops_oldest_counts_on_wire():
    """A stalled wire (blocking frame filter) fills the bounded queue; the
    oldest unsent windows drop, and the window_end frame — snapshotted at
    SEND time — carries the final counters to the collector."""
    gate, stalled = threading.Event(), threading.Event()

    def stall(msg, frame):
        stalled.set()
        gate.wait(timeout=30.0)
        return None

    collector = WindowCollector([0])
    with DaemonServer(collector) as server:
        client = WireClient(server.address, worker=0, max_queue=2,
                            frame_filter=stall)
        try:
            client.send_upload(0, _upload(0))
            # queue the rest only once the sender holds window 0
            assert stalled.wait(timeout=10.0)
            for w in range(1, 6):
                client.send_upload(w, _upload(0))
            # sender thread is stalled inside window 0's filter; of the 5
            # queued behind it, only the newest 2 survive
            deadline = time.monotonic() + 5.0
            while client.dropped < 3 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert client.dropped == 3
            client.end_window(5)
            gate.set()
            assert client.flush(timeout=10.0)
            batch = collector.wait_window(5, timeout=10.0)
        finally:
            gate.set()
            client.close()
    assert batch.client_dropped == 3
    # the NEWEST windows survived the eviction: window 5's upload arrived
    assert batch.present == [0] and not batch.timed_out


# -- collector: loss, duplication, dedup --------------------------------------

def _loopback_batch(n_workers, frame_filter=None, window=0):
    uploads = [_upload(w) for w in range(n_workers)]
    with LoopbackWire(range(n_workers), frame_filter=frame_filter) as wire:
        return wire.send_round(uploads, window=window, timeout=15.0)


def test_collector_assembles_full_window():
    batch = _loopback_batch(6)
    assert batch.present == list(range(6))
    assert batch.complete and not batch.timed_out
    assert batch.duplicates == 0 and batch.missing == []


def test_collector_dedups_duplicated_frames():
    def dup(msg, frame):
        return [frame, frame, frame] if msg["worker"] == 2 else None
    batch = _loopback_batch(5, frame_filter=dup)
    assert batch.present == list(range(5))
    assert batch.duplicates == 2          # first copy kept, rest counted


def test_collector_tolerates_dropped_uploads():
    def drop(msg, frame):
        return [] if msg["worker"] in (1, 3) else None
    batch = _loopback_batch(5, frame_filter=drop)
    assert batch.missing == [1, 3]
    assert batch.present == [0, 2, 4]
    assert not batch.timed_out            # window_end frames still closed it
    mask = batch.present_mask(5)
    np.testing.assert_array_equal(mask, [True, False, True, False, True])


def test_anchors_frame_slo_parity():
    """ISSUE 10 satellite: the ``slo`` field rides the anchors frame with
    the same present-only-when-provided contract ``numerics`` has — a
    workload without the stream produces BYTE-identical frames to the
    historical format, and the collector parses it into ``batch.slo``."""
    durs = [0.5, 0.6]
    pairs = [(0.21, 0.013), (0.19, 0.011)]
    msg = framing.anchors_msg(3, 7, durs, slo=pairs)
    (back,) = decode_frames(encode_frame(msg))
    assert back["slo"] == [[0.21, 0.013], [0.19, 0.011]]
    assert "numerics" not in back
    # absent stream -> byte-identical legacy frame
    legacy = framing.anchors_msg(3, 7, durs)
    assert "slo" not in legacy and "numerics" not in legacy
    assert encode_frame(legacy) == encode_frame(
        {"t": "anchors", "window": 3, "worker": 7, "durs": durs})
    # collector side: slo lands beside anchors/numerics, first copy wins
    collector = WindowCollector([7])
    collector.on_message(msg)
    collector.on_message(framing.anchors_msg(3, 7, [9.9], slo=[(1.0, 1.0)]))
    collector.on_message({"t": "window_end", "window": 3, "worker": 7,
                          "sent": 1, "dropped": 0})
    batch = collector.wait_window(3, timeout=1.0)
    assert batch.anchors[7] == durs
    assert batch.slo == {7: pairs}
    assert batch.numerics == {}


def test_collector_timeout_reports_never_ended_worker():
    collector = WindowCollector([0, 1])
    collector.on_message({"t": "window_end", "window": 0, "worker": 0,
                          "sent": 0, "dropped": 0})
    t0 = time.monotonic()
    batch = collector.wait_window(0, timeout=0.2)
    assert time.monotonic() - t0 < 5.0
    assert batch.timed_out and batch.missing == [0, 1]


# -- service wire mode over the real transport --------------------------------

def _sim_profiles(W=16, faults=(), seed=7):
    sim = FleetSimulator(SimConfig(n_workers=W, window_s=1.0, rate_hz=1000,
                                   seed=seed), list(faults))
    return sim.profile_window()


def test_wire_mode_loss_degrades_instead_of_crashing():
    """Dropping healthy workers' uploads must not break localization of
    the real culprits — and the report must surface the transport holes."""
    profiles = _sim_profiles(W=16, faults=[F.GpuThrottle(workers=(3, 5))])

    def drop(msg, frame):
        return [] if msg["worker"] in (0, 9) else None
    svc = PerfTrackerService(summarize_backend="numpy",
                             wire_frame_filter=drop)
    res = svc.diagnose_profiles(profiles, mode="wire")
    d = next(d for d in res.diagnoses if d.abnormality.function == GEMM)
    assert {3, 5} <= set(d.abnormality.workers.tolist())
    assert res.transport["missing"] == [0, 9]
    assert res.transport["present"] == 14
    assert "transport: 14/16 workers reported" in res.report()
    assert "missing=[0, 9]" in res.report()


def test_wire_mode_drop_counter_in_report():
    profiles = _sim_profiles(W=4)
    svc = PerfTrackerService(summarize_backend="numpy")
    res = svc.diagnose_profiles(profiles, mode="wire")
    assert res.transport["client_dropped"] == 0
    assert "dropped=0" in res.report()


def test_daemon_process_window_uploads_over_wire():
    collector = WindowCollector([4])
    with DaemonServer(collector) as server:
        daemon = PerfTrackerDaemon(4, server.address, backend="numpy")
        try:
            prof = _sim_profiles(W=5)[4]
            up = daemon.process_window(0, prof)
            batch = collector.wait_window(0, timeout=10.0)
        finally:
            daemon.close()
    assert batch.present == [4]
    assert batch.uploads[4].payload == up.payload


# -- partial-fleet threading: aggregator / summarize_fleet / EMA / localizer --

def test_aggregator_set_row_places_partial_fleet():
    agg = PatternAggregator(expected_workers=4)
    agg.reserve_workers(4)
    agg.set_row(2, {"f": np.array([0.5, 0.6, 0.1], np.float32)},
                {"f": Kind.GPU})
    pats, kinds = agg.finalize()
    np.testing.assert_allclose(pats["f"][2], [0.5, 0.6, 0.1])
    np.testing.assert_allclose(pats["f"][[0, 1, 3]], 0.0)
    assert kinds["f"] == Kind.GPU
    with pytest.raises(ValueError):
        agg.set_row(7, {"f": np.zeros(3, np.float32)})


def test_summarize_fleet_partial_scatters_to_global_rows():
    profiles = _sim_profiles(W=6)
    full = summarize_fleet(profiles, backend="numpy").agg.finalize()[0]
    sub = [profiles[1], profiles[4]]
    fs = summarize_fleet(sub, backend="numpy", workers=[1, 4], fleet_size=6)
    part = fs.agg.finalize()[0]
    for name in full:
        np.testing.assert_array_equal(np.asarray(part[name])[[1, 4]],
                                      np.asarray(full[name])[[1, 4]])
        np.testing.assert_array_equal(np.asarray(part[name])[[0, 2, 3, 5]],
                                      0.0)
    with pytest.raises(ValueError):
        summarize_fleet(sub, backend="numpy", workers=[1, 9], fleet_size=6)
    # regression (review): a negative id must raise, not wrap into the
    # last worker's row via numpy negative indexing
    with pytest.raises(ValueError):
        summarize_fleet(sub, backend="numpy", workers=[-1, 4], fleet_size=6)


def test_ema_fold_present_freezes_absent_rows():
    def agg_of(vals):
        a = PatternAggregator(expected_workers=3)
        a.reserve_workers(3)
        a.intern("f", Kind.GPU)
        a.scatter_block(0, np.asarray(vals, np.float32).reshape(3, 1, 3))
        return a
    ema = EmaPatternAggregator(3, alpha=0.5)
    ema.fold(agg_of([[0.4, 0.8, 0.1]] * 3))
    ema.fold(agg_of([[0.8, 0.4, 0.3]] * 3),
             present=np.array([True, False, True]))
    pats, _ = ema.finalize()
    np.testing.assert_allclose(pats["f"][0], [0.6, 0.6, 0.2], rtol=1e-6)
    np.testing.assert_allclose(pats["f"][2], [0.6, 0.6, 0.2], rtol=1e-6)
    # absent worker 1: frozen at its last smoothed value, no decay
    np.testing.assert_allclose(pats["f"][1], [0.4, 0.8, 0.1], rtol=1e-6)


def test_ema_returning_worker_gets_full_value_not_ramp():
    """Regression (review): a worker absent when a column FIRST appeared
    must initialize at full value on its own first evidence — not an
    alpha-scaled ramp from the zero it never reported."""
    def agg_of(vals):
        a = PatternAggregator(expected_workers=2)
        a.reserve_workers(2)
        a.intern("g", Kind.GPU)
        a.scatter_block(0, np.asarray(vals, np.float32).reshape(2, 1, 3))
        return a
    ema = EmaPatternAggregator(2, alpha=0.3)
    # window 0: column g first appears, worker 1's upload was dropped
    ema.fold(agg_of([[0.9, 0.9, 0.1], [0.0, 0.0, 0.0]]),
             present=np.array([True, False]))
    # window 1: worker 1 reports g for the first time
    ema.fold(agg_of([[0.9, 0.9, 0.1], [0.9, 0.9, 0.1]]))
    pats, _ = ema.finalize()
    np.testing.assert_allclose(pats["g"][1], [0.9, 0.9, 0.1], rtol=1e-6)
    np.testing.assert_allclose(pats["g"][0], [0.9, 0.9, 0.1], rtol=1e-6)


def test_collector_drops_straggler_frames_for_popped_windows():
    """Regression (review): uploads arriving AFTER their window was handed
    out must not resurrect the batch (unbounded memory over a long run)."""
    collector = WindowCollector([0, 1])
    for w in (0, 1):
        collector.on_message({"t": "window_end", "window": 0, "worker": w,
                              "sent": 1, "dropped": 0})
    collector.wait_window(0, timeout=1.0)
    # straggler upload for the already-popped window 0
    collector.on_message(framing.upload_msg(0, _upload(1), seq=9))
    assert collector.stale_frames == 1
    assert collector._batches == {}


def test_ema_fold_all_present_mask_identical_to_default():
    def agg_of():
        a = PatternAggregator(expected_workers=2)
        a.reserve_workers(2)
        a.intern("f", Kind.GPU)
        a.scatter_block(0, np.full((2, 1, 3), 0.5, np.float32))
        return a
    a_ = EmaPatternAggregator(2, alpha=0.6)
    b_ = EmaPatternAggregator(2, alpha=0.6)
    for _ in range(3):
        a_.fold(agg_of())
        b_.fold(agg_of(), present=np.array([True, True]))
    np.testing.assert_array_equal(a_.matrix()[0], b_.matrix()[0])


def test_localizer_present_mask_reports_global_ids():
    W = 10
    pats = np.tile(np.array([0.5, 0.9, 0.05], np.float32), (W, 1))
    pats[7] = [0.9, 0.1, 0.05]        # the real outlier
    pats[2] = 0.0                     # absent worker: zero row
    pats[5] = 0.0
    present = np.ones(W, bool)
    present[[2, 5]] = False
    abn = Localizer().localize({"f": pats}, {"f": Kind.GPU},
                               present=present)
    assert len(abn) == 1
    assert abn[0].workers.tolist() == [7]     # global id survives masking
    # absent rows are excluded from the typical-pattern median
    np.testing.assert_allclose(abn[0].typical, [0.5, 0.9, 0.05])


def test_localizer_full_present_identical_to_default():
    pats = np.tile(np.array([0.5, 0.9, 0.05], np.float32), (8, 1))
    pats[3] = [0.95, 0.05, 0.01]
    a = Localizer().localize({"f": pats.copy()}, {"f": Kind.GPU})
    b = Localizer().localize({"f": pats.copy()}, {"f": Kind.GPU},
                             present=np.ones(8, bool))
    assert len(a) == len(b) == 1
    np.testing.assert_array_equal(a[0].workers, b[0].workers)
    np.testing.assert_array_equal(a[0].delta, b[0].delta)


# -- multi-process integration (the CI `wire` job: pytest -m wire) ------------

W_MP = 32
INJECT, REMOVE = 2, 6
N_WINDOWS = 9
BASE_HZ, FULL_HZ = 250.0, 2000.0

#: (fault, expected incident function, culprit workers or None=fleet-wide)
MP_SCENARIOS = [
    pytest.param(F.GpuThrottle(workers=(3, 11)), GEMM, {3, 11},
                 id="C1P1_gpu_throttle"),
    pytest.param(F.NvlinkDown(workers=[5], group_size=8), ALLGATHER, {5},
                 id="C1P2_nvlink_down"),
    pytest.param(F.RingSlowLink(slow_worker=9, rho=0.4), ALLGATHER, {9},
                 id="S3_ring_slow_link"),
    pytest.param(F.SlowDataloader(), DATALOADER_STACK, None,
                 id="C2P1_slow_dataloader"),
    pytest.param(F.CpuBoundForward(workers=range(6)), FORWARD_STACK,
                 set(range(6)), id="C2P2_cpu_forward"),
    pytest.param(F.AsyncGc(probability=0.5, pause_s=0.25), GC_STACK, None,
                 id="C2P3_async_gc"),
]


def _mp_runner(fault, seed=5):
    esc = EscalationPolicy(n_workers=W_MP, base_rate_hz=BASE_HZ,
                           full_rate_hz=FULL_HZ)
    return ScenarioRunner(
        SimConfig(n_workers=W_MP, window_s=1.0, rate_hz=FULL_HZ, seed=seed),
        [ScheduledFault(fault, INJECT, REMOVE)],
        n_windows=N_WINDOWS, escalation=esc)


def _culprit_sets(res):
    """{function: frozenset(workers)} over confirmed-or-later incidents."""
    return {i.function: frozenset(i.workers)
            for i in res.incidents if i.function}


def _wire_log_path(tmp_path):
    import os
    return os.environ.get("REPRO_WIRE_LOG",
                          str(tmp_path / "wire-collector.log"))


@pytest.mark.wire
@pytest.mark.timeout(300)
@pytest.mark.parametrize("fault,expect,culprits", MP_SCENARIOS)
def test_multiprocess_matches_inprocess_fleet(fault, expect, culprits,
                                              tmp_path):
    """Acceptance: >=4 real worker processes, W>=32, same confirmed
    culprit sets as the in-process mode="fleet" pipeline."""
    res_in = _mp_runner(fault).run()
    res_mp = _mp_runner(fault).run_multiprocess(
        n_procs=4, log_path=_wire_log_path(tmp_path))
    assert _culprit_sets(res_mp) == _culprit_sets(res_in)
    incs = [i for i in res_mp.incidents if i.function == expect]
    assert incs, (expect, [i.function for i in res_mp.incidents])
    if culprits is not None:
        assert culprits <= set(incs[0].workers)
    wire = res_mp.wire_summary()
    assert wire["delivered"] == wire["expected"]     # lossless loopback
    assert wire["partial_windows"] == 0


@pytest.mark.wire
@pytest.mark.timeout(300)
@pytest.mark.parametrize("fault,expect,culprits", MP_SCENARIOS)
def test_multiprocess_10pct_loss_still_localizes(fault, expect, culprits,
                                                 tmp_path):
    """Acceptance: 10% injected upload loss, every fault still localized
    with its culprits, and the holes surfaced in the window reports."""
    res = _mp_runner(fault).run_multiprocess(
        n_procs=4, loss=0.10, log_path=_wire_log_path(tmp_path))
    incs = [i for i in res.incidents if i.function == expect]
    assert incs, (expect, [i.function for i in res.incidents])
    if culprits is not None:
        assert culprits <= set(incs[0].workers)
    wire = res.wire_summary()
    assert wire["delivered"] < wire["expected"]      # loss actually bit
    assert wire["partial_windows"] > 0
    # drop counters surface in the per-window incident report text
    partial = next(r for r in res.reports if r.transport["missing"])
    txt = partial.report(W_MP)
    assert "transport:" in txt and "missing=" in txt


@pytest.mark.wire
@pytest.mark.timeout(300)
def test_multiprocess_escalation_rates_cross_process(tmp_path):
    """The parent's escalation decision rides the window_start broadcast:
    culprit workers' profiles come back sampled at the full rate."""
    res = _mp_runner(F.GpuThrottle(workers=(3, 11))).run_multiprocess(
        n_procs=4, log_path=_wire_log_path(tmp_path))
    mid = res.reports[INJECT + 1]
    assert {3, 11} <= set(mid.escalated)
    assert mid.rates[3] == FULL_HZ and mid.rates[0] == BASE_HZ
    # the raw bytes the children actually materialized reflect the split
    assert res.reports[0].raw_bytes < W_MP * FULL_HZ * 1.0 * 4 * 8
