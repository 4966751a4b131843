"""Backend-parity invariant + the batched summarize pipeline (DESIGN.md §3-4).

python oracle == numpy == pallas(interpret) on randomized utilization
matrices (atol 1e-5), including the adversarial rows: all-zero, single
nonzero sample, and rows whose 80%-mass region is the whole window.
Plus: engine vs the per-event oracle path, unified kind resolution,
streaming aggregator vs the old dict stacking, deterministic localization.
"""
import numpy as np
import pytest

from repro.core.daemon import summarize_and_upload
from repro.core.events import FunctionEvent, Kind, SampleStream, WorkerProfile
from repro.core.localizer import Localizer
from repro.core.patterns import critical_duration, summarize_worker
from repro.core.service import PerfTrackerService
from repro.summarize import (PatternAggregator, available_backends,
                             get_backend, pack_profile, resolve_kinds,
                             summarize_profile)

BACKENDS = ["python", "numpy", "pallas"]
ATOL = 1e-5


def _rand_matrix(seed, E, n, zero_rows=(), single_rows=(), full_rows=()):
    rng = np.random.default_rng(seed)
    u = np.clip(rng.normal(0.45, 0.3, (E, n)), 0, 1).astype(np.float32)
    for _ in range(max(1, E // 4)):       # sprinkle zero bursts
        i = int(rng.integers(0, E))
        a = int(rng.integers(0, n))
        b = int(rng.integers(a, n)) + 1
        u[i, a:b] = 0
    for i in zero_rows:
        u[i] = 0.0
    for i in single_rows:
        u[i] = 0.0
        u[i, int(n * 0.6)] = 0.7
    for i in full_rows:                   # uniform: 80% mass needs it all
        u[i] = 0.5
    return u


def _backend(name):
    be = get_backend(name)
    if be.name != name:
        pytest.skip(f"backend {name} unavailable (got {be.name})")
    return be


# -- the parity invariant -----------------------------------------------------

@pytest.mark.parametrize("seed,E,n", [(0, 16, 256), (1, 8, 97), (2, 32, 130),
                                      (3, 1, 1), (4, 5, 2), (5, 24, 512)])
def test_backend_parity_randomized(seed, E, n):
    zero = [0] if E > 2 else []
    single = [1] if E > 2 and n > 2 else []
    full = [2] if E > 3 else []
    u = _rand_matrix(seed, E, n, zero, single, full)
    ref = _backend("python").batch_stats(u)
    for name in BACKENDS[1:]:
        out = _backend(name).batch_stats(u)
        np.testing.assert_allclose(
            np.asarray(out, np.float64), np.asarray(ref, np.float64),
            atol=ATOL, err_msg=f"{name} != python oracle (E={E}, n={n})")


def test_backend_parity_edge_rows():
    n = 64
    u = np.zeros((4, n), np.float32)
    u[1, 10] = 0.9                     # single sample
    u[2, :] = 0.25                     # uniform: full window is the region
    u[3, :20] = 0.8                    # contiguous burst
    ref = _backend("python").batch_stats(u)
    # all-zero row: count == full row width in every backend's report or
    # engine-normalized — here the protocol lets backends disagree only on
    # all-zero counts, which the engine overrides; compare the others hard
    for name in BACKENDS[1:]:
        out = _backend(name).batch_stats(u)
        np.testing.assert_allclose(out[1:], ref[1:], atol=ATOL,
                                   err_msg=name)
        np.testing.assert_allclose(out[0, :2], [0.0, 0.0], atol=ATOL)


def test_counts_match_scalar_oracle():
    u = _rand_matrix(7, 12, 200)
    for name in BACKENDS:
        out = _backend(name).batch_stats(u)
        for i, row in enumerate(u):
            if row.sum() <= 0:
                continue
            lo, hi = critical_duration(row)
            assert int(round(out[i, 2])) == hi - lo, (name, i)


# -- engine vs per-event oracle ----------------------------------------------

def _profile(seed=0, worker=0, with_orphan=False):
    rng = np.random.default_rng(seed)
    rate = 1000.0
    T = 4.0
    n = int(T * rate)
    gpu = np.clip(rng.normal(0.7, 0.2, n), 0, 1)
    cpu = np.clip(rng.normal(0.3, 0.2, n), 0, 1)
    gpu[1500:2100] = 0.0
    events = [
        FunctionEvent("matmul", Kind.GPU, 0.0, 1.4, worker),
        FunctionEvent("matmul", Kind.GPU, 1.5, 2.9, worker),
        FunctionEvent("allreduce", Kind.COMM, 2.0, 3.1, worker),
        FunctionEvent("data.next", Kind.PYTHON, 3.1, 3.9, worker, depth=1),
    ]
    if with_orphan:   # resource stream absent -> zero-weight pattern
        events.append(FunctionEvent("h2d", Kind.MEM, 0.2, 0.4, worker))
    return WorkerProfile(
        worker=worker, window=(0.0, T), events=events,
        streams={"gpu_sm": SampleStream(rate, 0.0, gpu),
                 "pcie_tx": SampleStream(rate, 0.0, gpu * 0.5),
                 "cpu": SampleStream(rate, 0.0, cpu)})


@pytest.mark.parametrize("backend", BACKENDS)
def test_summarize_worker_backend_parity(backend):
    _backend(backend)
    prof = _profile(with_orphan=True)
    ref = summarize_worker(prof, backend="python")
    out = summarize_worker(prof, backend=backend)
    assert set(out) == set(ref)
    assert "h2d" in out                       # orphan function still reported
    for name in ref:
        np.testing.assert_allclose(out[name].as_array(),
                                   ref[name].as_array(), atol=ATOL)


def test_prepacked_profile_matches_fresh_pack():
    prof = _profile(seed=3)
    ref = summarize_worker(prof, backend="numpy")
    prof.packed = pack_profile(prof)
    out = summarize_worker(prof, backend="numpy")
    for name in ref:
        np.testing.assert_allclose(out[name].as_array(),
                                   ref[name].as_array(), atol=0)


# -- unified kind resolution --------------------------------------------------

def test_kind_override_flows_to_stream_and_upload():
    prof = _profile()
    # reroute 'allreduce' to the CPU stream + PYTHON kind via kind_of
    override = {"allreduce": Kind.PYTHON}
    kinds = resolve_kinds(prof, override)
    assert kinds["allreduce"] == Kind.PYTHON
    assert kinds["matmul"] == Kind.GPU        # untouched functions keep kind

    pats_default, _ = summarize_profile(prof, backend="python")
    pats_override, k2 = summarize_profile(prof, kind_of=override,
                                          backend="python")
    assert k2["allreduce"] == Kind.PYTHON
    # different stream (cpu vs pcie_tx) -> different mu
    assert (abs(pats_override["allreduce"].mu - pats_default["allreduce"].mu)
            > 1e-3)

    up = summarize_and_upload(prof, kind_of=override)
    _, up_kinds = up.unpack()
    assert up_kinds["allreduce"] == Kind.PYTHON


def test_mixed_kind_function_keeps_per_event_streams():
    """A name recorded under two kinds reads each event's own stream
    (pre-refactor semantics); only explicit kind_of overrides reroute."""
    rate, T = 1000.0, 2.0
    n = int(T * rate)
    gpu = np.full(n, 0.9)
    pcie = np.full(n, 0.3)
    prof = WorkerProfile(
        worker=0, window=(0.0, T),
        events=[FunctionEvent("mixed", Kind.GPU, 0.0, 1.0),
                FunctionEvent("mixed", Kind.COMM, 1.0, 1.5)],
        streams={"gpu_sm": SampleStream(rate, 0.0, gpu),
                 "pcie_tx": SampleStream(rate, 0.0, pcie)})
    for backend in BACKENDS:
        _backend(backend)
        pats = summarize_worker(prof, backend=backend)
        # duration-weighted across the two per-event streams:
        # (1.0s * 0.9 + 0.5s * 0.3) / 1.5s
        assert pats["mixed"].mu == pytest.approx((1.0 * 0.9 + 0.5 * 0.3)
                                                 / 1.5, abs=1e-6)
    # an override forces both executions onto one stream
    pats = summarize_worker(prof, kinds={"mixed": Kind.COMM},
                            backend="python")
    assert pats["mixed"].mu == pytest.approx(0.3, abs=1e-6)


# -- streaming aggregator -----------------------------------------------------

def _legacy_aggregate(uploads):
    per_worker = [u.unpack() for u in uploads]
    names = sorted({n for pats, _ in per_worker for n in pats})
    kinds = {}
    W = len(uploads)
    agg = {n: np.zeros((W, 3), np.float32) for n in names}
    for w, (pats, ks) in enumerate(per_worker):
        for n, p in pats.items():
            agg[n][w] = p
            kinds.setdefault(n, ks[n])
    return agg, kinds


def test_aggregator_matches_legacy_stacking():
    uploads = [summarize_and_upload(_profile(seed=s, worker=s,
                                             with_orphan=(s % 2 == 0)))
               for s in range(5)]
    ref_agg, ref_kinds = _legacy_aggregate(uploads)
    agg, kinds = PatternAggregator().extend(uploads).finalize()
    assert list(agg) == list(ref_agg)          # sorted name order
    assert kinds == ref_kinds
    for n in ref_agg:
        np.testing.assert_array_equal(np.asarray(agg[n]), ref_agg[n])


def test_aggregator_growth_and_views():
    agg = PatternAggregator(expected_workers=1, expected_functions=1)
    rng = np.random.default_rng(0)
    expect = {}
    for w in range(40):                        # force repeated growth
        pats = {f"f{j}": rng.random(3).astype(np.float32)
                for j in rng.choice(20, size=5, replace=False)}
        for n, p in pats.items():
            expect.setdefault(n, {})[w] = p
        agg.add_patterns(pats, {n: Kind.GPU for n in pats})
    out, _ = agg.finalize()
    assert agg.n_workers == 40
    for n, rows in expect.items():
        for w, p in rows.items():
            np.testing.assert_array_equal(np.asarray(out[n][w]), p)
        mask = np.ones(40, bool)
        mask[list(rows)] = False
        assert not np.asarray(out[n][mask]).any()   # absent workers zero


def test_service_aggregate_is_streaming_equivalent():
    uploads = [summarize_and_upload(_profile(seed=s, worker=s))
               for s in range(4)]
    svc = PerfTrackerService()
    agg, kinds = svc.aggregate(uploads)
    ref_agg, ref_kinds = _legacy_aggregate(uploads)
    assert kinds == ref_kinds
    for n in ref_agg:
        np.testing.assert_array_equal(np.asarray(agg[n]), ref_agg[n])


# -- deterministic localization ----------------------------------------------

def _fleet_patterns(W=64, outlier=7):
    pats = np.tile(np.array([0.5, 0.9, 0.05], np.float32), (W, 1))
    pats[outlier] = [0.9, 0.3, 0.05]
    return pats


def test_delta_distance_order_independent():
    pats = _fleet_patterns(W=256)
    loc = Localizer()
    d1 = loc.delta_distance(pats, function="fwd")
    # interleave calls for other functions: must not perturb 'fwd'
    loc.delta_distance(pats, function="bwd")
    loc.delta_distance(pats, function="opt")
    d2 = loc.delta_distance(pats, function="fwd")
    np.testing.assert_array_equal(d1, d2)
    # a fresh Localizer reproduces the same Delta exactly
    np.testing.assert_array_equal(
        d1, Localizer().delta_distance(pats, function="fwd"))


def test_localize_independent_of_dict_order():
    pats_a = _fleet_patterns(W=256, outlier=3)
    pats_b = _fleet_patterns(W=256, outlier=9)
    kinds = {"a": Kind.GPU, "b": Kind.GPU}
    fwd = Localizer().localize({"a": pats_a, "b": pats_b}, kinds)
    rev = Localizer().localize({"b": pats_b, "a": pats_a}, kinds)
    assert {x.function: x.workers.tolist() for x in fwd} == \
           {x.function: x.workers.tolist() for x in rev}
    np.testing.assert_array_equal(
        *[sorted(x.delta.tolist() for x in r) for r in (fwd, rev)])


# -- end to end ---------------------------------------------------------------

@pytest.mark.parametrize("backend", ["python", "numpy"])
def test_service_end_to_end_backend_choice(backend):
    profiles = [_profile(seed=s, worker=s) for s in range(6)]
    svc = PerfTrackerService(summarize_backend=backend)
    res = svc.diagnose_profiles(profiles)
    assert res.fleet_size == 6
    assert res.pattern_bytes > 0 and res.raw_bytes > res.pattern_bytes
    assert "summarize_s" in res.timing


def test_available_backends_reports_all_three():
    names = available_backends()
    assert "python" in names and "numpy" in names
    # pallas present in this image (jax + interpret mode)
    assert "pallas" in names


def test_pallas_that_fails_to_load_raises(monkeypatch):
    """With jax installed, a kernel that cannot load is an error, never a
    silent drop to the host backends."""
    from repro.summarize.backends import PallasBackend

    def broken(self):
        raise ImportError("kernel module is broken")
    monkeypatch.setattr(PallasBackend, "_modules", broken)
    with pytest.raises(ImportError, match="broken"):
        PallasBackend().available()


def test_pallas_buckets_shapes_and_interprets_off_tpu():
    """Off the TPU the kernel interprets; every call pads (E, n) to
    power-of-two buckets, so varying windows reuse a few shapes."""
    import jax
    from repro.summarize.backends import PallasBackend
    be = PallasBackend()
    assert be.interpret() == (jax.default_backend() != "tpu")
    ref = get_backend("numpy")
    rng = np.random.default_rng(1)
    for E, n in [(3, 100), (5, 120), (9, 300)]:
        u = np.clip(rng.normal(0.4, 0.3, (E, n)), 0, 1).astype(np.float32)
        u[0] = 0.0
        out, want = be.batch_stats(u), ref.batch_stats(u)
        np.testing.assert_allclose(out[:, :2], want[:, :2], atol=1e-5)
        np.testing.assert_array_equal(out[:, 2], want[:, 2])
    assert be.shapes == {(8, 128), (16, 512)}
