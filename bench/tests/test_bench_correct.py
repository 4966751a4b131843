"""``correct`` on tiny cells on the CPU: a sound watched run is correct; a
run with its timed path broken underneath is not (a frozen optimizer, half
of each batch left out, a diagnosis naming the wrong worker, a summarize
answer altered); and the controls, the reference one precision down in the
program's place (fp8 matrix products, bfloat16 summarize rows), fail the
cell's limits (on the chip it was read at each cell's own size,
PERF.md).  One file, so that these compile-heavy runs take one test worker
at a time."""
import numpy as np
import pytest

from bench import compare, faults, summary_ref
from bench import reference as R
from bench.jobs import common
from bench.tests import tiny

WATCHED, FLEET = "sc2-watched-steady", "sc2-fleet4-loaderburn"


@pytest.fixture(scope="module")
def watched():
    return tiny.cell(WATCHED)


@pytest.fixture(scope="module")
def fleet():
    return tiny.cell(FLEET)


def test_a_sound_watched_run_is_correct(watched):
    out = tiny.run(watched)
    assert out["correct"], out["checks"]
    assert out["metrics"]["tokens_per_s"]["value"] > 0
    assert list(out)[-1] == "checks"


def test_a_sound_fleet_run_compares_within_its_limits(fleet):
    out = tiny.run(fleet)
    for name, c in out["checks"].items():
        assert c["value"] <= c["limit"], name
    assert out["attempted"] == fleet.traffic["workers"]
    assert out["metrics"]["fleet_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("name", [WATCHED, FLEET])
def test_a_step_that_returns_its_state_unchanged_is_not_correct(name):
    with faults.frozen_step():
        out = tiny.run(tiny.cell(name))
    assert not out["correct"]
    assert out["checks"]["grad_gap"]["value"] == pytest.approx(1.0)
    assert out["checks"]["change_median_gap"]["value"] > 0.5


@pytest.mark.parametrize("name", [WATCHED, FLEET])
def test_half_the_batch_left_out_is_not_correct(name):
    with faults.half_batch():
        out = tiny.run(tiny.cell(name))
    assert not out["correct"]


def test_a_diagnosis_of_the_wrong_worker_is_not_correct(fleet):
    with faults.wrong_worker(fleet.traffic["workers"]):
        out = tiny.run(fleet)
    assert not out["correct"]
    assert out["failed"] == out["attempted"]


@pytest.mark.parametrize("name", [WATCHED, FLEET])
def test_the_fp8_control_fails_the_limits(name):
    cell = tiny.cell(name)
    seed = 2 ** 31 + 23
    want, = common.reference(cell, seed)
    got, = common.reference(cell, seed, mm=R.mm_fp8)
    training = {k: v for k, v in cell.limits.items()
                if k in ("loss_gap", "grad_gap", "change_median_gap")}
    ok, checks = compare.check(compare.training_numbers(got, want),
                               training)
    assert not ok
    assert checks["grad_gap"]["value"] > checks["grad_gap"]["limit"]
    same = compare.training_numbers(want, want)
    assert compare.check(same, training)[0]


def test_an_altered_summarize_answer_is_not_correct(fleet):
    with faults.altered_summary():
        out = tiny.run(fleet)
    assert not out["correct"]
    assert out["checks"]["summarize_count_mismatch"]["value"] > 0


def test_the_bf16_summarize_control_fails_the_limits(fleet):
    from repro.summarize import get_backend
    rng = np.random.default_rng(3)
    u = rng.random((16, 200)).astype(np.float32)
    u[rng.random(u.shape) < 0.3] = 0.0
    limits = {k: v for k, v in fleet.limits.items()
              if k.startswith("summarize")}

    def check(got):
        moment, miss = summary_ref.gaps(u, got)
        return compare.check({"summarize_moment_gap": moment,
                              "summarize_count_mismatch": miss}, limits)
    with faults.summary_control():
        ok, checks = check(get_backend().batch_stats(u))
    assert not ok
    gap = checks["summarize_moment_gap"]
    assert gap["value"] > gap["limit"]
    assert check(get_backend().batch_stats(u))[0]
