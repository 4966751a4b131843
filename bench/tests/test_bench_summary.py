"""The plain reference of the summarize step (``bench/summary_ref.py``):
Algorithm 1 worked by hand, the same answers as the program's own
row-at-a-time oracle, and the rows whose decision rounds either way."""
import numpy as np
import pytest

from bench import summary_ref as S


def test_a_row_worked_by_hand():
    # mass 10; at g = 0 the regions [1, 3), [4, 6) and [8, 9) hold 3, 6
    # and 1, none 8; at g = 1 [1, 6) holds 9: the critical duration
    u = np.array([0, 1, 2, 0, 3, 3, 0, 0, 1, 0], np.float32)
    (mean, std, n), = S.answers(u)
    x = u[1:6].astype(np.float64)
    assert n == 5
    assert mean == pytest.approx(x.mean()) and std == pytest.approx(x.std())


def test_a_row_with_no_mass_spans_the_row():
    assert S.answers(np.zeros(7, np.float32)) == [(0.0, 0.0, 7)]


def test_the_same_answers_as_the_program_oracle():
    from repro.summarize import get_backend
    oracle = get_backend("python")
    rng = np.random.default_rng(5)
    for t in range(40):
        u = rng.random((8, int(rng.integers(1, 300)))).astype(np.float32)
        u[rng.random(u.shape) < rng.random()] = 0.0
        moment, miss = S.gaps(u, oracle.batch_stats(u))
        assert miss == 0 and moment < 1e-12, t


def test_a_row_at_the_threshold_accepts_either_rounding():
    # 8 of 10 equal samples hold exactly 80%: at g = 0 [0..7] qualifies,
    # one rounding down and it does not, and at g = 2 the whole row does
    u = np.array([1, 1, 1, 1, 1, 1, 1, 1, 0, 0, 0, 1, 1], np.float32)
    ok = S.answers(u)
    assert {a[2] for a in ok} == {8, 13}
    assert S.gaps(u[None], [[1.0, 0.0, 8]]) == (0.0, 0)
    assert S.gaps(u[None], [[ok[1][0], ok[1][1], 13]]) == (0.0, 0)
    assert S.gaps(u[None], [[1.0, 0.0, 9]])[1] == 1


def test_an_answer_that_is_not_a_number_fails():
    u = np.array([[0.5, 0.25, 0.0, 1.0]], np.float32)
    want = S.stats(u)
    got = np.array(want, copy=True)
    got[0, 0] = np.nan
    assert S.gaps(u, want) == (0.0, 0)
    assert S.gaps(u, got)[0] == float("inf")
