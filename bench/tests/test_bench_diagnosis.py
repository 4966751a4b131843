"""``diagnosis_s`` is a mean over every incident of the window, and an
incident that no report names on its worker while its fault lasts, or
whose incident does not plan ``migrate_dataloader``, counts in
``failed``."""
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench.jobs import fleet
from bench.faultcycle import Incident
from repro.core.mitigation import Action


def _report(index, *named):
    return NS(index=index, t=float(index), diagnoses=[
        NS(abnormality=NS(function=f, workers=np.asarray(w)))
        for f, w in named])


def _incident(opened, resolved, workers, *actions,
              function="dataloader.next"):
    return NS(function=function, opened_at=float(opened),
              resolved_at=None if resolved is None else float(resolved),
              workers_seen=tuple(workers),
              plans=[NS(action=a) for a in actions])


def test_diagnosis_is_the_mean_over_incidents_and_misses_fail():
    faults = [Incident(1, 2, 5), Incident(3, 6, 9), Incident(0, 10, 13),
              Incident(2, 14, 17)]
    reports = [_report(i) for i in range(18)]
    reports[2] = _report(2, ("dataloader.next", [1]))
    # the second fault is named a window late, on its worker alone
    reports[6] = _report(6, ("dataloader.next", [1, 3]))
    reports[7] = _report(7, ("train.step", [3]), ("dataloader.next", [3]))
    # the third is only ever named on the wrong worker
    reports[10] = _report(10, ("dataloader.next", [2]))
    # the fourth is named only once its fault has ended
    reports[17] = _report(17, ("dataloader.next", [2]))
    pipeline = [_incident(2, 4, [1], Action.MIGRATE_DATALOADER),
                _incident(6, None, [1, 3], Action.MIGRATE_DATALOADER),
                _incident(17, None, [2], Action.MIGRATE_DATALOADER),
                _incident(10, 12, [2], Action.MIGRATE_DATALOADER)]
    start = {w: {k: w * 10.0 + k for k in range(4)} for w in range(18)}
    tick_end = {w: w * 10.0 + 9.0 for w in range(18)}
    acc = fleet.account(faults, reports, pipeline, start, tick_end)
    assert acc[0] == (pytest.approx(29.0 - 21.0), 1)
    assert acc[1] == (pytest.approx(79.0 - 63.0), 2)
    assert acc[2] == (None, None) and acc[3] == (None, None)
    mean, failed, windows = fleet.tally(acc)
    assert mean == pytest.approx((8.0 + 16.0) / 2)
    assert failed == 2
    assert windows == [1, 2]


def test_a_naming_without_a_migration_plan_fails():
    faults = [Incident(0, 2, 5)]
    reports = [_report(i) for i in range(6)]
    reports[2] = _report(2, ("dataloader.next", [0]))
    start = {w: {0: float(w)} for w in range(6)}
    ends = {w: w + 0.5 for w in range(6)}
    for pipeline in ([],
                     [_incident(2, None, [0], Action.REPLACE_HOSTS)],
                     [_incident(5, None, [0], Action.MIGRATE_DATALOADER)],
                     [_incident(0, 1, [0], Action.MIGRATE_DATALOADER)],
                     [_incident(2, None, [0], Action.MIGRATE_DATALOADER,
                                function="train.step")]):
        acc = fleet.account(faults, reports, pipeline, start, ends)
        assert fleet.tally(acc) == (None, 1, [])
    ok = [_incident(2, None, [0], Action.MIGRATE_DATALOADER)]
    acc = fleet.account(faults, reports, ok, start, ends)
    assert fleet.tally(acc) == (pytest.approx(0.5), 0, [1])


def test_an_incident_opened_later_in_the_fault_counts():
    # the detector's trigger can open the pipeline's incident a window
    # after the first report that names the worker; it still plans the
    # migration while the fault lasts
    faults = [Incident(1, 2, 5)]
    reports = [_report(i) for i in range(6)]
    for i in (2, 3, 4):
        reports[i] = _report(i, ("dataloader.next", [1]))
    pipeline = [_incident(3.5, 4.5, [1], Action.MIGRATE_DATALOADER)]
    start = {w: {1: float(w)} for w in range(6)}
    ends = {w: w + 0.5 for w in range(6)}
    acc = fleet.account(faults, reports, pipeline, start, ends)
    assert fleet.tally(acc) == (pytest.approx(0.5), 0, [1])
