"""Faults planted in the program underneath a run, for the checks that
``correct`` comes out false when the timed path is broken (the tests in
``bench/tests`` and the readings of ``bench/calibrate.py``).  Each is a
context manager that patches the program while it is open; none is ever
used by a benchmark run.

- ``frozen_step``: the optimizer returns the state it was given;
- ``half_batch``: the feed leaves half of each batch out of the loss (its
  labels masked), so the mean is taken over the rest;
- ``wrong_worker``: a diagnosis of the loader names the next worker
  instead of the one it found;
- ``altered_summary``: the summarize step's answer for the first row of
  every call is one sample longer than the one it computed;
- ``summary_control``: the plain reference of the summarize step one
  precision down, on the rows rounded to bfloat16, in the place of the
  program's (the control its limits have to fail).
"""
from __future__ import annotations

from contextlib import contextmanager

import numpy as np


@contextmanager
def frozen_step():
    from repro.train import loop
    fused, split = loop.make_train_step, loop.make_split_train_step

    def frozen_fused(model, opt, accum_steps=1):
        real = fused(model, opt, accum_steps)

        def step(params, opt_state, batch):
            _, _, metrics = real(params, opt_state, batch)
            return params, opt_state, metrics
        return step

    def frozen_split(model, opt):
        grad, upd = split(model, opt)

        def opt_step(grads, opt_state, params):
            _, _, metrics = upd(grads, opt_state, params)
            return params, opt_state, metrics
        return grad, opt_step

    loop.make_train_step, loop.make_split_train_step = (frozen_fused,
                                                        frozen_split)
    try:
        yield
    finally:
        loop.make_train_step, loop.make_split_train_step = fused, split


def mask_half(batch: dict) -> dict:
    labels = np.array(batch["labels"], copy=True)
    if labels.shape[0] > 1:
        labels[labels.shape[0] // 2:] = -1
    else:
        labels[:, labels.shape[1] // 2:] = -1
    return dict(batch, labels=labels)


@contextmanager
def half_batch():
    from repro.data import pipeline
    real = pipeline.DataLoader.next

    def next_half(self):
        return mask_half(real(self))

    pipeline.DataLoader.next = next_half
    try:
        yield
    finally:
        pipeline.DataLoader.next = real


@contextmanager
def wrong_worker(n_workers: int, function: str = "dataloader.next"):
    from repro.online import pipeline
    real = pipeline.build_report

    def build_report(abn, fleet_size):
        for a in abn:
            if a.function == function:
                a.workers = (np.asarray(a.workers) + 1) % n_workers
        return real(abn, fleet_size)

    pipeline.build_report = build_report
    try:
        yield
    finally:
        pipeline.build_report = real


@contextmanager
def _summary(patch):
    """The summarize backend the fleet's pipeline uses, with its
    ``batch_stats`` replaced by ``patch(real, u)``."""
    from repro.summarize import get_backend
    kind = type(get_backend())
    real = kind.batch_stats
    kind.batch_stats = lambda self, u: patch(lambda x: real(self, x), u)
    try:
        yield
    finally:
        kind.batch_stats = real


def altered_summary():
    def patch(real, u):
        out = np.array(real(u), np.float64, copy=True)
        out[0, 2] += 1
        return out
    return _summary(patch)


def summary_control():
    import ml_dtypes
    from bench import summary_ref

    def patch(real, u):
        low = np.asarray(u, np.float32).astype(ml_dtypes.bfloat16)
        return summary_ref.stats(low.astype(np.float32))
    return _summary(patch)
