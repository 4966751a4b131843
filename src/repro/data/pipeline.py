"""Deterministic synthetic token pipeline with a prefetching loader.

The loader exposes ``next()`` — one of PerfTracker's two anchors. A
``delay_s`` knob injects storage slowness (used by examples/tests to
reproduce paper case C2P1 online).

Data is generated from a counting PRNG keyed by (seed, step, shard), so any
(worker, step) pair is reproducible regardless of fleet size — elastic
restarts resume mid-epoch deterministically.
"""
from __future__ import annotations

import functools
import queue
import threading
import time
from dataclasses import dataclass
from typing import Dict

import numpy as np

from repro.configs.base import ModelConfig


@dataclass
class DataConfig:
    batch: int = 8
    seq_len: int = 128
    seed: int = 1234
    shard: int = 0              # this host's DP shard index
    num_shards: int = 1
    prefetch: int = 2
    delay_s: float = 0.0        # injected storage latency (C2P1 repro)


class SyntheticLM:
    """Markov-ish synthetic token stream: next-token structure so a real
    model can overfit it (loss decreases — used in examples/train_lm.py)."""

    def __init__(self, cfg: ModelConfig, data: DataConfig):
        self.cfg = cfg
        self.data = data

    @functools.cached_property
    def _powers(self):
        """(A, G) of ``batch_at`` for k = 0 .. seq_len."""
        S, V = self.data.seq_len, self.cfg.vocab_size
        A = np.ones(S + 1, np.int64)
        G = np.zeros(S + 1, np.int64)
        for k in range(1, S + 1):
            A[k] = A[k - 1] * 31 % V
            G[k] = (G[k - 1] * 31 + 1) % V
        return A, G

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        d = self.data
        rng = np.random.default_rng(
            (d.seed, step, d.shard))
        B, S, V = d.batch, d.seq_len, self.cfg.vocab_size
        # structured stream: tok[t+1] = (31*tok[t] + 17*m) % V, restarted
        # at a random token with probability 0.05 per position.  Built
        # without a per-position loop (the loader must outpace a chip's
        # step): t tokens after its last restart r, a row holds
        # f^(t-r)(tok[r]) with f^k(x) = (A[k]*x + 17*m*G[k]) % V,
        # A[k] = 31^k and G[k] = 1 + 31 + ... + 31^(k-1), all mod V
        m = rng.integers(1, 8, B)[:, None]
        restart = rng.random((B, S + 1)) < 0.05
        restart[:, 0] = True
        fresh = rng.integers(0, V, (B, S + 1))
        A, G = self._powers
        pos = np.arange(S + 1)
        last = np.maximum.accumulate(np.where(restart, pos, 0), axis=1)
        k = pos - last
        x0 = np.take_along_axis(fresh, last, axis=1)
        x = (A[k] * x0 + 17 * m * G[k]) % V
        out = {"tokens": x[:, :-1].astype(np.int32),
               "labels": x[:, 1:].astype(np.int32)}
        if self.cfg.frontend == "audio":
            rngf = np.random.default_rng((d.seed, step, d.shard, 7))
            out = {"embeds": rngf.normal(
                size=(B, S, self.cfg.d_model)).astype(np.float32),
                "labels": out["labels"]}
        elif self.cfg.frontend == "vision":
            F = min(self.cfg.frontend_tokens, S - 1)
            rngf = np.random.default_rng((d.seed, step, d.shard, 7))
            out = {"embeds": rngf.normal(
                size=(B, F, self.cfg.d_model)).astype(np.float32),
                "tokens": out["tokens"][:, :S - F],
                "labels": out["labels"]}
        return out


class DataLoader:
    """Prefetching loader; ``next()`` is the PerfTracker anchor."""

    def __init__(self, source: SyntheticLM, start_step: int = 0):
        self.source = source
        self.step = start_step
        self._q: "queue.Queue" = queue.Queue(
            maxsize=max(1, source.data.prefetch))
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._producer, daemon=True)
        self._produce_step = start_step
        self._thread.start()

    def _producer(self):
        while not self._stop.is_set():
            b = self.source.batch_at(self._produce_step)
            self._produce_step += 1
            while not self._stop.is_set():
                try:
                    self._q.put(b, timeout=0.1)
                    break
                except queue.Full:
                    continue

    def next(self) -> Dict[str, np.ndarray]:
        if self.source.data.delay_s:
            time.sleep(self.source.data.delay_s)   # injected storage fault
        b = self._q.get()
        self.step += 1
        return b

    def close(self):
        self._stop.set()
