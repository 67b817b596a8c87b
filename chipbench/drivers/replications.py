"""Replications driver: the paper's experiment, back to back. Each
replication is ``n_samples`` independent samples of ``sample_len`` requests,
every sample on a cold cache, through ``jax_cache.simulate_batch``.

Closed loop with ``IN_FLIGHT`` replications in flight: the host dispatches
replication ``r + 2``'s ids and its simulation, then blocks on replication
``r``'s hits and copies them to the host, so the chip has two replications
(some two seconds) queued whenever the host stalls. Set-up runs one
replication, which compiles.

Correctness: once the window has closed, ``CHECK_SAMPLES`` samples drawn
from the seed among all replications run come off the device and are
replayed through the plain reference policy; every request's hit bit must
be equal."""
from __future__ import annotations

import collections
import importlib
import random
import time

import numpy as np

from chipbench.generator import Traffic

#: samples replayed through the reference after the window: 1.2M requests,
#: about a second of plain Python
CHECK_SAMPLES = 12

#: replications dispatched and not yet copied back
IN_FLIGHT = 3


class Driver:
    def __init__(self, cell, seed: int, span):
        from repro.core import jax_cache

        cfg, traffic = cell.config, cell.traffic
        if [int(w) for w in cfg["widths"]] != [1]:
            raise ValueError("the replications driver runs one flat cache")
        self.config = cfg
        self.kind = cfg["kinds"][0]
        self.spec = jax_cache.PolicySpec(
            self.kind, int(cfg["n_objects"]), int(cfg["capacities"][0]),
            hot_size=int(cfg.get("hot_size", [0])[0]),
        )
        self.traffic = Traffic(cfg, traffic, seed)
        self.S, self.T = self.traffic.shape
        self.seed = seed
        self.span = span
        self.traces = []  # device ids of every replication, in order
        self.hits = []  # host (S, T) hits of every replication
        self.window_from = 0

    def _loop(self, stop):
        from repro.core import jax_cache

        pending = collections.deque()
        stopped, n = False, 0
        t_ready = time.perf_counter()
        while True:
            if not stopped and stop():
                stopped = True
            if not stopped:
                with self.span("traffic"):
                    ids = self.traffic.block(len(self.traces))
                with self.span("simulate"):
                    pending.append(jax_cache.simulate_batch(self.spec, ids))
                self.traces.append(ids)
                n += 1
            if pending and (stopped or len(pending) >= IN_FLIGHT):
                with self.span("block"):
                    self.hits.append(np.asarray(pending.popleft()))
                t_ready = time.perf_counter()
            elif stopped:
                return n, t_ready

    def setup(self) -> dict:
        self._loop(lambda: len(self.traces) >= 1)
        self.window_from = len(self.traces)
        return {"warm_replications": self.window_from}

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        n, t_end = self._loop(lambda: time.perf_counter() - t0 >= seconds)
        return {"requests": n * self.S * self.T, "chunks": n, "wall_s": t_end - t0}

    def release(self) -> dict:
        """Draw the samples to check from the seed, pull their ids, free the
        rest; returns the hit ratio over every replication run."""
        pairs = [(r, s) for r in range(len(self.traces)) for s in range(self.S)]
        self.sample = sorted(random.Random(self.seed).sample(pairs, min(CHECK_SAMPLES, len(pairs))))
        self.ids = {p: np.asarray(self.traces[p[0]][p[1]]) for p in self.sample}
        del self.traces
        return {"chr": float(np.mean([h.mean() for h in self.hits]))}

    def check(self) -> dict:
        policy = importlib.import_module(f"chipbench.reference.{self.kind}").Policy
        cfg = self.config
        differ = hits_off = failed = 0
        for r, s in self.sample:
            ref = policy(capacity=cfg["capacities"][0], hot_size=cfg.get("hot_size", [0])[0],
                         n_objects=cfg["n_objects"])
            want = np.fromiter(map(ref.request, self.ids[(r, s)].tolist()), bool, self.T)
            got = self.hits[r][s]
            d = int((got != want).sum())
            differ += d
            hits_off += abs(int(got.sum()) - int(want.sum()))
            if r >= self.window_from:
                failed += d
        return {
            "checked": len(self.sample) * self.T,
            "failed": failed,
            "checks": {
                "decisions_differ": (differ, 0),
                "hits_off": (hits_off, 0),
            },
        }
