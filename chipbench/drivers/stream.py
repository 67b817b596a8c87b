"""Stream driver: an unbounded request stream, chunk by chunk, through
``FleetStream.push`` in a closed loop.

The host dispatches chunk ``c + 1``'s ids, pushes them, and only then blocks
on chunk ``c``'s per-node hit output, which it copies to the host as a live
consumer would: at most two chunks are in flight. Set-up pushes chunks of
the same stream until every edge cache is full (or ``WARM_MAX_CHUNKS``), so
the first chunk compiles and the window starts on warm caches. The engine is
the flat compact-lane path where the tree is one node of a kind that path
takes, and the level-major engine otherwise: picked from the configuration.

Correctness: the ids of every chunk pushed (set-up and window) come off the
device with its hits; once the window has closed they are replayed through
the plain reference; every request's per-node hit bit at every level, and each node's
request, hit and occupancy counters and the origin count, must be equal."""
from __future__ import annotations

import collections
import time

import jax
import numpy as np

from chipbench.generator import Traffic
from chipbench.reference import fleet as fleet_ref

#: set-up pushes at least this many chunks (the first compiles), then stops
#: once every edge is full, or at the cap (a fault can keep edges empty)
WARM_MIN_CHUNKS, WARM_MAX_CHUNKS = 2, 200


class Driver:
    def __init__(self, cell, seed: int, span):
        from repro import fleet

        cfg, traffic = cell.config, cell.traffic
        self.config = cfg
        self.widths = [int(w) for w in cfg["widths"]]
        self.capacity = [int(c) for c in cfg["capacities"]]
        topo = fleet.tree(
            n_objects=int(cfg["n_objects"]), widths=tuple(self.widths),
            kinds=tuple(cfg["kinds"]), capacities=tuple(self.capacity),
            hot_size=tuple(int(h) for h in cfg.get("hot_size", [0] * len(self.widths))),
            router=cfg["router"],
        )
        self.G = int(traffic["chunk_len"])
        fast = self.widths == [1] and cfg["kinds"][0] in fleet.FAST_KINDS
        self.stream = fleet.FleetStream(
            fleet.StreamConfig(topo=topo, chunk_len=self.G, fast=fast)
        )
        self.traffic = Traffic(cfg, traffic, seed)
        self.span = span
        self.pushed = 0
        # host copies, chunk by chunk, of the ids pushed and the per-level
        # node hits (kept off the device: thousands of small live buffers
        # there are what the window would otherwise accumulate)
        self.ids, self.node_hits = [], []
        self.window_from = 0  # index of the window's first chunk

    # ------------------------------------------------------------ the loop
    def _loop(self, stop):
        """Push chunks until ``stop()``, two in flight, then drain. Returns
        (chunks pushed, lags, push call seconds, time the last was ready)."""
        pending = collections.deque()
        lags, push_s = [], []
        stopped, n = False, 0
        t_ready = time.perf_counter()
        while True:
            if not stopped and stop():
                stopped = True
            if not stopped:
                with self.span("traffic"):
                    ids = self.traffic.block(self.pushed)
                t_push = time.perf_counter()
                with self.span("push"):
                    out = self.stream.push(ids)
                push_s.append(time.perf_counter() - t_push)
                self.pushed += 1
                pending.append((t_push, ids, out))
                n += 1
            if pending and (stopped or len(pending) > 1):
                t_push, ids, out = pending.popleft()
                with self.span("block"):
                    ids, hits = jax.device_get((ids, out["node_hit"]))
                t_ready = time.perf_counter()
                lags.append(t_ready - t_push)
                self.ids.append(ids)
                self.node_hits.append(hits)
            elif stopped:
                return n, lags, push_s, t_ready

    def _edges_full(self) -> bool:
        count = np.asarray(self.stream.states()[0]["count"]).reshape(-1)
        return bool((count >= self.capacity[0]).all())

    def setup(self) -> dict:
        def stop():
            n = self.pushed
            if n < WARM_MIN_CHUNKS:
                return False
            return n >= WARM_MAX_CHUNKS or self._edges_full()

        self._loop(stop)
        self.window_from = self.pushed
        return {"warm_chunks": self.window_from, "edges_full": self._edges_full()}

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        n, lags, push_s, t_end = self._loop(lambda: time.perf_counter() - t0 >= seconds)
        return {"requests": n * self.G, "chunks": n, "wall_s": t_end - t0,
                "lags_s": lags, "push_s": push_s}

    def release(self) -> dict:
        """Pull the ids and the counters off the device, free the stream;
        returns the share of all requests each level served."""
        st = self.stream.stats()
        self.counters = [
            {k: np.asarray(t[k]).reshape(-1).tolist() for k in ("requests", "hits", "count")}
            for t in st.tiers
        ]
        self.origin = int(st.origin_misses)
        self.ids = np.concatenate(self.ids)
        del self.stream
        shares = {f"served_l{l}": sum(c["hits"]) / st.requests for l, c in enumerate(self.counters)}
        return {**shares, "served_origin": self.origin / st.requests}

    # ---------------------------------------------------------- the check
    def check(self) -> dict:
        ref = fleet_ref.replay(self.config, self.ids)
        G = self.G
        differ = np.zeros(len(self.ids), bool)
        for c, per_level in enumerate(self.node_hits):
            for l, got in enumerate(per_level):
                want = ref.node_hits(l, self.widths[l], c * G, (c + 1) * G)
                differ[c * G:(c + 1) * G] |= (np.asarray(got) != want).any(axis=0)
        off = {"requests": 0, "hits": 0, "count": 0}
        for l, got in enumerate(self.counters):
            want = ref.counters(l)
            for k in off:
                off[k] += int(np.abs(np.subtract(got[k], want[k])).sum())
        return {
            "checked": len(self.ids),
            "failed": int(differ[self.window_from * G:].sum()),
            "checks": {
                "decisions_differ": (int(differ.sum()), 0),
                "node_requests_off": (off["requests"], 0),
                "node_hits_off": (off["hits"], 0),
                "occupancy_off": (off["count"], 0),
                "origin_off": (abs(self.origin - ref.origin), 0),
            },
        }
