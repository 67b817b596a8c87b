"""Routed stream driver: the stream driver (``drivers/stream.py``) on a two-tier
CDN, ``n_edges`` edge caches over ``n_origin_nodes`` origin nodes, whose
levels route by the configuration's ``routers``, position-keyed ones
included (``sticky`` client sessions of ``session_len`` requests).
Chunks are pushed with no assignment, so ``FleetStream.push`` routes each
on the device from its stream position.

The window also reports the engine's lane counters over its own chunks:
``lanes``, the node-steps run, and ``lanes_valid``, those whose node held an
active request (``StreamStats``); a program without them leaves both
``None``. They are read at the end of set-up and in ``release()``, after the
window has closed, and added to the window's result there, so that the
window (and a profile of it) holds no host sync the loop does not.

Correctness: as the stream driver, with every chunk replayed through
``reference/routed_fleet.py``."""
from __future__ import annotations

import numpy as np

from chipbench import cells
from chipbench.generator import Traffic
from chipbench.reference import routed_fleet

stream = cells.load_module("drivers", "stream")


class Driver(stream.Driver):
    def __init__(self, cell, seed: int, span):
        from repro import fleet

        cfg, traffic = cell.config, cell.traffic
        self.widths = [int(cfg["n_edges"]), int(cfg["n_origin_nodes"])]
        self.config = cfg = dict(cfg, widths=self.widths)
        self.capacity = [int(c) for c in cfg["capacities"]]
        topo = fleet.tree(
            n_objects=int(cfg["n_objects"]), widths=tuple(self.widths),
            kinds=tuple(cfg["kinds"]), capacities=tuple(self.capacity),
            hot_size=tuple(int(h) for h in cfg["hot_size"]),
            routers=tuple(cfg["routers"]), session_len=int(cfg["session_len"]),
        )
        self.G = int(traffic["chunk_len"])
        self.stream = fleet.FleetStream(fleet.StreamConfig(topo=topo, chunk_len=self.G))
        self.traffic = Traffic(cfg, traffic, seed)
        self.span = span
        self.pushed = 0
        self.ids, self.node_hits = [], []
        self.window_from = 0

    def setup(self) -> dict:
        warm = super().setup()
        # also compiles the few small programs stats() runs, before the window
        self.lanes_from = self.stream.stats()
        return warm

    def window(self, seconds: float) -> dict:
        self.last_window = super().window(seconds)
        return self.last_window

    def release(self) -> dict:
        st = self.stream.stats()
        for k in ("lanes", "lanes_valid"):
            a, b = getattr(st, k), getattr(self.lanes_from, k)
            self.last_window[k] = None if a is None else a - b
        return super().release()

    def check(self) -> dict:
        ref = routed_fleet.replay(self.config, self.ids)
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
