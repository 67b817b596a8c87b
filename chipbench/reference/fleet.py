"""Plain replay of a tier tree, request by request, in trace order.

The edge of a request is ``lowbias32(id) % n_edges`` (the configuration's
``"hash"`` router); above the edge a node's misses go to its parent, node
``i`` of level ``l`` feeding node ``i * widths[l+1] // widths[l]``. A
request is served by the lowest level whose node holds it, else by the
origin; every level it reached (the serving one included) then updates its
node, inserting on a miss (leave-copy-everywhere). A configuration with one
level of one node is a flat cache."""
from __future__ import annotations

import dataclasses
import importlib

import numpy as np


def lowbias32(h: np.ndarray) -> np.ndarray:
    """The lowbias32 integer finaliser over uint32 (wrapping arithmetic)."""
    h = h.astype(np.uint32)
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x7FEB352D)
    h = h ^ (h >> np.uint32(15))
    h = h * np.uint32(0x846CA68B)
    return h ^ (h >> np.uint32(16))


def assignments(config: dict, trace: np.ndarray) -> list[np.ndarray]:
    """The node of every request at every level: one (T,) array a level."""
    widths = [int(w) for w in config["widths"]]
    if widths[0] == 1:
        edge = np.zeros(len(trace), np.int64)
    elif config["router"] == "hash":
        edge = (lowbias32(trace) % np.uint32(widths[0])).astype(np.int64)
    else:
        raise ValueError(f"no reference for edge router {config['router']!r}")
    out = [edge]
    for lo, hi in zip(widths[:-1], widths[1:]):
        out.append(out[-1] * hi // lo)
    return out


def make_nodes(config: dict) -> list[list]:
    kinds, caps = config["kinds"], config["capacities"]
    hot = config.get("hot_size", [0] * len(kinds))
    return [
        [
            importlib.import_module(f"chipbench.reference.{kinds[l]}").Policy(
                capacity=caps[l], hot_size=hot[l], n_objects=config["n_objects"]
            )
            for _ in range(int(w))
        ]
        for l, w in enumerate(config["widths"])
    ]


@dataclasses.dataclass
class Replay:
    node: list[np.ndarray]  # per level: (T,) node index of every request
    served: np.ndarray  # (T,) level that served each request; L = origin
    nodes: list[list]  # per level: the reference policy objects

    def node_hits(self, level: int, width: int, lo: int, hi: int) -> np.ndarray:
        """(width, hi - lo) bool: request t hit node k of ``level``."""
        hit = self.served[lo:hi] == level
        k = self.node[level][lo:hi]
        out = np.zeros((width, hi - lo), bool)
        out[k[hit], np.nonzero(hit)[0]] = True
        return out

    def counters(self, level: int) -> dict[str, list[int]]:
        """Per node of ``level``: requests reaching it, hits, occupancy."""
        width = len(self.nodes[level])
        reached = self.served >= level
        k = self.node[level]
        return {
            "requests": np.bincount(k[reached], minlength=width).tolist(),
            "hits": np.bincount(k[self.served == level], minlength=width).tolist(),
            "count": [len(p) for p in self.nodes[level]],
        }

    @property
    def origin(self) -> int:
        return int((self.served == len(self.nodes)).sum())


def replay(config: dict, trace: np.ndarray) -> Replay:
    trace = np.asarray(trace, np.int64)
    node = assignments(config, trace)
    nodes = make_nodes(config)
    L = len(nodes)
    served = np.full(len(trace), L, np.int8)
    if L == 1 and len(nodes[0]) == 1:
        req = nodes[0][0].request
        for t, x in enumerate(trace.tolist()):
            if req(x):
                served[t] = 0
        return Replay(node, served, nodes)
    per_level = [n.tolist() for n in node]
    for t, x in enumerate(trace.tolist()):
        for l in range(L):
            if nodes[l][per_level[l][t]].request(x):
                served[t] = l
                break
    return Replay(node, served, nodes)
