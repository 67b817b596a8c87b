"""Plain replay of a routed tier tree, request by request, in stream order.

Each level picks the node of a request with its own router, the
configuration's ``routers``, seeded by the level's index ``l`` (salt
``l * 1,000,003`` mod 2**32):

* ``sticky``: the session ``t // session_len`` of stream position ``t``
  goes to node ``lowbias32(session + salt) % width``;
* ``hash``: id ``x`` goes to node ``lowbias32(x + salt) % width``;
* ``round_robin``: position ``t`` goes to node ``t % width``;
* ``tree`` (above the edge): node ``i`` of the level below feeds node
  ``i * width // width_below``.

A request is served by the lowest level whose node holds it, else by the
origin; every level it reached (the serving one included) then updates its
node, inserting on a miss (leave-copy-everywhere). The policies are the
per-kind files beside this one."""
from __future__ import annotations

import numpy as np

from chipbench.reference import fleet

MASK = 0xFFFFFFFF
SALT_STRIDE = 1_000_003


def lowbias32(h: int) -> int:
    """The lowbias32 integer finaliser of one uint32, on Python ints."""
    h &= MASK
    h ^= h >> 16
    h = (h * 0x7FEB352D) & MASK
    h ^= h >> 15
    h = (h * 0x846CA68B) & MASK
    return h ^ (h >> 16)


def _router(mode: str, level: int, width: int, width_below: int, session_len: int):
    """``node(t, x, below)``: the node of level ``level`` for the request of
    id ``x`` at stream position ``t`` whose node one level down is ``below``."""
    salt = level * SALT_STRIDE & MASK
    if mode == "sticky":
        return lambda t, x, below: lowbias32(t // session_len + salt) % width
    if mode == "hash":
        return lambda t, x, below: lowbias32(x + salt) % width
    if mode == "round_robin":
        return lambda t, x, below: t % width
    if mode == "tree" and level > 0:
        return lambda t, x, below: below * width // width_below
    raise ValueError(f"no reference for router {mode!r} at level {level}")


def replay(config: dict, trace: np.ndarray) -> fleet.Replay:
    widths = [int(w) for w in config["widths"]]
    session_len = int(config.get("session_len", 64))
    routers = [
        _router(mode, l, widths[l], widths[l - 1] if l else 1, session_len)
        for l, mode in enumerate(config["routers"])
    ]
    nodes = fleet.make_nodes(config)
    L = len(nodes)
    node = [[] for _ in range(L)]
    served = []
    for t, x in enumerate(np.asarray(trace, np.int64).tolist()):
        k, path = None, []
        for l in range(L):
            k = routers[l](t, x, k)
            node[l].append(k)
            path.append(k)
        s = L
        for l in range(L):
            if nodes[l][path[l]].request(x):
                s = l
                break
        served.append(s)
    return fleet.Replay([np.array(n, np.int64) for n in node], np.array(served, np.int8), nodes)
