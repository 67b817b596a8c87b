"""Request routing: which edge node serves each request of a trace.

A CDN front-end maps clients (or content) onto edge caches. Three standard
partitioning schemes are provided, all deterministic functions of the trace so
the jitted hierarchy simulator and the pure-Python reference see the *same*
assignment array:

  * ``hash``        — content-addressed: edge = mix(object_id) % E. Each object
                      lives on exactly one edge (consistent-hash style), so the
                      fleet behaves like one partitioned cache.
  * ``sticky``      — client-session affinity: consecutive requests form
                      sessions of ``session_len``; each session hashes to an
                      edge. Objects replicate across edges (every edge sees the
                      head of the Zipf), trading capacity for locality.
  * ``round_robin`` — load-balanced spraying: request t -> edge t % E. The
                      adversarial case for cache locality.

ROUTER_MODES lists the valid names. ``route`` returns an int32 ``(T,)`` (or
``(S, T)`` for batched traces) edge-assignment array.

``sticky`` and ``round_robin`` key on the request's position in the stream.
:func:`route_level` and :func:`route_device` take that position's offset
``t0`` for the trace they are given, so a stream cut into chunks routes
every chunk as the whole stream would: every router streams.
"""
from __future__ import annotations

import numpy as np

ROUTER_MODES = ("hash", "sticky", "round_robin")

#: per-level router sentinel: follow the topology's static parent map instead
#: of routing (valid for every level but the edge tier). See
#: ``repro.fleet.Topology.routers``.
TREE = "tree"
LEVEL_ROUTER_MODES = ROUTER_MODES + (TREE,)

_SEED_STRIDE = 1_000_003

_MIX_MULT = np.uint64(0xFF51AFD7ED558CCD)
_MIX_MULT2 = np.uint64(0xC4CEB9FE1A85EC53)


def _mix64(x: np.ndarray) -> np.ndarray:
    """SplitMix64-style avalanche; uniform over uint64 for sequential inputs."""
    h = x.astype(np.uint64) + np.uint64(0x9E3779B97F4A7C15)
    h ^= h >> np.uint64(33)
    h *= _MIX_MULT
    h ^= h >> np.uint64(33)
    h *= _MIX_MULT2
    h ^= h >> np.uint64(33)
    return h


def route(
    trace: np.ndarray,
    n_edges: int,
    mode: str = "hash",
    *,
    session_len: int = 64,
    seed: int = 0,
) -> np.ndarray:
    """Edge assignment for every request of ``trace`` (last axis = time)."""
    if n_edges < 1:
        raise ValueError(f"n_edges must be >= 1, got {n_edges}")
    trace = np.asarray(trace)
    T = trace.shape[-1]
    if mode == "round_robin":
        assign = np.broadcast_to(np.arange(T, dtype=np.int64) % n_edges, trace.shape)
    elif mode == "hash":
        assign = _mix64(trace.astype(np.int64) + np.int64(seed) * np.int64(_SEED_STRIDE)) % np.uint64(n_edges)
    elif mode == "sticky":
        if session_len < 1:
            raise ValueError(f"session_len must be >= 1, got {session_len}")
        block = np.arange(T, dtype=np.int64) // session_len
        assign = _mix64(block + np.int64(seed) * np.int64(_SEED_STRIDE)) % np.uint64(n_edges)
        assign = np.broadcast_to(assign, trace.shape)
    else:
        raise ValueError(f"unknown router mode {mode!r}; expected one of {ROUTER_MODES}")
    return np.ascontiguousarray(assign.astype(np.int32))


def route_level(
    trace,
    n_nodes: int,
    mode: str = "hash",
    *,
    session_len: int = 64,
    seed: int = 0,
    t0=0,
    xp=np,
):
    """32-bit (lowbias32) router over one tier's ``n_nodes`` nodes, generic
    over ``xp`` (numpy or jax.numpy) with **bit-identical** partitions.

    ``t0`` is the stream position of ``trace[..., 0]`` (an int32 scalar, may
    be traced): request ``t`` sits at position ``t0 + t``, which ``sticky``
    (session ``(t0 + t) // session_len``) and ``round_robin`` (node
    ``(t0 + t) % n_nodes``) key on; ``hash`` ignores it. Routing a slice at
    its offset equals routing the whole trace and slicing.

    This is the per-level routing primitive: non-edge tiers of a
    ``repro.fleet.Topology`` with a router kind (instead of the static
    parent map) derive their node assignment from it *inside* the jitted
    simulator, and the pure-Python reference oracle replays the exact same
    assignment host-side — which is only possible because the hash is the
    shared pure-uint32 lowbias32 mixer (``core.sketch``), not the host
    router's 64-bit avalanche (unavailable under JAX's default x64-off).
    """
    from repro.core.sketch import _mix32

    if n_nodes < 1:
        raise ValueError(f"n_nodes must be >= 1, got {n_nodes}")
    T = trace.shape[-1]
    salt = xp.uint32(np.uint32(np.int64(seed) * _SEED_STRIDE & 0xFFFFFFFF))
    if mode == "round_robin":
        pos = xp.arange(T, dtype=xp.int32) + t0
        assign = xp.broadcast_to(pos % n_nodes, trace.shape)
    elif mode == "hash":
        h = _mix32(trace.astype(xp.uint32) + salt, xp)
        assign = h % xp.uint32(n_nodes)
    elif mode == "sticky":
        if session_len < 1:
            raise ValueError(f"session_len must be >= 1, got {session_len}")
        pos = xp.arange(T, dtype=xp.int32) + t0
        block = (pos // session_len).astype(xp.uint32)
        assign = xp.broadcast_to(
            _mix32(block + salt, xp) % xp.uint32(n_nodes), trace.shape
        )
    else:
        raise ValueError(f"unknown router mode {mode!r}; expected one of {ROUTER_MODES}")
    return assign.astype(xp.int32)


def route_point(
    mode: str,
    obj_id: int,
    t: int,
    n_nodes: int,
    *,
    session_len: int = 64,
    seed: int = 0,
) -> int:
    """One request's node under :func:`route_level` semantics (host scalar).

    The serving front (``repro.serving.fleet_cache``) routes each lookup's
    climb per level with this — same mixer, same salts — so a served fleet
    partitions its upper tiers exactly as the simulator does."""
    from repro.core.sketch import _mix32

    if n_nodes < 1:
        raise ValueError(f"n_nodes must be >= 1, got {n_nodes}")
    if mode == "round_robin":
        return int(t % n_nodes)
    salt = np.uint32(np.int64(seed) * _SEED_STRIDE & 0xFFFFFFFF)
    if mode == "hash":
        key = obj_id
    elif mode == "sticky":
        if session_len < 1:
            raise ValueError(f"session_len must be >= 1, got {session_len}")
        key = t // session_len
    else:
        raise ValueError(f"unknown router mode {mode!r}; expected one of {ROUTER_MODES}")
    # 1-element array: uint32 wrap-around is silent for arrays, warned for scalars
    return int(_mix32(np.asarray([key], np.uint32) + salt, np)[0] % np.uint32(n_nodes))


def route_device(
    trace,
    n_edges: int,
    mode: str = "hash",
    *,
    session_len: int = 64,
    seed: int = 0,
    t0=0,
):
    """jnp analogue of :func:`route`, usable *inside* jit (the fleet's
    on-device trace-generation path routes freshly synthesized chunks without
    a host round-trip). ``t0`` is the stream position of the trace's first
    request, as in :func:`route_level`.

    Hash/sticky use the shared 32-bit lowbias mixer via :func:`route_level`
    (JAX runs with x64 off, so the host router's 64-bit avalanche is
    unavailable): partitions are equally deterministic/uniform but *differ*
    from the host ``route``. Parity tests always carry the assignment array
    with the results, so oracle comparisons stay exact either way.
    """
    import jax.numpy as jnp

    return route_level(
        trace, n_edges, mode, session_len=session_len, seed=seed, t0=t0, xp=jnp
    )
