"""Jitted N-tier fleet simulator: each engine is one chunk program.

Every engine here is a pure function ``(carry, trace, assignment) ->
(carry, out)`` over one chunk of a request stream. The carry holds the
per-level policy states, the counter accumulators and the stream position
``t0``. :class:`repro.fleet.stream.FleetStream` jits it with a donated carry
and pushes chunk after chunk; a bounded run (:func:`simulate_fleet`) is the
same function applied once, from the fresh carry, to a chunk as long as the
trace, its accumulators then folded into per-level counters. The
edge-sharded runs (:mod:`repro.fleet.shard`) apply it inside a
``shard_map``. Two engines, chosen from the topology:

* **Level-major** (all-lce placements, the default,
  :func:`_level_major_chunk_fn`): every level runs the branch-free
  ``jax_cache.step`` as a single vmapped, masked scan over its nodes: node
  ``i`` at level ``l`` is *active* at trace position ``t`` iff the request
  routed to it (the edge assignment pushed up the parent tree) **and** no
  level below served it — i.e. each tier consumes exactly the interleaved
  miss stream of its children, in true request order. Each node steps its
  own requests, compacted (:func:`masked_scan`): a level's loop runs to its
  busiest node's load, not to the chunk length, while every shape stays
  fixed, so the whole topology is jittable and vmaps over trace samples.
  plfua_dyn and instrumented levels keep the dense scan over every position.

* **Time-major** (any non-lce placement, :mod:`repro.fleet.placement`,
  :func:`_placed_chunk_fn`): cross-tier placement makes a tier's insert
  decision depend on *where the request was served above it* — information
  that only exists after the upper tiers' hit tests at the same trace
  position, so the per-level scans no longer factorise. The placed engine
  scans *time* instead: each step probes the miss path bottom-up
  (pre-update membership gathers), resolves the serving level, then applies
  fill-gated ``step`` updates to the one consulted node per level.
  ``prob(1.0)`` topologies reproduce the level-major engine bit for bit
  (asserted in tests/test_placement.py) — the cross-validation between the
  two engines.

plfua_dyn's hot-set refresh is global-time: a chunk scans sub-chunks of
``gcd(refresh, chunk length)`` and refreshes where a sub-chunk ends on a
whole multiple of the level's period, tested against the traced ``t0``
(``jax_cache.stream_chunked_scan``; the placed engine's own sub-chunk scan
takes the gcd over every plfua_dyn level). So K chunks and one chunk of
their concatenation run the same schedule, and a partial tail period never
fires.

Decision parity: :mod:`repro.fleet.reference` runs the same topology with the
paper's pure-Python policy objects; tests assert identical per-level hit
sequences, final cache contents, and eviction counts (tests/test_fleet.py,
tests/test_placement.py). ``repro.cdn.simulate_hierarchy`` is a thin depth-2
wrapper over this module.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import jax_cache, sketch
from repro.core.jax_cache import PolicySpec
from repro.fleet import placement as placement_mod
from repro.fleet import topology as topo_mod
from repro.fleet.topology import Topology
from repro.telemetry import spec as telemetry_spec

__all__ = [
    "masked_scan",
    "simulate_fleet",
    "simulate_fleet_batch",
]


def masked_scan(
    spec: PolicySpec,
    state,
    trace,
    active,
    cap=None,
    *,
    t0=0,
    instrument=False,
    sizes=None,
    cap_bytes=None,
    og=None,
):
    """Step one node through its own requests, the positions where
    ``active`` is True, in stream order; returns ``(state, hits & active)``.

    Each node steps its own requests, compacted: the active positions are
    packed to the front, their ids gathered once, and ``step`` runs once a
    request (a ``while`` loop with a traced trip count), the hit bits then
    gathered back to their positions. ``step`` reads no position, so the
    states and hits are those of a scan over every position that freezes
    the state where the node is inactive. Under a level's ``vmap`` the loop
    runs to the busiest node's load; a node that has finished keeps its
    state through the loop's one batched select a step.

    plfua_dyn routes through the dense ``jax_cache.stream_chunked_scan`` so
    its global-time hot-set refresh fires at stream-position boundaries —
    ``t0`` is the trace's first position, traced in a stream — for every
    instance, active or not (the reference oracle drives ``refresh_now`` on
    the same timer). ``instrument`` (static) switches to the dense telemetry
    twin, which returns ``(state, hits, events)`` with the per-position
    event series (identical state/hit trajectory — asserted in
    tests/test_telemetry.py). :func:`compacts` says which scan a level gets.
    ``sizes``/``cap_bytes`` are the byte-capacity inputs of
    ``jax_cache.step``; ``og`` the (n_objects, n_groups) group one-hot for
    group-segmented telemetry."""
    with jax.named_scope("repro.step"):
        if spec.kind == "plfua_dyn":
            return jax_cache.stream_chunked_scan(
                spec, state, trace, active, cap, t0=t0, instrument=instrument,
                sizes=sizes, cap_bytes=cap_bytes, og=og,
            )
        if instrument:
            return jax_cache.instrumented_scan(
                spec, state, trace, active, cap, sizes=sizes, cap_bytes=cap_bytes,
                og=og,
            )

        T = trace.shape[0]
        with jax.named_scope("repro.compact"):
            # slot of each active position in the packed order; inactive
            # positions scatter out of bounds and are dropped
            slot = jnp.cumsum(active, dtype=jnp.int32) - 1
            pos = jnp.zeros((T,), jnp.int32).at[jnp.where(active, slot, T)].set(
                jnp.arange(T, dtype=jnp.int32), mode="drop"
            )
            ids = jnp.take(trace, pos)
            n = active.sum(dtype=jnp.int32)

        def body(carry):
            i, s, hits = carry
            ns, hit = jax_cache.step(
                spec, s, ids[i], cap, sizes=sizes, cap_bytes=cap_bytes
            )
            return i + 1, ns, hits.at[i].set(hit)

        # zeros_like keeps ``active``'s sharding (varying under a shard_map)
        _, state, hits = jax.lax.while_loop(
            lambda carry: carry[0] < n,
            body,
            (jnp.int32(0), state, jnp.zeros_like(active)),
        )
        with jax.named_scope("repro.compact"):
            return state, jnp.take(hits, jnp.maximum(slot, 0)) & active


def compacts(spec: PolicySpec, instrument: bool = False) -> bool:
    """Whether :func:`masked_scan` steps a node through its own requests
    only (True) or through every position of the trace (plfua_dyn's
    global-time refresh, the telemetry twin's per-position series)."""
    return not instrument and spec.kind != "plfua_dyn"


def level_assignments(
    topo: Topology, trace: jax.Array, assignment: jax.Array, t0=0
) -> list[jax.Array]:
    """Per-level node assignment, one (T,) int32 per level: the edge
    assignment pushed up the parent tree for ``"tree"`` levels (parent maps
    are static tuples, folded into the jit as constants), or the level's own
    router for routed tiers at stream position ``t0`` (a stream chunk's
    traced offset) — the jnp instantiation of the xp-generic
    :func:`repro.fleet.topology.level_assignments` the oracle replays."""
    return topo_mod.level_assignments(topo, trace, assignment, xp=jnp, t0=t0)


def stack_level_state(specs: tuple[PolicySpec, ...]):
    """Stacked zero state for one level's node fleet."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[jax_cache.init_state(s) for s in specs]
    )


def _group_tables(telemetry, groups):
    """``(groups, og)`` of a grouped run — the int32 id→group catalogue and
    its (n_objects, n_groups) one-hot — or ``(None, None)`` when grouping
    is off. Raises if ``n_groups > 0`` but no catalogue was passed."""
    if telemetry is None or not telemetry.n_groups:
        return None, None
    if groups is None:
        raise ValueError("telemetry.n_groups > 0 requires a groups catalogue")
    groups = jnp.asarray(groups, jnp.int32)
    return groups, telemetry_spec.group_onehot(groups, telemetry.n_groups, jnp)


def _node_ids(K, first=None):
    """Node ids of a level's (K,) stacked state: ``0..K-1``, or from
    ``first`` on for an edge-sharded level 0 (this device's slice)."""
    ids = jnp.arange(K, dtype=jnp.int32)
    return ids if first is None else first + ids


def _edge_slice(edge_axis, states0):
    """``(K_local, first id)`` of this device's slice of the edge level
    inside a ``shard_map`` over ``edge_axis``."""
    K = int(states0["count"].shape[0])
    return K, jax.lax.axis_index(edge_axis).astype(jnp.int32) * K


# ------------------------------------------------------- counter accumulators
def _acc_keys(spec: PolicySpec, sized: bool) -> tuple[str, ...]:
    """Counter accumulators a level needs beyond requests/hits: kinds whose
    insert count is not carried in state (sketch kinds carry it, since
    their admission is data-dependent; byte mode carries it for every kind,
    since an admitted object may not fit) accumulate it from per-chunk miss
    sums; plfua also accumulates its hot-gated request count; sized runs
    accumulate byte traffic."""
    keys = ["requests", "hits"]
    if spec.kind == "plfua":
        keys.append("admitted")
        if not spec.capacity_bytes:
            keys.append("inserts")
    elif spec.kind not in jax_cache.SKETCH_POLICY_KINDS:
        if not spec.capacity_bytes:
            keys.append("inserts")
    if sized:
        keys += ["req_bytes", "hit_bytes"]
    return tuple(keys)


def _zero_acc(topo: Topology, sized: bool):
    return tuple(
        {
            k: jnp.zeros((len(lvl),), jnp.int32)
            for k in _acc_keys(lvl[0], sized)
        }
        for lvl in topo.levels
    )


def _accumulate_level(spec, acc_l, active, hits, trace, states_l, sz_t):
    """One chunk's contribution to a level-major level's accumulators."""
    out = dict(acc_l)
    out["requests"] = acc_l["requests"] + active.sum(-1).astype(jnp.int32)
    out["hits"] = acc_l["hits"] + hits.sum(-1).astype(jnp.int32)
    miss = active & ~hits
    if spec.kind == "plfua":
        admitted = jnp.take(states_l["hot"], trace, axis=-1)
        if "inserts" in acc_l:
            out["inserts"] = acc_l["inserts"] + (miss & admitted).sum(-1).astype(
                jnp.int32
            )
        out["admitted"] = acc_l["admitted"] + (active & admitted).sum(-1).astype(
            jnp.int32
        )
    elif "inserts" in acc_l:
        out["inserts"] = acc_l["inserts"] + miss.sum(-1).astype(jnp.int32)
    if sz_t is not None:
        out["req_bytes"] = acc_l["req_bytes"] + (active * sz_t).sum(-1)
        out["hit_bytes"] = acc_l["hit_bytes"] + (hits * sz_t).sum(-1)
    return out


def _tier_from_acc(spec: PolicySpec, acc_l, state_l, *, inserts=None, admitted=None):
    """One level's counter dict (requests/hits/admitted_requests/inserts/
    evictions/count, plus req_bytes/hit_bytes in a sized run and the
    resident ``bytes`` of a byte-capacity level) from its accumulators and
    final state. Every admitted miss inserts, so evictions = inserts -
    final occupancy. Placed runs pass their carried ``fills``/``admitted``."""
    if inserts is None:
        if spec.capacity_bytes or spec.kind in jax_cache.SKETCH_POLICY_KINDS:
            inserts = state_l["inserts"]
        else:
            inserts = acc_l["inserts"]
    if admitted is None:
        if spec.kind == "plfua":
            admitted = acc_l["admitted"]
        elif spec.kind in jax_cache.SKETCH_POLICY_KINDS:
            # every hit touches policy metadata; every insert is an admitted miss
            admitted = acc_l["hits"] + inserts
        else:
            admitted = acc_l["requests"]
    count = state_l["count"]
    tier = {
        "requests": acc_l["requests"],
        "hits": acc_l["hits"],
        "admitted_requests": admitted,
        "inserts": inserts,
        "evictions": inserts - count,
        "count": count,
    }
    if "req_bytes" in acc_l:
        tier["req_bytes"] = acc_l["req_bytes"]
        tier["hit_bytes"] = acc_l["hit_bytes"]
    if spec.capacity_bytes:
        tier["bytes"] = state_l["bytes"]
    return tier


# ------------------------------------------------------ level-major engine
def _level_major_chunk_fn(
    topo: Topology, G: int, telemetry=None, *, sizes=None, og=None, groups=None,
    edge_axis: str | None = None,
):
    """The level-major engine's chunk program for chunks of ``G`` requests:
    returns ``(level_major_chunk, carry0)``.

    ``level_major_chunk(carry, trace, assignment) -> (carry, out)`` runs
    the levels bottom-up, each a vmapped :func:`masked_scan` over its nodes
    on the positions its routing and the demand chain leave it, and folds
    the hit series into the carry's counter accumulators, the origin count
    and the ``node_steps`` counter (``K_l`` x the busiest node's load at a
    compacted level, ``K_l`` x ``G`` at a dense one). ``out`` holds the
    per-level ``hit`` (G,) and ``node_hit`` (K_l, G) series and
    ``origin_miss``; with ``telemetry`` also the per-level window series
    (and, grouped, the eviction pressure). ``sizes``, ``og`` and ``groups``
    are the shared byte catalogue and group tables (:func:`_group_tables`).

    With ``edge_axis`` the function runs inside a ``shard_map`` whose level-0
    carry holds this device's contiguous slice of edges: level 0 scans that
    slice, and one ``psum`` rebuilds the global served mask, so the upper
    levels (replicated) consume the fleet-wide miss stream."""
    instrument = telemetry is not None
    grouped = og is not None

    def level_major_chunk(carry, trace, assignment):
        t0 = carry["t0"]
        trace = trace.astype(jnp.int32)
        with jax.named_scope("repro.route"):
            assigns = level_assignments(topo, trace, assignment, t0=t0)
        groups_t = None if groups is None else groups[trace]
        sz_t = None if sizes is None else jnp.take(sizes, trace, axis=-1)
        demand = jnp.ones((G,), jnp.bool_)
        node_steps = carry["node_steps"]
        new_states, new_acc = [], []
        hit_lv, node_hit, series, pressure = [], [], [], []
        for l, specs in enumerate(topo.levels):
            with jax.named_scope(f"repro.level{l}"):
                s0 = specs[0]
                K, first = len(specs), None
                sharded = edge_axis is not None and l == 0
                if sharded:
                    K, first = _edge_slice(edge_axis, carry["states"][0])
                active = (
                    assigns[l][None, :] == _node_ids(K, first)[:, None]
                ) & demand[None, :]
                caps = jnp.array([s.capacity for s in specs], jnp.int32)
                if sharded:
                    caps = jax.lax.dynamic_slice_in_dim(caps, first, K)
                if s0.capacity_bytes:
                    caps_b = jnp.array([s.capacity_bytes for s in specs], jnp.int32)
                    if sharded:
                        caps_b = jax.lax.dynamic_slice_in_dim(caps_b, first, K)
                    out = jax.vmap(
                        lambda st, act, cap, capb: masked_scan(
                            s0, st, trace, act, cap, t0=t0, instrument=instrument,
                            sizes=sizes, cap_bytes=capb, og=og,
                        )
                    )(carry["states"][l], active, caps, caps_b)
                else:
                    out = jax.vmap(
                        lambda st, act, cap: masked_scan(
                            s0, st, trace, act, cap, t0=t0, instrument=instrument,
                            sizes=sizes, og=og,
                        )
                    )(carry["states"][l], active, caps)
                if instrument:
                    states_l, hits, events = out
                    with jax.named_scope("repro.telemetry"):
                        series.append(
                            jax_cache.telemetry_series(
                                s0, telemetry, G, hits, events, active=active,
                                groups_t=groups_t,
                                chunk_len=(
                                    jax_cache.stream_sub_len(s0, G)
                                    if s0.kind == "plfua_dyn"
                                    else None
                                ),
                            )
                        )
                        if grouped:
                            pressure.append(
                                telemetry_spec.windowed_pressure(
                                    telemetry.window, groups_t, events["evict_g"],
                                    xp=jnp,
                                )
                            )
                else:
                    states_l, hits = out
                if compacts(s0, instrument):
                    # the level's loop runs to its busiest node's load
                    loads = active.sum(-1, dtype=jnp.int32)
                    node_steps = node_steps + jnp.int32(K) * loads.max()
                else:
                    node_steps = node_steps + jnp.int32(K * G)
                new_states.append(states_l)
                new_acc.append(
                    _accumulate_level(
                        s0, carry["acc"][l], active, hits, trace, states_l, sz_t
                    )
                )
                node_hit.append(hits)
                hit_l = hits.any(axis=0)
                if sharded:
                    # one collective rebuilds the global served mask (exactly
                    # one edge is active per position, so sum == any)
                    hit_l = jax.lax.psum(hit_l.astype(jnp.int32), edge_axis) > 0
                hit_lv.append(hit_l)
                demand = demand & ~hit_l
        new_carry = {
            "states": tuple(new_states),
            "acc": tuple(new_acc),
            "origin": carry["origin"] + demand.sum(dtype=jnp.int32),
            "node_steps": node_steps,
            "t0": t0 + jnp.int32(G),
        }
        out = {
            "hit": tuple(hit_lv),
            "node_hit": tuple(node_hit),
            "origin_miss": demand,
        }
        if instrument:
            out["telemetry"] = tuple(series)
            if grouped:
                out["telemetry_pressure"] = tuple(pressure)
        return new_carry, out

    carry0 = {
        "states": tuple(stack_level_state(lvl) for lvl in topo.levels),
        "acc": _zero_acc(topo, sizes is not None),
        "origin": jnp.zeros((), jnp.int32),
        "node_steps": jnp.zeros((), jnp.int32),
        "t0": jnp.zeros((), jnp.int32),
    }
    return level_major_chunk, carry0


# ------------------------------------------------- time-major placed engine
def _victim_key(spec: PolicySpec, state):
    """The array whose masked argmin is the node's eviction candidate —
    recency stamps for LRU, the cached GDSF priority for gdsf, (windowed/
    parked) frequency for everyone else.

    The admit placement duels against the candidate of the *pre-request*
    state (the reference oracle's ``peek_victim`` reads the same snapshot).
    For every kind but wlfu this is exactly the victim ``jax_cache.step``
    would evict; wlfu slides its window before evicting, so in the corner
    case where that slide demotes a different cached object the duel's
    candidate and the step's victim can differ — a deliberate, documented
    pick (duelling pre-state keeps the gate computable without replaying
    the slide), identical across the jitted engine and the oracle.
    """
    if spec.kind == "lru":
        return state["last"]
    if spec.kind == "gdsf":
        return state["score"]
    if spec.kind == "arc":
        # ARC's candidate is the LRU of the list REPLACE would demote. The
        # pre-state pick drops the x-dependent tiebreak (|T1| == p on a B2
        # ghost hit): like wlfu's slide, the duel's candidate can then differ
        # from the step's victim in that corner — same pick in the oracle.
        lst = state["lst"]
        t1n = (lst == 1).sum().astype(jnp.int32)
        t2n = (lst == 2).sum().astype(jnp.int32)
        pref = jnp.where((t1n > state["p"]) | (t2n == 0), 1, 2)
        return jnp.where(lst == pref, state["stamp"], jax_cache._I32_MAX)
    return state["freq"]


def _dyn_chunk(topo: Topology) -> int | None:
    """The gcd of every plfua_dyn level's refresh period (their global-time
    refreshes all land on multiples of it), or None when no level has one."""
    periods = [
        lvl[0].effective_refresh
        for lvl in topo.levels
        if lvl[0].kind == "plfua_dyn"
    ]
    if not periods:
        return None
    g = periods[0]
    for p in periods[1:]:
        g = math.gcd(g, p)
    return g


def _placed_prelude(
    topo: Topology,
    *,
    edge_axis: str | None = None,
    instrument: bool = False,
    sizes=None,
    og=None,
):
    """Setup of the time-major placed engine: the per-level specs, the zero
    carry (states / placement sketches / fill + admitted counters) and the
    ``step_t`` scan body. With ``edge_axis`` the body runs inside a
    ``shard_map`` whose level-0 carry is this device's slice of edges: the
    probe rebuilds the global edge-served bit with one ``psum`` a step, and
    upper levels run replicated (identical on every device, being pure
    functions of replicated inputs). Returns ``(specs, dyn_levels, carry0,
    step_t)``.
    """
    if instrument and edge_axis is not None:
        raise NotImplementedError("telemetry is single-device (no edge mesh)")
    if edge_axis is not None and any(
        lvl[0].capacity_bytes for lvl in topo.levels
    ):
        raise NotImplementedError("byte-capacity placement is single-device")
    L = topo.n_levels
    specs = [lvl[0] for lvl in topo.levels]
    parsed = [placement_mod.parse(p) for p in topo.placements]

    states = [stack_level_state(lvl) for lvl in topo.levels]
    caps = [jnp.array([s.capacity for s in lvl], jnp.int32) for lvl in topo.levels]
    caps_b = [
        jnp.array([s.capacity_bytes for s in lvl], jnp.int32) for lvl in topo.levels
    ]

    # admit placement: host-side bucket constants + per-node sketch state
    admit_tables: dict[int, jax.Array] = {}
    admit_windows: dict[int, int] = {}
    pstates: dict[int, dict] = {}
    for l, (pk, _) in enumerate(parsed):
        if pk != "admit":
            continue
        width, window = placement_mod.admit_params(topo.levels[l])
        admit_tables[l] = jnp.asarray(
            sketch.bucket_table(np.arange(topo.n_objects), width)
        )
        admit_windows[l] = window
        K = len(topo.levels[l])
        pstates[l] = dict(
            rows=jnp.zeros((K, sketch.DEPTH, width), jnp.int32),
            seen=jnp.zeros((K,), jnp.int32),
        )
    fills = [jnp.zeros((len(lvl),), jnp.int32) for lvl in topo.levels]
    admitted = [jnp.zeros_like(f) for f in fills]

    def step_t(carry, inp):
        states, pstates, fills, admitted = carry
        t, x, nodes = inp
        # ---- probe the miss path bottom-up on pre-update membership
        with jax.named_scope("repro.probe"):
            consulted, hits = [], []
            demand = jnp.bool_(True)
            if edge_axis is not None:
                n_local, offset = _edge_slice(edge_axis, states[0])
                local0 = nodes[0] - offset
                own0 = (local0 >= 0) & (local0 < n_local)
                node0 = jnp.clip(local0, 0, n_local - 1)
            else:
                own0, node0 = jnp.bool_(True), nodes[0]
            for l in range(L):
                if l == 0:
                    in_c = own0 & states[0]["in_cache"][node0, x]
                    if edge_axis is not None:
                        # one collective rebuilds the global edge-served bit
                        # (exactly one device owns the assigned edge)
                        in_c = jax.lax.psum(in_c.astype(jnp.int32), edge_axis) > 0
                else:
                    in_c = states[l]["in_cache"][nodes[l], x]
                consulted.append(demand)
                hits.append(demand & in_c)
                demand = demand & ~in_c
            serve = jnp.int32(L)  # L = served at origin
            for l in reversed(range(L)):
                serve = jnp.where(hits[l], jnp.int32(l), serve)
        # ---- fill-gated update of the one consulted node per level
        new_states, new_fills, new_admitted, tel = [], [], [], []
        new_pstates = dict(pstates)
        for l in range(L):
            with jax.named_scope(f"repro.level{l}"):
                spec = specs[l]
                node = node0 if l == 0 else nodes[l]
                act = consulted[l] & (own0 if l == 0 else True)
                st = jax.tree_util.tree_map(lambda a: a[node], states[l])
                # capacities are indexed by the global node id (the edge
                # level's table is not sliced under a mesh)
                cap = caps[l][nodes[l]]
                cap_b = caps_b[l][nodes[l]] if spec.capacity_bytes else None
                pk, pp = parsed[l]
                if pk == "lce":
                    fill = None
                elif pk == "lcd":
                    fill = serve == l + 1
                elif pk == "prob":
                    fill = (serve == l + 1) | placement_mod.prob_fill(t, l, pp, jnp)
                else:  # admit: feed + age the placement sketch, then duel
                    ps = pstates[l]
                    idx = admit_tables[l][x]
                    rows = sketch.rows_add(ps["rows"][node], idx)
                    seen = ps["seen"][node] + 1
                    age = seen >= admit_windows[l]
                    rows = jnp.where(age, sketch.rows_halve(rows), rows)
                    seen = jnp.where(age, 0, seen)
                    victim = jax_cache._masked_argmin(
                        _victim_key(spec, st), st["in_cache"]
                    )
                    if spec.capacity_bytes:
                        # byte mode: "full" = does not fit as-is (cf. tinylfu)
                        size_x = jnp.int32(1) if sizes is None else sizes[x]
                        full = st["bytes"] + size_x > cap_b
                    else:
                        full = st["count"] >= cap
                    est_x = sketch.rows_estimate(rows, idx)
                    est_v = sketch.rows_estimate(rows, admit_tables[l][victim])
                    fill = (~full) | (est_x > est_v)
                    new_pstates[l] = dict(
                        rows=ps["rows"].at[node].set(
                            jnp.where(act, rows, ps["rows"][node])
                        ),
                        seen=ps["seen"].at[node].set(
                            jnp.where(act, seen, ps["seen"][node])
                        ),
                    )
                ns, hit = jax_cache.step(
                    spec, st, x, cap, fill=fill, sizes=sizes, cap_bytes=cap_b
                )
                insert = act & (~hit) & ns["in_cache"][x]
                new_states.append(
                    jax.tree_util.tree_map(
                        lambda old, new: old.at[node].set(
                            jnp.where(act, new, old[node])
                        ),
                        states[l],
                        ns,
                    )
                )
                if instrument:
                    gate = jnp.bool_(True) if fill is None else fill
                    tel_l = {
                        "fill": insert,
                        # int32 victim count: byte mode can evict several per
                        # insert; in object mode this is the old 0/1 event
                        "evict": jnp.where(act, st["count"] - ns["count"], 0)
                        + insert.astype(jnp.int32),
                        "offer": act & (~hit) & gate,
                        # post-step occupancy snapshot of the whole node fleet
                        "count": new_states[l]["count"],
                    }
                    if og is not None:
                        # victim-group counts at the consulted node (membership
                        # diff = exactly the victims; masked like the scalar) and
                        # the whole node fleet's per-group occupancy snapshot
                        vmask = st["in_cache"] & ~ns["in_cache"]
                        tel_l["evict_g"] = jnp.where(
                            act, vmask.astype(jnp.int32) @ og, 0
                        )
                        tel_l["count_g"] = (
                            new_states[l]["in_cache"].astype(jnp.int32) @ og
                        )
                    if spec.kind == "tinylfu":
                        tel_l["aging"] = act & (ns["seen"] == 0)
                    tel.append(tel_l)
                new_fills.append(fills[l].at[node].add(insert.astype(jnp.int32)))
                # same admitted_requests conventions as _tier_from_acc
                if spec.kind == "plfua":
                    adm = act & st["hot"][x]
                elif spec.kind in jax_cache.SKETCH_POLICY_KINDS:
                    adm = (act & hit) | insert
                else:
                    adm = act
                new_admitted.append(
                    admitted[l].at[node].add(adm.astype(jnp.int32))
                )
        carry = (
            tuple(new_states),
            new_pstates,
            tuple(new_fills),
            tuple(new_admitted),
        )
        if instrument:
            return carry, (tuple(hits), tuple(tel))
        return carry, tuple(hits)

    dyn_levels = [l for l in range(L) if specs[l].kind == "plfua_dyn"]
    carry0 = (tuple(states), pstates, tuple(fills), tuple(admitted))
    return specs, dyn_levels, carry0, step_t


def _placed_untile(out, n_levels, dyn_levels, fire, *, instrument=False, og=None):
    """Flatten a placed sub-chunk scan's stacked output back to chunk-major.

    ``fire`` is the traced (n_sub, n_dyn) refresh schedule. Returns
    ``hit_lv`` or ``(hit_lv, tel_lv)`` under ``instrument``."""
    if not instrument:
        return [h.reshape(-1) for h in out]
    hits, tel, churns, churns_g = out
    hit_lv = [h.reshape(-1) for h in hits]
    # un-chunk the event series: scalars (n_sub, S) -> (G,); the per-step
    # occupancy snapshot (n_sub, S, K) -> (K, G); grouped events keep
    # their trailing group axis — evict_g (n_sub, S, n_g) -> (G, n_g),
    # count_g (n_sub, S, K, n_g) -> (K, G, n_g)
    tel_lv = []
    for l in range(n_levels):
        d = {}
        for k, v in tel[l].items():
            if k == "evict_g":
                d[k] = v.reshape((-1,) + v.shape[2:])
            elif k == "count_g":
                d[k] = jnp.moveaxis(v.reshape((-1,) + v.shape[2:]), 0, 1)
            elif v.ndim == 2:
                d[k] = v.reshape(-1)
            else:
                d[k] = v.reshape(-1, v.shape[-1]).T
        tel_lv.append(d)
    n_sub = fire.shape[0]
    for j, l in enumerate(dyn_levels):
        K = churns[j].shape[-1]
        # all nodes of a dyn level refresh on the same global-time schedule
        tel_lv[l]["fired"] = jnp.broadcast_to(fire[:, j], (K, n_sub))
        tel_lv[l]["churn"] = churns[j].T  # (n_sub, K) -> (K, n_sub)
        if og is not None:
            # (n_sub, K, n_g) -> (K, n_sub, n_g)
            tel_lv[l]["churn_g"] = jnp.moveaxis(churns_g[j], 0, 1)
    return hit_lv, tel_lv


def _placed_chunk_fn(
    topo: Topology, G: int, telemetry=None, *, sizes=None, og=None, groups=None,
    edge_axis: str | None = None,
):
    """The time-major placed engine's chunk program for chunks of ``G``
    requests: returns ``(placed_chunk, carry0)``, with the same arguments,
    carry layout (``placed`` in place of ``states``, no ``node_steps``) and
    ``out`` as :func:`_level_major_chunk_fn`.

    The chunk is scanned in sub-chunks of the gcd of ``G`` and every
    plfua_dyn refresh period, so every whole multiple of every period is a
    sub-chunk boundary and the traced fire test on the stream position
    refreshes exactly there. The hit series is then folded: level ``l``
    node ``k`` is active at ``t`` iff the request routed to it and no level
    below served it (the level-major masks by construction); the counters
    accumulate from those masks, and with ``telemetry`` the per-step events
    (consulted-node scalars) are scattered to nodes through them and
    bucketed. The placement gate makes ``fill_offers`` engine-computed here
    (a consulted miss whose gate was open), unlike the level-major engine
    where every miss is an offer."""
    instrument = telemetry is not None
    grouped = og is not None
    specs, dyn_levels, placed0, step_t = _placed_prelude(
        topo, edge_axis=edge_axis, instrument=instrument, sizes=sizes, og=og
    )
    gdyn = _dyn_chunk(topo)
    sub = math.gcd(gdyn, G) if gdyn else G
    n_sub = G // sub

    # scan ``step_t`` over one sub-chunk, then apply each plfua_dyn level's
    # vmapped hot-set refresh where that level's fire flag is set (under a
    # ``lax.cond``, as in ``jax_cache``'s refresh cell; with churn capture
    # under ``instrument``)
    def sub_chunk(carry, inp):
        xs, fire_c = inp
        carry, out = jax.lax.scan(step_t, carry, xs)
        states, pstates, fills, admitted = carry
        states = list(states)
        churns, churns_g = [], []
        for j, l in enumerate(dyn_levels):
            refreshed = jax.lax.cond(
                fire_c[j],
                jax.vmap(functools.partial(jax_cache.refresh_hot, specs[l])),
                lambda s: s,
                states[l],
            )
            if instrument:
                diff = states[l]["hot"] != refreshed["hot"]  # (K, N)
                churns.append(diff.sum(-1).astype(jnp.int32))
                if og is not None:
                    churns_g.append(diff.astype(jnp.int32) @ og)
            states[l] = refreshed
        carry = (tuple(states), pstates, fills, admitted)
        if instrument:
            hits, tel = out
            return carry, (hits, tel, tuple(churns), tuple(churns_g))
        return carry, out

    def placed_chunk(carry, trace, assignment):
        t0 = carry["t0"]
        trace = trace.astype(jnp.int32)
        with jax.named_scope("repro.route"):
            assigns = level_assignments(topo, trace, assignment, t0=t0)
        groups_t = None if groups is None else groups[trace]
        sz_t = None if sizes is None else jnp.take(sizes, trace, axis=-1)
        t_arr = t0 + jnp.arange(G, dtype=jnp.int32)
        ends = t0 + (jnp.arange(n_sub, dtype=jnp.int32) + 1) * jnp.int32(sub)
        if dyn_levels:
            fire = jnp.stack(
                [
                    ends % jnp.int32(specs[l].effective_refresh) == 0
                    for l in dyn_levels
                ],
                axis=1,
            )
        else:
            fire = jnp.zeros((n_sub, 0), jnp.bool_)
        tile = lambda a: a.reshape(n_sub, sub, *a.shape[1:])
        with jax.named_scope("repro.step"):
            placed, out = jax.lax.scan(
                sub_chunk,
                carry["placed"],
                (
                    (
                        tile(t_arr),
                        tile(trace),
                        tuple(tile(a) for a in assigns),
                    ),
                    fire,
                ),
            )
        untiled = _placed_untile(
            out, topo.n_levels, dyn_levels, fire, instrument=instrument, og=og
        )
        if instrument:
            hit_lv, tel_lv = untiled
        else:
            hit_lv = untiled
        demand = jnp.ones((G,), jnp.bool_)
        new_acc, node_hit, series, pressure = [], [], [], []
        for l in range(topo.n_levels):
            K, first = len(topo.levels[l]), None
            if edge_axis is not None and l == 0:
                K, first = _edge_slice(edge_axis, placed[0][0])
            active = (
                assigns[l][None, :] == _node_ids(K, first)[:, None]
            ) & demand[None, :]
            nh = active & hit_lv[l][None, :]
            acc_l = dict(carry["acc"][l])
            acc_l["requests"] = acc_l["requests"] + active.sum(-1).astype(jnp.int32)
            acc_l["hits"] = acc_l["hits"] + nh.sum(-1).astype(jnp.int32)
            if sz_t is not None:
                acc_l["req_bytes"] = acc_l["req_bytes"] + (
                    active * sz_t[None, :]
                ).sum(-1)
                acc_l["hit_bytes"] = acc_l["hit_bytes"] + (nh * sz_t[None, :]).sum(-1)
            new_acc.append(acc_l)
            node_hit.append(nh)
            if instrument:
                with jax.named_scope("repro.telemetry"):
                    ev = tel_lv[l]
                    per_node = lambda s: active & s[None, :]
                    aging = ev.get("aging")
                    common = dict(
                        hits=nh,
                        active=active,
                        fills=per_node(ev["fill"]),
                        offers=per_node(ev["offer"]),
                        aging=None if aging is None else per_node(aging),
                        fired=ev.get("fired"),
                        hit_bytes=None if sz_t is None else nh * sz_t[None, :],
                        miss_bytes=(
                            None if sz_t is None else (active & ~nh) * sz_t[None, :]
                        ),
                        chunk_len=sub,
                        xp=jnp,
                    )
                    if grouped:
                        # scatter the consulted-node victim-group counts to
                        # nodes through the same activity masks
                        evict_g = active[:, :, None] * ev["evict_g"][None, :, :]
                        series.append(
                            telemetry_spec.grouped_series_from_run(
                                telemetry.window, G, telemetry.n_groups, groups_t,
                                evictions_g=evict_g, occupancy_g=ev["count_g"],
                                churn_g=ev.get("churn_g"), **common,
                            )
                        )
                        pressure.append(
                            telemetry_spec.windowed_pressure(
                                telemetry.window, groups_t, evict_g, xp=jnp
                            )
                        )
                    else:
                        series.append(
                            telemetry_spec.series_from_run(
                                telemetry.window, G,
                                # int32 victim counts, scattered to the
                                # consulted node
                                evictions=active * ev["evict"][None, :],
                                occupancy=ev["count"], churn=ev.get("churn"),
                                **common,
                            )
                        )
            demand = demand & ~hit_lv[l]
        new_carry = {
            "placed": placed,
            "acc": tuple(new_acc),
            "origin": carry["origin"] + demand.sum(dtype=jnp.int32),
            "t0": t0 + jnp.int32(G),
        }
        out = {
            "hit": tuple(hit_lv),
            "node_hit": tuple(node_hit),
            "origin_miss": demand,
        }
        if instrument:
            out["telemetry"] = tuple(series)
            if grouped:
                out["telemetry_pressure"] = tuple(pressure)
        return new_carry, out

    carry0 = {
        "placed": placed0,
        "acc": _zero_acc(topo, sizes is not None),
        "origin": jnp.zeros((), jnp.int32),
        "t0": jnp.zeros((), jnp.int32),
    }
    return placed_chunk, carry0


def _chunk_program(topo: Topology, G: int, telemetry=None, **kw):
    """``(chunk, carry0)`` of the topology's engine: time-major when any
    level places copies selectively, level-major otherwise."""
    build = _placed_chunk_fn if topo.has_placement else _level_major_chunk_fn
    return build(topo, G, telemetry, **kw)


def _final_states(topo: Topology, carry):
    """Per-level stacked policy states of an engine's carry."""
    return tuple(carry["placed"][0]) if topo.has_placement else carry["states"]


def _final_tiers(topo: Topology, carry):
    """Per-level counter dicts (:func:`_tier_from_acc`) of an engine's carry."""
    states = _final_states(topo, carry)
    if topo.has_placement:
        _, _, fills, admitted = carry["placed"]
        return tuple(
            _tier_from_acc(
                lvl[0], carry["acc"][l], states[l],
                inserts=fills[l], admitted=admitted[l],
            )
            for l, lvl in enumerate(topo.levels)
        )
    return tuple(
        _tier_from_acc(lvl[0], carry["acc"][l], states[l])
        for l, lvl in enumerate(topo.levels)
    )


def _bounded_result(topo: Topology, carry, out):
    """The :func:`simulate_fleet` pytree of one chunk run from the fresh
    carry: the chunk's series plus the carry's states and counters."""
    result = {
        "hit": out["hit"],
        "node_hit": out["node_hit"],
        "tiers": _final_tiers(topo, carry),
        "states": _final_states(topo, carry),
        "origin_miss": out["origin_miss"],
    }
    if topo.has_placement:
        # admit levels' placement-sketch state (level index -> rows/seen)
        result["placement_states"] = carry["placed"][1]
    for k in ("telemetry", "telemetry_pressure"):
        if k in out:
            result[k] = out[k]
    return result


def _simulate_fleet_impl(
    topo: Topology, trace, assignment, telemetry=None, sizes=None, groups=None
):
    """One bounded run: the engine's chunk program applied once, from the
    fresh carry, to a chunk as long as the trace."""
    trace = trace.astype(jnp.int32)
    assignment = assignment.astype(jnp.int32)
    if sizes is not None:
        sizes = jnp.asarray(sizes, jnp.int32)
    groups, og = _group_tables(telemetry, groups)
    chunk, carry0 = _chunk_program(
        topo, trace.shape[0], telemetry, sizes=sizes, og=og, groups=groups
    )
    return _bounded_result(topo, *chunk(carry0, trace, assignment))


@functools.partial(jax.jit, static_argnums=(0, 3))
def simulate_fleet(
    topo: Topology, trace: jax.Array, assignment: jax.Array, telemetry=None,
    sizes=None, groups=None,
):
    """Run one trace through an N-tier topology. See module docstring.

    Returns a dict of arrays:
      ``hit``         tuple per level, (T,) bool — served at this level
      ``node_hit``    tuple per level, (K_l, T) bool — per-node hit series
      ``tiers``       tuple per level of counter dicts (requests/hits/
                      admitted_requests/inserts/evictions/count, shape (K_l,);
                      plus req_bytes/hit_bytes when ``sizes`` is given and
                      resident ``bytes`` for byte-capacity levels)
      ``states``      tuple per level of stacked final policy states
      ``origin_miss`` (T,) bool — missed every tier
    Placed topologies add ``placement_states`` (admit levels' sketches).

    ``sizes`` is the shared (n_objects,) int32 byte catalogue (traced;
    ``workloads.object_sizes``) — required for byte-capacity levels to be
    meaningful, optional byte accounting otherwise.

    With a static :class:`repro.telemetry.TelemetrySpec` the dict gains
    ``telemetry``: per level a (K_l, n_windows, N_METRICS) int32 windowed
    series accumulated inside the scan (docs/observability.md). A grouped
    spec (``telemetry.n_groups > 0``, with the ``groups`` id→group int32
    catalogue) widens that to (K_l, n_windows, n_groups, N_METRICS) and
    adds ``telemetry_pressure``: per level (K_l, n_windows, n_groups)
    cross-tenant eviction counts (a tenant's objects evicted by another
    tenant's requests).
    """
    return _simulate_fleet_impl(topo, trace, assignment, telemetry, sizes, groups)


@functools.partial(jax.jit, static_argnums=(0, 3))
def simulate_fleet_batch(
    topo: Topology, traces: jax.Array, assignments: jax.Array, telemetry=None,
    sizes=None, groups=None,
):
    """vmap the fleet over (S, T) trace samples in one device launch
    (``sizes``/``groups`` are shared across samples — one object universe)."""
    return jax.vmap(
        lambda tr, a: _simulate_fleet_impl(topo, tr, a, telemetry, sizes, groups)
    )(traces, assignments)
