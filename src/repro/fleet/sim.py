"""Jitted N-tier fleet simulator: one device launch per topology.

Two engines share this module, selected statically per topology:

* **Level-major** (all-lce placements, the default): every level runs the
  branch-free ``jax_cache.step`` as a single vmapped, masked scan over its
  nodes: node ``i`` at level ``l`` is *active* at trace position ``t`` iff
  the request routed to it (the edge assignment pushed up the parent tree)
  **and** no level below served it — i.e. each tier consumes exactly the
  interleaved miss stream of its children, in true request order. Each
  node steps its own requests, compacted (:func:`masked_scan`): a level's
  loop runs to its busiest node's load, not to the trace length, while
  every shape stays fixed, so the whole topology is jittable and vmaps
  over trace samples. plfua_dyn and instrumented levels keep the dense
  scan over every position.

* **Time-major** (any non-lce placement, :mod:`repro.fleet.placement`):
  cross-tier placement makes a tier's insert decision depend on *where the
  request was served above it* — information that only exists after the
  upper tiers' hit tests at the same trace position, so the per-level
  full-trace scans no longer factorise. The placed engine scans *time*
  instead: each step probes the miss path bottom-up (pre-update membership
  gathers), resolves the serving level, then applies fill-gated ``step``
  updates to the one consulted node per level. plfua_dyn's global-time
  hot-set refresh keeps its chunked hoisting: the time scan runs in chunks
  of the gcd of all plfua_dyn refresh periods and refreshes at chunk
  boundaries whose global position is a whole multiple of each level's
  period (partial tail periods never fire, as in ``_chunked_scan``).
  ``prob(1.0)`` topologies reproduce the level-major engine bit for bit
  (asserted in tests/test_placement.py) — the cross-validation between the
  two engines.

Decision parity: :mod:`repro.fleet.reference` runs the same topology with the
paper's pure-Python policy objects; tests assert identical per-level hit
sequences, final cache contents, and eviction counts (tests/test_fleet.py,
tests/test_placement.py). ``repro.cdn.simulate_hierarchy`` is now a thin
depth-2 wrapper over this module.
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
    "tier_counters",
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

    plfua_dyn routes through the dense chunked scan so its global-time
    hot-set refresh fires at trace-position boundaries for every instance,
    active or not (the reference oracle drives ``refresh_now`` on the same
    timer). ``instrument`` (static) switches to the dense telemetry twin,
    which returns ``(state, hits, events)`` with the per-position event
    series (identical state/hit trajectory — asserted in
    tests/test_telemetry.py). :func:`compacts` says which scan a level gets.
    ``sizes``/``cap_bytes`` are the byte-capacity inputs of
    ``jax_cache.step``; ``og`` the (n_objects, n_groups) group one-hot for
    group-segmented telemetry."""
    if instrument:
        return jax_cache.instrumented_scan(
            spec, state, trace, active, cap, sizes=sizes, cap_bytes=cap_bytes, og=og
        )
    if spec.kind == "plfua_dyn":
        return jax_cache._chunked_scan(
            spec, state, trace, active, cap, sizes=sizes, cap_bytes=cap_bytes
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
        ns, hit = jax_cache.step(spec, s, ids[i], cap, sizes=sizes, cap_bytes=cap_bytes)
        return i + 1, ns, hits.at[i].set(hit)

    # zeros_like keeps ``active``'s sharding (varying under a shard_map)
    _, state, hits = jax.lax.while_loop(
        lambda carry: carry[0] < n, body, (jnp.int32(0), state, jnp.zeros_like(active))
    )
    with jax.named_scope("repro.compact"):
        return state, jnp.take(hits, jnp.maximum(slot, 0)) & active


def compacts(spec: PolicySpec, instrument: bool = False) -> bool:
    """Whether :func:`masked_scan` steps a node through its own requests
    only (True) or through every position of the trace (plfua_dyn's
    global-time refresh, the telemetry twin's per-position series)."""
    return not instrument and spec.kind != "plfua_dyn"


def tier_counters(spec: PolicySpec, hits, active, trace, state, sizes=None):
    """Derived per-node accounting, all from the hit/active series + final state.

    Inserts are implied by the policy semantics (every admitted miss inserts),
    so evictions = inserts - final occupancy. Sketch kinds carry the insert
    count in state (admission there is data-dependent, and plfua_dyn's hot
    mask changes over time, so neither can be derived from the final state);
    in byte mode *every* kind carries it (an admitted object may not fit).
    With ``sizes`` the dict gains per-node byte accounting: ``req_bytes`` /
    ``hit_bytes`` traffic sums and, in byte mode, the resident ``bytes``.
    """
    miss = active & ~hits
    count = state["count"]
    if spec.kind == "plfua":
        admitted = jnp.take(state["hot"], trace, axis=-1)  # hot mask gathered at x_t
        inserts = (
            state["inserts"] if spec.capacity_bytes else (miss & admitted).sum(-1)
        )
        admitted_requests = (active & admitted).sum(-1)
    elif spec.kind in jax_cache.SKETCH_POLICY_KINDS:
        inserts = state["inserts"]
        # every hit touches policy metadata; every insert is an admitted miss
        admitted_requests = hits.sum(-1) + inserts
    else:
        inserts = state["inserts"] if spec.capacity_bytes else miss.sum(-1)
        admitted_requests = active.sum(-1)
    out = {
        "requests": active.sum(-1),
        "hits": hits.sum(-1),
        "admitted_requests": admitted_requests,
        "inserts": inserts,
        "evictions": inserts - count,
        "count": count,
    }
    if sizes is not None:
        sz_t = jnp.take(sizes, trace, axis=-1).astype(jnp.int32)  # (T,)
        out["req_bytes"] = (active * sz_t).sum(-1)
        out["hit_bytes"] = (hits * sz_t).sum(-1)
    if spec.capacity_bytes:
        out["bytes"] = state["bytes"]
    return out


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


def run_level(
    specs: tuple[PolicySpec, ...], trace, active, *, instrument=False, sizes=None,
    og=None,
):
    """One level: vmap the masked scan over its nodes.

    ``active``: (K, T) bool — request t routed here and unserved below.
    Returns (stacked final states, (K, T) hit series), plus the vmapped
    per-node event series when ``instrument`` is set. ``sizes`` is the
    global per-object byte array, shared by every node, and ``og`` the
    shared group one-hot (grouped telemetry)."""
    s0 = specs[0]
    states = stack_level_state(specs)
    caps = jnp.array([s.capacity for s in specs], jnp.int32)
    if s0.capacity_bytes:
        caps_b = jnp.array([s.capacity_bytes for s in specs], jnp.int32)
        return jax.vmap(
            lambda st, act, cap, capb: masked_scan(
                s0, st, trace, act, cap,
                instrument=instrument, sizes=sizes, cap_bytes=capb, og=og,
            )
        )(states, active, caps, caps_b)
    return jax.vmap(
        lambda st, act, cap: masked_scan(
            s0, st, trace, act, cap, instrument=instrument, sizes=sizes, og=og
        )
    )(states, active, caps)


def level_series(
    spec: PolicySpec, telemetry, trace_len, hits, active, events, groups_t=None
):
    """Bucket one level's vmapped event series into (K, n_windows, N_METRICS)
    (a group axis before N_METRICS when ``telemetry.n_groups > 0``) — the
    level-major engine has no placement gate, so fill offers default to
    the miss count (every miss of an active node is offered)."""
    return jax_cache.telemetry_series(
        spec, telemetry, trace_len, hits, events, active=active, groups_t=groups_t
    )


def upper_levels(
    topo: Topology, trace, assigns, demand, *, telemetry=None, sizes=None,
    og=None, groups_t=None,
):
    """Run levels 1..L-1 given the edge tier's surviving ``demand`` stream.

    Shared by the single-device path and the shard_map path (which computes
    level 0 under a device mesh and the global miss stream via a collective).
    Returns (per-level hit series list, counters list, states list, demand[,
    per-level telemetry series list when ``telemetry`` is set — grouped runs
    additionally append the per-level eviction-pressure list]).
    """
    instrument = telemetry is not None
    grouped = instrument and telemetry.n_groups > 0
    level_hits, counters, states_out, series_out, pressure_out = [], [], [], [], []
    for l in range(1, topo.n_levels):
        specs = topo.levels[l]
        K = len(specs)
        active = (
            assigns[l][None, :] == jnp.arange(K, dtype=jnp.int32)[:, None]
        ) & demand[None, :]
        if instrument:
            states, hits, events = run_level(
                specs, trace, active, instrument=True, sizes=sizes, og=og
            )
            series_out.append(
                level_series(
                    specs[0], telemetry, trace.shape[0], hits, active, events,
                    groups_t=groups_t,
                )
            )
            if grouped:
                pressure_out.append(
                    telemetry_spec.windowed_pressure(
                        telemetry.window, groups_t, events["evict_g"], xp=jnp
                    )
                )
        else:
            states, hits = run_level(specs, trace, active, sizes=sizes)
        hit_l = hits.any(axis=0)
        level_hits.append(hits)
        counters.append(tier_counters(specs[0], hits, active, trace, states, sizes))
        states_out.append(states)
        demand = demand & ~hit_l
    if grouped:
        return level_hits, counters, states_out, demand, series_out, pressure_out
    if instrument:
        return level_hits, counters, states_out, demand, series_out
    return level_hits, counters, states_out, demand


def _simulate_fleet_impl(
    topo: Topology, trace, assignment, telemetry=None, sizes=None, groups=None
):
    if topo.has_placement:
        # non-lce placement couples the levels at each trace position ->
        # the time-major engine (see module docstring)
        return _simulate_placed_impl(topo, trace, assignment, telemetry, sizes, groups)
    trace = trace.astype(jnp.int32)
    assignment = assignment.astype(jnp.int32)
    if sizes is not None:
        sizes = jnp.asarray(sizes, jnp.int32)
    og, groups_t = jax_cache.group_scatter_arrays(telemetry, groups, trace)
    grouped = og is not None
    assigns = level_assignments(topo, trace, assignment)

    specs0 = topo.levels[0]
    E = len(specs0)
    active0 = assigns[0][None, :] == jnp.arange(E, dtype=jnp.int32)[:, None]
    pressure = []
    if telemetry is not None:
        edge_states, edge_hits, edge_events = run_level(
            specs0, trace, active0, instrument=True, sizes=sizes, og=og
        )
        edge_series = level_series(
            specs0[0], telemetry, trace.shape[0], edge_hits, active0, edge_events,
            groups_t=groups_t,
        )
        demand = ~edge_hits.any(axis=0)
        if grouped:
            pressure.append(
                telemetry_spec.windowed_pressure(
                    telemetry.window, groups_t, edge_events["evict_g"], xp=jnp
                )
            )
            hits_up, counters_up, states_up, demand, series_up, pressure_up = (
                upper_levels(
                    topo, trace, assigns, demand, telemetry=telemetry,
                    sizes=sizes, og=og, groups_t=groups_t,
                )
            )
            pressure.extend(pressure_up)
        else:
            hits_up, counters_up, states_up, demand, series_up = upper_levels(
                topo, trace, assigns, demand, telemetry=telemetry, sizes=sizes
            )
    else:
        edge_states, edge_hits = run_level(specs0, trace, active0, sizes=sizes)
        demand = ~edge_hits.any(axis=0)
        hits_up, counters_up, states_up, demand = upper_levels(
            topo, trace, assigns, demand, sizes=sizes
        )
    all_hits = [edge_hits, *hits_up]
    out = {
        # (T,) bool per level: request served at this level
        "hit": tuple(h.any(axis=0) for h in all_hits),
        # (K_l, T) bool per level: which node served it
        "node_hit": tuple(all_hits),
        # per-level counter dicts, arrays of shape (K_l,)
        "tiers": (
            tier_counters(specs0[0], edge_hits, active0, trace, edge_states, sizes),
            *counters_up,
        ),
        # per-level stacked final policy states
        "states": (edge_states, *states_up),
        # (T,) bool: missed every tier -> fetched from origin
        "origin_miss": demand,
    }
    if telemetry is not None:
        # (K_l, n_windows, N_METRICS) int32 per level (docs/observability.md);
        # grouped runs carry (K_l, n_windows, n_groups, N_METRICS) instead
        out["telemetry"] = (edge_series, *series_up)
        if grouped:
            # per level (K_l, n_windows, n_groups): evictions of each group's
            # objects at steps requested by *another* group (cross-tenant
            # eviction pressure)
            out["telemetry_pressure"] = tuple(pressure)
    return out


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
    """Chunk length of the placed time scan: the gcd of every plfua_dyn
    level's refresh period (their global-time refreshes all land on chunk
    boundaries), or None when no level needs one."""
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
    level0_states=None,
    level0_caps=None,
    edge_axis: str | None = None,
    instrument: bool = False,
    sizes=None,
    og=None,
):
    """Shared setup of the time-major placed engine: the per-level specs,
    the zero carry (states / placement sketches / fill + admitted counters)
    and the ``step_t`` scan body. Used by the bounded :func:`_placed_run`
    and the streaming engine (:mod:`repro.fleet.stream`), so both scan the
    *same program* over their chunks — the bit-identity the stream↔bounded
    differential tests pin. Returns ``(specs, dyn_levels, carry0, step_t)``.
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
    if level0_states is not None:
        states[0] = level0_states
    if level0_caps is not None:
        caps[0] = level0_caps
    n_local = int(states[0]["count"].shape[0])  # E, or E/D under a mesh

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
        K = n_local if l == 0 else len(topo.levels[l])
        pstates[l] = dict(
            rows=jnp.zeros((K, sketch.DEPTH, width), jnp.int32),
            seen=jnp.zeros((K,), jnp.int32),
        )
    fills = [
        jnp.zeros((int(states[l]["count"].shape[0]),), jnp.int32) for l in range(L)
    ]
    admitted = [jnp.zeros_like(f) for f in fills]

    def step_t(carry, inp):
        states, pstates, fills, admitted = carry
        t, x, valid, nodes = inp
        # ---- probe the miss path bottom-up on pre-update membership
        with jax.named_scope("repro.probe"):
            consulted, hits = [], []
            demand = valid
            if edge_axis is not None:
                offset = jax.lax.axis_index(edge_axis).astype(jnp.int32) * n_local
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
                cap = caps[l][node]
                cap_b = caps_b[l][node] if spec.capacity_bytes else None
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
                # same admitted_requests conventions as tier_counters
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


def _placed_chunk_fn(specs, dyn_levels, step_t, *, instrument=False, og=None):
    """The placed engine's per-chunk scan body: scan ``step_t`` over one
    chunk, then apply each plfua_dyn level's vmapped hot-set refresh where
    that level's fire flag is set (with churn capture under ``instrument``).
    Shared between the bounded host-scheduled scan and the streaming
    traced-global-time scan."""

    def chunk_fn(carry, inp):
        xs, fire_c = inp
        carry, out = jax.lax.scan(step_t, carry, xs)
        states, pstates, fills, admitted = carry
        states = list(states)
        churns, churns_g = [], []
        for j, l in enumerate(dyn_levels):
            refreshed = jax.vmap(
                lambda s: jax_cache.refresh_hot(specs[l], s)
            )(states[l])
            if instrument:
                diff = states[l]["hot"] != refreshed["hot"]  # (K, N)
                churns.append(
                    jnp.where(fire_c[j], diff.sum(-1).astype(jnp.int32), 0)
                )
                if og is not None:
                    churns_g.append(
                        jnp.where(fire_c[j], diff.astype(jnp.int32) @ og, 0)
                    )
            states[l] = jax.tree_util.tree_map(
                lambda o, r: jnp.where(fire_c[j], r, o), states[l], refreshed
            )
        carry = (tuple(states), pstates, fills, admitted)
        if instrument:
            hits, tel = out
            return carry, (hits, tel, tuple(churns), tuple(churns_g))
        return carry, out

    return chunk_fn


def _placed_untile(out, T, n_levels, dyn_levels, fire, *, instrument=False, og=None):
    """Flatten a placed chunk scan's stacked output back to trace-major.

    ``fire`` is the (n_chunks, n_dyn) refresh schedule — host numpy for the
    bounded engine, traced for the streaming one (both flow through the same
    jnp ops). Truncation to ``[:T]`` drops the bounded engine's padded tail;
    streaming chunks pass ``T == n_chunks * chunk_len`` so nothing is cut.
    Returns ``hit_lv`` or ``(hit_lv, tel_lv)`` under ``instrument``."""
    if not instrument:
        return [h.reshape(-1)[:T] for h in out]
    hits, tel, churns, churns_g = out
    hit_lv = [h.reshape(-1)[:T] for h in hits]
    # un-chunk the event series: scalars (n_chunks, G) -> (T,); the per-step
    # occupancy snapshot (n_chunks, G, K) -> (K, T); grouped events keep
    # their trailing group axis — evict_g (n_chunks, G, n_g) -> (T, n_g),
    # count_g (n_chunks, G, K, n_g) -> (K, T, n_g)
    tel_lv = []
    for l in range(n_levels):
        d = {}
        for k, v in tel[l].items():
            if k == "evict_g":
                d[k] = v.reshape((-1,) + v.shape[2:])[:T]
            elif k == "count_g":
                d[k] = jnp.moveaxis(v.reshape((-1,) + v.shape[2:])[:T], 0, 1)
            elif v.ndim == 2:
                d[k] = v.reshape(-1)[:T]
            else:
                d[k] = v.reshape(-1, v.shape[-1])[:T].T
        tel_lv.append(d)
    fire = jnp.asarray(fire)
    n_chunks = fire.shape[0]
    for j, l in enumerate(dyn_levels):
        K = churns[j].shape[-1]
        # all nodes of a dyn level refresh on the same global-time schedule
        tel_lv[l]["fired"] = jnp.broadcast_to(fire[:, j], (K, n_chunks))
        tel_lv[l]["churn"] = churns[j].T  # (n_chunks, K) -> (K, n_chunks)
        if og is not None:
            # (n_chunks, K, n_g) -> (K, n_chunks, n_g)
            tel_lv[l]["churn_g"] = jnp.moveaxis(churns_g[j], 0, 1)
    return hit_lv, tel_lv


def _placed_run(
    topo: Topology,
    trace,
    assigns,
    *,
    level0_states=None,
    level0_caps=None,
    edge_axis: str | None = None,
    instrument: bool = False,
    sizes=None,
    og=None,
):
    """The time-major scan shared by the single-device and edge-sharded
    placed paths. ``trace`` (T,) int32, ``assigns`` one (T,) int32 per level.

    With ``edge_axis`` set this runs *inside* a shard_map body: the level-0
    stacked state/caps hold only this device's contiguous slice of edges
    (``level0_states`` / ``level0_caps``), the probe rebuilds the global
    edge-served bit with one ``psum`` per step, and upper levels run
    replicated (identical on every device, being pure functions of
    replicated inputs).

    Returns ``(states, pstates, fills, admitted, hit_lv)`` where ``hit_lv``
    is one (T,) bool per level, ``fills``/``admitted`` one (K_l,) int32 per
    level (level 0 local in the sharded case), and ``pstates`` maps admit
    levels to their placement-sketch state.

    ``instrument`` (static, single-device only) additionally emits the
    per-level telemetry event series and extends the return to
    ``(..., hit_lv, tel_lv, chunk_len)``; the placement gate makes
    ``fill_offers`` engine-computed here (a consulted miss whose gate was
    open), unlike the level-major engine where every miss is an offer.
    """
    (T,) = trace.shape
    specs, dyn_levels, carry0, step_t = _placed_prelude(
        topo,
        level0_states=level0_states,
        level0_caps=level0_caps,
        edge_axis=edge_axis,
        instrument=instrument,
        sizes=sizes,
        og=og,
    )

    # chunked over the gcd of the plfua_dyn refresh periods so the
    # estimate-all + top-k stays amortised (cf. jax_cache._chunked_scan)
    G = _dyn_chunk(topo) or T
    n_chunks = -(-T // G)
    pad = n_chunks * G - T
    t_arr = jnp.arange(n_chunks * G, dtype=jnp.int32)
    x_p = jnp.concatenate([trace, jnp.zeros((pad,), jnp.int32)])
    valid_p = jnp.concatenate(
        [jnp.ones((T,), jnp.bool_), jnp.zeros((pad,), jnp.bool_)]
    )
    assigns_p = tuple(
        jnp.concatenate([a, jnp.zeros((pad,), jnp.int32)]) for a in assigns
    )
    # a refresh fires only at boundaries that are whole multiples of the
    # level's own period *and* lie within the real trace (no partial tail)
    fire = np.array(
        [
            [
                (c + 1) * G <= T
                and ((c + 1) * G) % specs[l].effective_refresh == 0
                for l in dyn_levels
            ]
            for c in range(n_chunks)
        ],
        bool,
    ).reshape(n_chunks, len(dyn_levels))

    chunk_fn = _placed_chunk_fn(specs, dyn_levels, step_t, instrument=instrument, og=og)
    chunk = lambda a: a.reshape(n_chunks, G, *a.shape[1:])
    (states, pstates, fills, admitted), out = jax.lax.scan(
        chunk_fn,
        carry0,
        (
            (
                chunk(t_arr),
                chunk(x_p),
                chunk(valid_p),
                tuple(chunk(a) for a in assigns_p),
            ),
            jnp.asarray(fire),
        ),
    )
    untiled = _placed_untile(
        out, T, topo.n_levels, dyn_levels, fire, instrument=instrument, og=og
    )
    if not instrument:
        return list(states), pstates, list(fills), list(admitted), untiled
    hit_lv, tel_lv = untiled
    return list(states), pstates, list(fills), list(admitted), hit_lv, tel_lv, G


def assemble_placed(
    topo: Topology,
    assigns,
    states,
    pstates,
    fills,
    admitted,
    hit_lv,
    *,
    telemetry=None,
    tel_lv=None,
    chunk_len=None,
    trace=None,
    sizes=None,
    groups_t=None,
):
    """Fold a ``_placed_run`` result into the ``simulate_fleet`` pytree.

    Per-node activity is recomputed from the hit series (level ``l`` node
    ``k`` is active at ``t`` iff the request routed to it and no level below
    served it) — identical to the level-major masks by construction. With
    ``telemetry``/``tel_lv`` the per-step events (which are consulted-node
    scalars) are scattered to nodes through the same masks and bucketed;
    ``trace``/``sizes`` add the per-node byte accounting and ``groups_t``
    (per-position group ids) the group-segmented series + pressure."""
    T = hit_lv[0].shape[0]
    grouped = telemetry is not None and telemetry.n_groups > 0
    demand = jnp.ones((T,), jnp.bool_)
    sz_t = (
        None
        if sizes is None
        else jnp.take(jnp.asarray(sizes, jnp.int32), trace, axis=-1)
    )
    tiers, node_hits, series, pressure = [], [], [], []
    for l in range(topo.n_levels):
        K = len(topo.levels[l])
        active = (
            assigns[l][None, :] == jnp.arange(K, dtype=jnp.int32)[:, None]
        ) & demand[None, :]
        nh = active & hit_lv[l][None, :]
        count = states[l]["count"]
        tier = {
            "requests": active.sum(-1),
            "hits": nh.sum(-1),
            "admitted_requests": admitted[l],
            "inserts": fills[l],
            "evictions": fills[l] - count,
            "count": count,
        }
        if sz_t is not None:
            tier["req_bytes"] = (active * sz_t[None, :]).sum(-1)
            tier["hit_bytes"] = (nh * sz_t[None, :]).sum(-1)
        if topo.levels[l][0].capacity_bytes:
            tier["bytes"] = states[l]["bytes"]
        tiers.append(tier)
        node_hits.append(nh)
        if telemetry is not None:
            ev = tel_lv[l]
            per_node = lambda s: active & s[None, :]
            aging = ev.get("aging")
            if grouped:
                # scatter the consulted-node victim-group counts to nodes
                # through the same activity masks as the scalar events
                evict_g = active[:, :, None] * ev["evict_g"][None, :, :]
                series.append(
                    telemetry_spec.grouped_series_from_run(
                        telemetry.window,
                        T,
                        telemetry.n_groups,
                        groups_t,
                        hits=nh,
                        active=active,
                        fills=per_node(ev["fill"]),
                        evictions_g=evict_g,
                        occupancy_g=ev["count_g"],
                        offers=per_node(ev["offer"]),
                        aging=None if aging is None else per_node(aging),
                        fired=ev.get("fired"),
                        churn_g=ev.get("churn_g"),
                        hit_bytes=None if sz_t is None else nh * sz_t[None, :],
                        miss_bytes=(
                            None
                            if sz_t is None
                            else (active & ~nh) * sz_t[None, :]
                        ),
                        chunk_len=chunk_len,
                        xp=jnp,
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
                        telemetry.window,
                        T,
                        hits=nh,
                        active=active,
                        fills=per_node(ev["fill"]),
                        # int32 victim counts, scattered to the consulted node
                        evictions=active * ev["evict"][None, :],
                        occupancy=ev["count"],
                        offers=per_node(ev["offer"]),
                        aging=None if aging is None else per_node(aging),
                        fired=ev.get("fired"),
                        churn=ev.get("churn"),
                        hit_bytes=None if sz_t is None else nh * sz_t[None, :],
                        miss_bytes=(
                            None
                            if sz_t is None
                            else (active & ~nh) * sz_t[None, :]
                        ),
                        chunk_len=chunk_len,
                        xp=jnp,
                    )
                )
        demand = demand & ~hit_lv[l]
    out = {
        "hit": tuple(hit_lv),
        "node_hit": tuple(node_hits),
        "tiers": tuple(tiers),
        "states": tuple(states),
        "origin_miss": demand,
        # admit levels' placement-sketch state (level index -> rows/seen)
        "placement_states": pstates,
    }
    if telemetry is not None:
        out["telemetry"] = tuple(series)
        if grouped:
            out["telemetry_pressure"] = tuple(pressure)
    return out


def _simulate_placed_impl(
    topo: Topology, trace, assignment, telemetry=None, sizes=None, groups=None
):
    trace = trace.astype(jnp.int32)
    assignment = assignment.astype(jnp.int32)
    if sizes is not None:
        sizes = jnp.asarray(sizes, jnp.int32)
    og, groups_t = jax_cache.group_scatter_arrays(telemetry, groups, trace)
    assigns = level_assignments(topo, trace, assignment)
    if telemetry is not None:
        states, pstates, fills, admitted, hit_lv, tel_lv, G = _placed_run(
            topo, trace, assigns, instrument=True, sizes=sizes, og=og
        )
        return assemble_placed(
            topo, assigns, states, pstates, fills, admitted, hit_lv,
            telemetry=telemetry, tel_lv=tel_lv, chunk_len=G,
            trace=trace, sizes=sizes, groups_t=groups_t,
        )
    states, pstates, fills, admitted, hit_lv = _placed_run(
        topo, trace, assigns, sizes=sizes
    )
    return assemble_placed(
        topo, assigns, states, pstates, fills, admitted, hit_lv,
        trace=trace, sizes=sizes,
    )


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
