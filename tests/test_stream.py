"""Stream ↔ bounded differential suite.

A bounded fleet run (``simulate_fleet``) is one chunk of the same chunk
program ``FleetStream`` pushes, as long as the trace. So K pushed chunks of
length G must reproduce that one chunk (or the flat ``jax_cache.simulate``)
on the concatenated trace exactly — hit series, final states, tier
counters, grouped telemetry series stitched across chunk boundaries,
eviction pressure. What the comparison pins is what a chunk boundary
touches: the donated carry, the stream position ``t0`` and the stitching
of telemetry. That promise is what makes the line-rate bench numbers
(the ``fleet_stream`` benchmark group) legitimate measurements of *the
same algorithms* the paper tables score, so this suite pins it over:

* all 9 policy kinds × stationary/churn on a depth-2 tree with grouped
  telemetry (level-major engine underneath);
* the placed engine (lcd / prob / admit) on a plfua_dyn tree, where the
  traced global-time fire schedule of K chunks must reproduce that of one
  chunk — including a chunk length that does *not* divide the refresh
  period (gcd sub-chunking);
* the fast compact-lane path against the dense flat simulator for every
  FAST_KIND (the candidate-prefix bound, tie-breaks included; arc's whole
  directory as lanes, filled to its 2c bound);
* the double-buffered ``stream_fleet`` driver against a bounded run over
  the same on-device-generated chunks;
* position-keyed routers (``sticky``, ``round_robin``) at every level,
  routed on device from the absolute stream position, sessions straddling
  chunk boundaries, against the bounded engine and the plain reference.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro import fleet, workloads
from repro.core import jax_cache
from repro.cdn import router
from repro.core.jax_cache import PolicySpec
from repro.fleet.reference import simulate_fleet_reference
from repro.fleet.stream import FAST_KINDS, FleetStream, StreamConfig, stream_fleet
from repro.telemetry import TelemetrySpec

N, G, K = 96, 50, 4
T = G * K
ALL_KINDS = ("lru", "lfu", "wlfu", "plfu", "plfua", "plfua_dyn", "tinylfu", "gdsf", "arc")

_rng = np.random.default_rng(0)
GROUPS = _rng.integers(0, 3, size=N).astype(np.int32)
SIZES = _rng.integers(1, 9, size=N).astype(np.int32)
TEL = TelemetrySpec(window=25, n_groups=3)


def _topo(kind, **kw):
    return fleet.tree(
        n_objects=N, widths=(3, 1), kinds=kind, capacities=(5, 13),
        window=48 if kind == "wlfu" else 0,
        refresh=30 if kind == "plfua_dyn" else 0,
        **kw,
    )


def _run_stream(cfg, trace, assignment, **kw):
    """Push the trace through in K chunks; return (FleetStream, per-chunk hit
    tuples)."""
    fs = FleetStream(cfg, **kw)
    hits = []
    for c in range(K):
        sl = slice(c * G, (c + 1) * G)
        a = None if assignment is None else jnp.asarray(assignment[sl])
        out = fs.push(jnp.asarray(trace[sl]), a)
        hits.append(out["hit"])
    return fs, hits


def _assert_stream_matches(bounded, fs, hits_chunks, *, tel=False, ctx=""):
    """Full bounded-vs-stream parity: hit series, counters, states, rollup,
    and (with ``tel``) the stitched telemetry series + pressure."""
    st = fs.stats()
    for l in range(len(bounded["hit"])):
        cat = np.concatenate([np.asarray(h[l]) for h in hits_chunks])
        np.testing.assert_array_equal(
            cat, np.asarray(bounded["hit"][l]), err_msg=f"{ctx}: hit level {l}"
        )
        for k in bounded["tiers"][l]:
            np.testing.assert_array_equal(
                np.asarray(bounded["tiers"][l][k]), np.asarray(st.tiers[l][k]),
                err_msg=f"{ctx}: tiers[{l}][{k}]",
            )
        for k in bounded["states"][l]:
            np.testing.assert_array_equal(
                np.asarray(bounded["states"][l][k]),
                np.asarray(fs.states()[l][k]),
                err_msg=f"{ctx}: states[{l}][{k}]",
            )
    assert st.requests == T and st.chunks == K
    assert st.origin_misses == int(np.asarray(bounded["origin_miss"]).sum()), ctx
    assert st.hits == T - st.origin_misses
    assert st.total_chr == pytest.approx(st.hits / T)
    if tel:
        for l in range(len(bounded["telemetry"])):
            np.testing.assert_array_equal(
                np.asarray(bounded["telemetry"][l]), np.asarray(st.telemetry[l]),
                err_msg=f"{ctx}: telemetry level {l}",
            )
        for l in range(len(bounded["telemetry_pressure"])):
            np.testing.assert_array_equal(
                np.asarray(bounded["telemetry_pressure"][l]),
                np.asarray(st.telemetry_pressure[l]),
                err_msg=f"{ctx}: pressure level {l}",
            )


def _device_assignment(topo, trace):
    """The whole trace's edge assignment as ``push`` routes it on device."""
    return np.asarray(router.route_device(
        jnp.asarray(trace), topo.n_edges, topo.router, session_len=topo.session_len
    ))


def _recount_node_steps(topo, trace, assignment, level_hit, chunk_len, telemetry=False):
    """Sum over chunks and levels of what the level-major engine's loops
    run: ``K_l`` x the busiest node's load at a compacted level, ``K_l`` x
    ``chunk_len`` at a dense one (plfua_dyn, or any level with telemetry).
    The loads come from the levels' assignments and the served levels."""
    assigns = [np.asarray(a) for a in fleet.level_assignments(
        topo, jnp.asarray(trace), jnp.asarray(assignment))]
    reached = np.ones(len(trace), bool)
    total = 0
    for l, lvl in enumerate(topo.levels):
        K = len(lvl)
        dense = telemetry or lvl[0].kind == "plfua_dyn"
        for c in range(len(trace) // chunk_len):
            sl = slice(c * chunk_len, (c + 1) * chunk_len)
            loads = np.bincount(assigns[l][sl][reached[sl]], minlength=K)
            total += K * (chunk_len if dense else int(loads.max()))
        reached &= ~np.asarray(level_hit[l])
    return total


def _assert_routed_stream_matches(topo, trace, chunk_len):
    """Push ``trace`` in chunks with no assignment; the stream equals the
    bounded engine on the concatenation (per-level and per-node hits, tier
    counters, states) and the plain reference (per-level hits), its lane
    counters count the dense node-step grid and the active node-steps, and
    ``node_steps`` what the engine's loops ran."""
    n = len(trace) // chunk_len
    assignment = _device_assignment(topo, trace)
    bounded = fleet.simulate_fleet(topo, jnp.asarray(trace), jnp.asarray(assignment))
    ref = simulate_fleet_reference(topo, trace, assignment)
    fs = FleetStream(StreamConfig(topo=topo, chunk_len=chunk_len))
    outs = [fs.push(jnp.asarray(trace[c * chunk_len:(c + 1) * chunk_len])) for c in range(n)]
    st = fs.stats()
    reached = np.ones(len(trace), bool)
    n_reached = 0
    for l in range(topo.n_levels):
        for key in ("hit", "node_hit"):
            cat = np.concatenate([np.asarray(o[key][l]) for o in outs], axis=-1)
            np.testing.assert_array_equal(cat, np.asarray(bounded[key][l]),
                                          err_msg=f"{key} level {l}")
        np.testing.assert_array_equal(np.asarray(bounded["hit"][l]), ref.level_hit[l],
                                      err_msg=f"reference level {l}")
        for k in bounded["tiers"][l]:
            np.testing.assert_array_equal(np.asarray(bounded["tiers"][l][k]),
                                          np.asarray(st.tiers[l][k]), err_msg=f"tiers[{l}][{k}]")
        for k in bounded["states"][l]:
            np.testing.assert_array_equal(np.asarray(bounded["states"][l][k]),
                                          np.asarray(fs.states()[l][k]), err_msg=f"states[{l}][{k}]")
        n_reached += int(reached.sum())
        reached &= ~ref.level_hit[l]
    assert st.origin_misses == int(reached.sum())
    stepped = topo.n_levels if topo.has_placement else topo.n_nodes
    assert st.lanes == n * chunk_len * stepped
    assert st.lanes_valid == n_reached
    if topo.has_placement:
        assert st.node_steps == st.lanes
    else:
        assert st.node_steps == _recount_node_steps(
            topo, trace, assignment, ref.level_hit, chunk_len
        )
        assert st.lanes_valid <= st.node_steps <= st.lanes
    return st


# ----------------------------------------------------------- config contract
def test_stream_config_validation():
    topo = _topo("lru")
    with pytest.raises(ValueError, match="chunk_len"):
        StreamConfig(topo=topo, chunk_len=0)
    # position-keyed upper routers route from the absolute stream position,
    # so a chunked stream equals the bounded engine on the whole trace
    sticky = fleet.tree(
        n_objects=N, widths=(3, 2, 1), kinds="lru", capacities=(5, 9, 13),
        routers=("hash", "sticky", "tree"),
    )
    trace = workloads.make_traces("stationary", N, 1, T, seed=21)[0]
    _assert_routed_stream_matches(sticky, trace, G)
    # telemetry windows must tile the chunk so series stitch by concatenation
    with pytest.raises(ValueError, match="window"):
        StreamConfig(topo=topo, chunk_len=G, telemetry=TelemetrySpec(window=30))
    # fast-path preconditions
    with pytest.raises(ValueError, match="depth-1"):
        StreamConfig(topo=topo, chunk_len=G, fast=True)
    flat_wlfu = fleet.tree(n_objects=N, widths=(1,), kinds="wlfu", capacities=13, window=48)
    with pytest.raises(ValueError, match="fast=True supports"):
        StreamConfig(topo=flat_wlfu, chunk_len=G, fast=True)
    flat = fleet.tree(n_objects=N, widths=(1,), kinds="lru", capacities=13)
    with pytest.raises(ValueError, match="telemetry"):
        StreamConfig(
            topo=flat, chunk_len=G, fast=True, telemetry=TelemetrySpec(window=25)
        )
    dyn = fleet.tree(
        n_objects=N, widths=(1,), kinds="plfua_dyn", capacities=13, refresh=30
    )
    with pytest.raises(ValueError, match="refresh"):
        StreamConfig(topo=dyn, chunk_len=G, fast=True)  # 30 % 50 != 0


def test_stream_push_contract():
    topo = _topo("lru")
    fs = FleetStream(StreamConfig(topo=topo, chunk_len=G))
    with pytest.raises(ValueError, match="shape"):
        fs.push(jnp.zeros((G + 1,), jnp.int32))
    # a sticky *edge* router is routed on device at the chunk's stream
    # position: pushing no assignment equals pushing the whole trace's
    # assignment, sliced
    sticky_edge = fleet.tree(
        n_objects=N, widths=(3, 1), kinds="lru", capacities=(5, 13),
        router="sticky",
    )
    trace = workloads.make_traces("stationary", N, 1, T, seed=22)[0]
    assignment = _device_assignment(sticky_edge, trace)
    routed = FleetStream(StreamConfig(topo=sticky_edge, chunk_len=G))
    given = FleetStream(StreamConfig(topo=sticky_edge, chunk_len=G))
    for c in range(K):
        sl = slice(c * G, (c + 1) * G)
        a = routed.push(jnp.asarray(trace[sl]))
        b = given.push(jnp.asarray(trace[sl]), jnp.asarray(assignment[sl]))
        for l in range(2):
            np.testing.assert_array_equal(np.asarray(a["node_hit"][l]),
                                          np.asarray(b["node_hit"][l]))
    for l in range(2):
        for k in routed.states()[l]:
            np.testing.assert_array_equal(np.asarray(routed.states()[l][k]),
                                          np.asarray(given.states()[l][k]))


# --------------------------------------------- level-major engine, all kinds
@pytest.mark.parametrize("scenario", ["stationary", "churn"])
@pytest.mark.parametrize("kind", ALL_KINDS)
def test_stream_level_major_bit_identity(kind, scenario):
    """K chunks == one bounded simulate_fleet, all 9 kinds, with grouped
    telemetry + byte accounting stitched across chunk boundaries. G=50 does
    not divide plfua_dyn's refresh=30: the stream's gcd sub-chunking must
    reproduce the bounded global-time fire schedule."""
    topo = _topo(kind)
    trace = workloads.make_traces(scenario, N, 1, T, seed=3)[0]
    assignment = topo.assignment(trace)
    bounded = fleet.simulate_fleet(
        topo, jnp.asarray(trace), jnp.asarray(assignment), TEL,
        sizes=SIZES, groups=GROUPS,
    )
    cfg = StreamConfig(topo=topo, chunk_len=G, telemetry=TEL)
    fs, hits = _run_stream(cfg, trace, assignment, sizes=SIZES, groups=GROUPS)
    _assert_stream_matches(
        bounded, fs, hits, tel=True, ctx=f"{kind}/{scenario}"
    )


def test_stream_group_sum_identity():
    """The stitched grouped series sums over the group axis to the bounded
    *ungrouped* series — the group axis stays observational across chunk
    boundaries (window spill or double-bucketing at a seam would break it)."""
    topo = _topo("plfua_dyn")
    trace = workloads.make_traces("churn", N, 1, T, seed=11)[0]
    assignment = topo.assignment(trace)
    plain = fleet.simulate_fleet(
        topo, jnp.asarray(trace), jnp.asarray(assignment),
        TelemetrySpec(window=25),
    )
    cfg = StreamConfig(topo=topo, chunk_len=G, telemetry=TEL)
    fs, _ = _run_stream(cfg, trace, assignment, groups=GROUPS)
    st = fs.stats()
    for l in range(topo.n_levels):
        np.testing.assert_array_equal(
            np.asarray(st.telemetry[l]).sum(axis=2),
            np.asarray(plain["telemetry"][l]),
            err_msg=f"group-sum != ungrouped series, level {l}",
        )


# ------------------------------------------------------------- placed engine
@pytest.mark.parametrize("pl", ["lcd", "prob(0.3)", "admit"])
def test_stream_placed_bit_identity(pl):
    """Placement couples the levels per step -> the stream shares the placed
    engine's scan cell; parity covers the placement sketches' carry, the
    traced refresh schedule and the scattered telemetry."""
    topo = fleet.tree(
        n_objects=N, widths=(3, 1), kinds=("lru", "plfua_dyn"),
        capacities=(5, 13), refresh=(0, 30), placements=("lce", pl),
    )
    trace = workloads.make_traces("churn", N, 1, T, seed=5)[0]
    assignment = topo.assignment(trace)
    bounded = fleet.simulate_fleet(
        topo, jnp.asarray(trace), jnp.asarray(assignment), TEL,
        sizes=SIZES, groups=GROUPS,
    )
    cfg = StreamConfig(topo=topo, chunk_len=G, telemetry=TEL)
    fs, hits = _run_stream(cfg, trace, assignment, sizes=SIZES, groups=GROUPS)
    _assert_stream_matches(bounded, fs, hits, tel=True, ctx=f"placed {pl}")


# ------------------------------------------------------------ fast-lane path
_FAST_SPECS = {
    "lru": {}, "lfu": {}, "plfu": {"hot_size": 24}, "plfua": {"hot_size": 24},
    "plfua_dyn": {"hot_size": 24, "refresh": 2 * G}, "gdsf": {}, "tinylfu": {},
    "arc": {},
}


@pytest.mark.parametrize("kind", FAST_KINDS)
def test_stream_fast_parity(kind):
    """The compact working-set engine == the dense flat simulator, hit for
    hit and state field for state field — the candidate-prefix bound and the
    id-sorted tie-break hold across chunk boundaries (plfua_dyn's refresh =
    2 chunks exercises the boundary cond)."""
    kw = _FAST_SPECS[kind]
    spec = PolicySpec(kind=kind, n_objects=N, capacity=13, **kw)
    trace = workloads.make_traces("churn", N, 1, T, seed=7)[0]
    ref_hits, ref_state = jax_cache.simulate(spec, jnp.asarray(trace))
    topo = fleet.tree(
        n_objects=N, widths=(1,), kinds=kind, capacities=13,
        **{k: (v,) for k, v in kw.items()},
    )
    fs = FleetStream(StreamConfig(topo=topo, chunk_len=G, fast=True))
    hits = []
    for c in range(K):
        out = fs.push(jnp.asarray(trace[c * G:(c + 1) * G]))
        hits.append(np.asarray(out["hit"][0]))
    np.testing.assert_array_equal(
        np.concatenate(hits), np.asarray(ref_hits), err_msg=f"fast {kind} hits"
    )
    fstate = fs.states()[0]
    for k in ref_state:
        np.testing.assert_array_equal(
            np.asarray(ref_state[k]), np.asarray(fstate[k]),
            err_msg=f"fast {kind} state[{k}]",
        )
    st = fs.stats()
    assert st.hits == int(np.asarray(ref_hits).sum())
    assert st.requests == T
    assert int(st.tiers[0]["count"][0]) == int(ref_state["count"])


def test_stream_fast_arc_full_directory():
    """ARC's directory roster at its bound: a churn stream long enough that
    the directory holds exactly 2c entries, id N - 1 among them (a sentinel
    lane reads that id's row, so it must read unlisted), equals the dense
    simulator field for field; ``ghost_lanes`` counts the B1/B2 lanes of
    every chunk's start, recounted from the states."""
    n, cap, g, k = 300, 13, 50, 40
    spec = PolicySpec(kind="arc", n_objects=n, capacity=cap)
    trace = workloads.make_traces("churn", n, 1, g * k, seed=7)[0].astype(np.int32)
    trace[::7] = n - 1
    ref_hits, ref_state = jax_cache.simulate(spec, jnp.asarray(trace))
    topo = fleet.tree(n_objects=n, widths=(1,), kinds="arc", capacities=cap)
    fs = FleetStream(StreamConfig(topo=topo, chunk_len=g, fast=True))
    hits, ghosts = [], 0
    for c in range(k):
        ghosts += int((np.asarray(fs.states()[0]["lst"]) >= 3).sum())
        hits.append(np.asarray(fs.push(jnp.asarray(trace[c * g:(c + 1) * g]))["hit"][0]))
    np.testing.assert_array_equal(np.concatenate(hits), np.asarray(ref_hits))
    fstate = fs.states()[0]
    for key in ref_state:
        np.testing.assert_array_equal(
            np.asarray(ref_state[key]), np.asarray(fstate[key]), err_msg=f"state[{key}]"
        )
    lst = np.asarray(fstate["lst"])
    assert (lst != 0).sum() == 2 * cap and lst[n - 1] != 0
    st = fs.stats()
    assert st.ghost_lanes == ghosts > 0
    assert st.lanes == k * (2 * cap + g) and 0 < st.lanes_valid < st.lanes


def test_stream_ghost_lanes_only_where_counted():
    """Other fast kinds hold no ghosts (0); the level-major engine counts none."""
    trace = workloads.make_traces("stationary", N, 1, G, seed=3)[0]
    flat = fleet.tree(n_objects=N, widths=(1,), kinds="lru", capacities=13)
    fast = FleetStream(StreamConfig(topo=flat, chunk_len=G, fast=True))
    fast.push(jnp.asarray(trace))
    assert fast.stats().ghost_lanes == 0
    dense = FleetStream(StreamConfig(topo=_topo("arc"), chunk_len=G))
    dense.push(jnp.asarray(trace), jnp.asarray(_topo("arc").assignment(trace)))
    assert dense.stats().ghost_lanes is None


def test_stream_fast_sized_gdsf():
    """Size-aware victim scoring flows through the compact lanes (the sizes
    catalogue is gathered per lane like the sketch tables)."""
    spec = PolicySpec(kind="gdsf", n_objects=N, capacity=13)
    trace = workloads.make_traces("stationary", N, 1, T, seed=9)[0]
    ref_hits, ref_state = jax_cache.simulate(spec, jnp.asarray(trace), sizes=SIZES)
    topo = fleet.tree(n_objects=N, widths=(1,), kinds="gdsf", capacities=13)
    fs = FleetStream(StreamConfig(topo=topo, chunk_len=G, fast=True), sizes=SIZES)
    hits = []
    for c in range(K):
        out = fs.push(jnp.asarray(trace[c * G:(c + 1) * G]))
        hits.append(np.asarray(out["hit"][0]))
    np.testing.assert_array_equal(np.concatenate(hits), np.asarray(ref_hits))
    for k in ref_state:
        np.testing.assert_array_equal(
            np.asarray(ref_state[k]), np.asarray(fs.states()[0][k]),
            err_msg=f"sized gdsf state[{k}]",
        )


# --------------------------------------------------------- on-device routing
def test_stream_device_routing_hash():
    """push(assignment=None) routes on device with the id-pure hash router;
    parity against a bounded run fed the *same* device-routed assignment."""
    from repro.cdn import router

    topo = fleet.tree(
        n_objects=N, widths=(4, 1), kinds="lru", capacities=(5, 13),
    )
    trace = workloads.make_traces("stationary", N, 1, T, seed=13)[0]
    assignment = np.asarray(
        router.route_device(jnp.asarray(trace), 4, "hash", session_len=64)
    )
    bounded = fleet.simulate_fleet(
        topo, jnp.asarray(trace), jnp.asarray(assignment)
    )
    fs = FleetStream(StreamConfig(topo=topo, chunk_len=G))
    hits = []
    for c in range(K):
        out = fs.push(jnp.asarray(trace[c * G:(c + 1) * G]))  # no assignment
        hits.append(out["hit"])
    _assert_stream_matches(bounded, fs, hits, ctx="device-routed")


# ------------------------------------------- double-buffered stream_fleet
def test_stream_fleet_double_buffered_generation():
    """stream_fleet's generate-ahead loop == a bounded run over the host
    concatenation of the same on-device chunks, and the rollup carries the
    measured wall clock (req/s, J/step)."""
    from repro.workloads.device import DeviceTraceSpec, gen_stream_chunk

    n_chunks = 4
    dspec = DeviceTraceSpec("stationary", N, n_samples=1, trace_len=G, seed=17)
    topo = fleet.tree(n_objects=N, widths=(1, 1), kinds="lru", capacities=(5, 13))
    cfg = StreamConfig(topo=topo, chunk_len=G)
    st = stream_fleet(cfg, dspec, n_chunks)
    chunks = [
        np.asarray(gen_stream_chunk(dspec, jnp.int32(0), jnp.int32(c)))
        for c in range(n_chunks)
    ]
    full = jnp.asarray(np.concatenate(chunks))
    bounded = fleet.simulate_fleet(
        topo, full, jnp.zeros((n_chunks * G,), jnp.int32)
    )
    assert st.requests == n_chunks * G and st.chunks == n_chunks
    assert st.origin_misses == int(np.asarray(bounded["origin_miss"]).sum())
    for l in range(2):
        np.testing.assert_array_equal(
            np.asarray(st.tiers[l]["hits"]),
            np.asarray(bounded["tiers"][l]["hits"]),
        )
    assert st.elapsed_s is not None and st.elapsed_s > 0
    assert st.req_per_s == pytest.approx(st.requests / st.elapsed_s)
    assert st.j_per_step is not None and st.j_per_step > 0
    with pytest.raises(ValueError, match="trace_len"):
        stream_fleet(StreamConfig(topo=topo, chunk_len=G + 1), dspec, 2)


# ----------------------------------------------- position-keyed routing
#: topologies whose routers key on the stream position at some level; the
#: session length 16 divides neither G = 50 nor the chunk boundaries
_ROUTED = {
    "sticky_edge": dict(widths=(3, 1), kinds="lru", capacities=(5, 13), router="sticky"),
    "sticky_upper": dict(widths=(3, 2, 1), kinds="lru", capacities=(5, 9, 13),
                         routers=("hash", "sticky", "tree")),
    "round_robin_upper": dict(widths=(3, 2, 1), kinds=("lru", "plfu", "lfu"),
                              capacities=(5, 9, 13), routers=("sticky", "round_robin", "hash")),
    "round_robin_edge": dict(widths=(4, 2), kinds=("plfu", "lru"), capacities=(5, 11),
                             routers=("round_robin", "sticky")),
    "placed": dict(widths=(3, 2), kinds=("lru", "plfu"), capacities=(5, 11),
                   placements=("lce", "lcd"), routers=("sticky", "round_robin")),
}


@pytest.mark.parametrize("name", sorted(_ROUTED))
def test_stream_position_keyed_routers_match_bounded(name):
    topo = fleet.tree(n_objects=N, session_len=16, **_ROUTED[name])
    assert topo.has_placement == (name == "placed")
    trace = workloads.make_traces("churn", N, 1, T, seed=31)[0]
    _assert_routed_stream_matches(topo, trace, G)


def test_stream_chunk_on_one_edge():
    """Sessions as long as a chunk: every session of a chunk lands on one
    edge, the others idle, so level 0's loop runs the whole chunk."""
    topo = fleet.tree(n_objects=N, widths=(3, 2), kinds=("plfua", "lru"),
                      capacities=(5, 11), hot_size=(10, 0), router="sticky",
                      session_len=G)
    trace = workloads.make_traces("stationary", N, 1, T, seed=37)[0]
    edges = _device_assignment(topo, trace).reshape(K, G)
    assert all(len(set(chunk.tolist())) == 1 for chunk in edges)
    st = _assert_routed_stream_matches(topo, trace, G)
    # each chunk's busy edge steps every position: 3 edges x G at level 0
    assert st.node_steps >= K * 3 * G


def test_stream_chunk_with_an_idle_origin_node():
    """A chunk whose ids all hash to origin node 0: node 1 gets no request
    there, and the loop runs to node 0's load alone."""
    topo = fleet.tree(n_objects=N, widths=(3, 2), kinds=("lru", "plfu"),
                      capacities=(5, 11), routers=("sticky", "hash"),
                      session_len=16)
    origin = router.route_level(np.arange(N), 2, "hash", seed=1)
    trace = workloads.make_traces("churn", N, 1, T, seed=43)[0]
    node0_ids = np.flatnonzero(origin == 0)
    trace[G:2 * G] = node0_ids[trace[G:2 * G] % len(node0_ids)]
    assert (origin[trace[G:2 * G]] == 0).all()
    assert (origin[trace] == 1).any()
    _assert_routed_stream_matches(topo, trace, G)


@pytest.mark.parametrize("dense", ["plfua_dyn", "telemetry"])
def test_stream_node_steps_of_dense_levels(dense):
    """A level that keeps the dense scan steps every node at every position
    (plfua_dyn's global-time refresh; every level with telemetry), the
    compacted levels their busiest node's load."""
    kinds = ("lru", "plfua_dyn") if dense == "plfua_dyn" else ("lru", "plfu")
    topo = fleet.tree(n_objects=N, widths=(3, 1), kinds=kinds, capacities=(5, 13),
                      refresh=(0, 30))
    tel = TelemetrySpec(window=25) if dense == "telemetry" else None
    trace = workloads.make_traces("churn", N, 1, T, seed=47)[0]
    assignment = topo.assignment(trace)
    fs, _ = _run_stream(StreamConfig(topo=topo, chunk_len=G, telemetry=tel),
                        trace, assignment)
    st = fs.stats()
    ref = simulate_fleet_reference(topo, trace, assignment)
    want = _recount_node_steps(topo, trace, assignment, ref.level_hit, G,
                               telemetry=tel is not None)
    assert st.node_steps == want
    if tel is not None:
        assert st.node_steps == st.lanes
    else:
        # the compacted edge level runs less than the dense grid
        assert st.lanes_valid <= st.node_steps < st.lanes


def test_stream_edge8_origin4_fleet_matches_bounded():
    """The photo-CDN shape at N = 3,000: 8 sticky PLFUA edges (rate 0.02,
    hot set 2 x C) over 4 hash-partitioned PLFU origin nodes, sessions of 64
    in chunks of 256, no assignment pushed."""
    n = 3_000
    topo = fleet.tree(
        n_objects=n, widths=(8, 4), kinds=("plfua", "plfu"), capacities=(60, 150),
        hot_size=(120, 0), routers=("sticky", "hash"), session_len=64,
    )
    trace = workloads.make_traces("stationary", n, 1, 6 * 256, seed=41)[0]
    st = _assert_routed_stream_matches(topo, trace, 256)
    # each request steps 12 nodes and is active at its edge and, on an edge
    # miss, at one origin node
    assert st.lanes == 6 * 256 * 12
    assert 6 * 256 < st.lanes_valid < 2 * 6 * 256
    # the compacted loops run each level to its busiest node's load
    assert st.lanes_valid < st.node_steps < st.lanes // 2
