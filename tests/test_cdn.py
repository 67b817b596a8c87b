"""CDN hierarchy tests: the jitted fleet simulator must match the pure-Python
reference hierarchy decision-for-decision (hit sequences, final contents,
eviction counts, per tier), for every policy kind, router, and workload
scenario; plus router determinism/properties and report-accounting checks."""
import numpy as np
import pytest

from repro import cdn, workloads
from repro.cdn import router as router_mod
from repro.core.jax_cache import JAX_POLICY_KINDS, PolicySpec

N, E, T = 128, 4, 1_200
SCENARIOS = ("stationary", "churn", "flash_crowd")


def _hspec(kind, router="hash", n=N, n_edges=E):
    return cdn.two_tier(
        kind, n, n_edges=n_edges, edge_capacity=7, parent_capacity=24,
        router=router, window=48 if kind == "wlfu" else 0,
    )


def _assert_parity(hspec, trace, assignment):
    out = cdn.simulate_hierarchy(hspec, trace, assignment)
    ref = cdn.simulate_hierarchy_reference(hspec, trace, assignment)
    np.testing.assert_array_equal(
        np.asarray(out["edge_hit"]), ref.edge_hit, err_msg="edge hit sequence"
    )
    np.testing.assert_array_equal(
        np.asarray(out["parent_hit"]), ref.parent_hit, err_msg="parent hit sequence"
    )
    e_ref, p_ref = ref.in_cache(hspec.n_objects)
    np.testing.assert_array_equal(np.asarray(out["edge_states"]["in_cache"]), e_ref)
    np.testing.assert_array_equal(np.asarray(out["parent_state"]["in_cache"]), p_ref)
    assert [int(v) for v in np.asarray(out["edge"]["evictions"])] == [
        p.evictions for p in ref.edges
    ]
    assert int(np.asarray(out["parent"]["evictions"])) == ref.parent.evictions
    assert [int(v) for v in np.asarray(out["edge"]["hits"])] == [
        p.hits for p in ref.edges
    ]
    return out


@pytest.mark.slow  # the fast lane gets flat-simulator parity from test_differential
@pytest.mark.parametrize("kind", JAX_POLICY_KINDS)
@pytest.mark.parametrize("scenario", SCENARIOS)
def test_hierarchy_matches_reference(kind, scenario):
    """The acceptance matrix: 4 edges + parent, every policy x scenario."""
    hspec = _hspec(kind)
    trace = workloads.make_traces(scenario, N, n_samples=1, trace_len=T, seed=13)[0]
    _assert_parity(hspec, trace, hspec.assignment(trace))


@pytest.mark.parametrize("router", cdn.ROUTER_MODES)
def test_hierarchy_matches_reference_any_router(router):
    hspec = _hspec("plfu", router=router)
    trace = workloads.make_traces("stationary", N, 1, T, seed=3)[0]
    _assert_parity(hspec, trace, hspec.assignment(trace))


def test_heterogeneous_edges_match_reference():
    """Edges with different capacities and PLFUA hot sizes in one vmap."""
    edges = tuple(
        PolicySpec(kind="plfua", n_objects=N, capacity=c, hot_size=h)
        for c, h in ((4, 10), (7, 20), (11, 0), (6, 16))
    )
    hspec = cdn.HierarchySpec(
        edges=edges,
        parent=PolicySpec(kind="plfua", n_objects=N, capacity=24),
        router="round_robin",
    )
    trace = workloads.make_traces("multi_tenant", N, 1, T, seed=5)[0]
    _assert_parity(hspec, trace, hspec.assignment(trace))


def test_batch_matches_single():
    hspec = _hspec("lfu")
    traces = workloads.make_traces("churn", N, n_samples=3, trace_len=800, seed=2)
    assign = hspec.assignment(traces)
    batched = cdn.simulate_hierarchy_batch(hspec, traces, assign)
    for s in range(3):
        single = cdn.simulate_hierarchy(hspec, traces[s], assign[s])
        np.testing.assert_array_equal(
            np.asarray(batched["edge_hit"])[s], np.asarray(single["edge_hit"])
        )
        np.testing.assert_array_equal(
            np.asarray(batched["parent_hit"])[s], np.asarray(single["parent_hit"])
        )


def test_counter_conservation():
    hspec = _hspec("plfu")
    trace = workloads.make_traces("stationary", N, 1, T, seed=7)[0]
    out = cdn.simulate_hierarchy(hspec, trace, hspec.assignment(trace))
    edge_req = np.asarray(out["edge"]["requests"])
    assert edge_req.sum() == T  # every request hits exactly one edge
    edge_hits = int(np.asarray(out["edge"]["hits"]).sum())
    assert int(np.asarray(out["parent"]["requests"])) == T - edge_hits
    assert (np.asarray(out["edge"]["evictions"]) >= 0).all()
    assert int(np.asarray(out["parent"]["evictions"])) >= 0
    # occupancy never exceeds capacity
    assert (np.asarray(out["edge"]["count"]) <= 7).all()
    assert int(np.asarray(out["parent"]["count"])) <= 24


def test_report_rollup():
    hspec = _hspec("plfua")
    traces = workloads.make_traces("flash_crowd", N, 2, 800, seed=9)
    out = cdn.simulate_hierarchy_batch(hspec, traces, hspec.assignment(traces))
    rep = cdn.hierarchy_report(hspec, out)
    assert rep.n_requests == 2 * 800
    assert 0.0 <= rep.edge_chr <= 1.0 and 0.0 <= rep.total_chr <= 1.0
    assert rep.total_chr >= rep.edge_chr
    assert rep.origin_requests == rep.n_requests - rep.edge.hits - rep.parent.hits
    assert rep.origin_requests >= 0
    assert rep.mgmt_cpu_s > 0 and rep.mgmt_energy_j > rep.mgmt_cpu_s  # ~5.9 W/core
    rows = rep.rows()
    assert len(rows) == E + 2  # per-edge + aggregate + parent
    scan = cdn.hierarchy_report(hspec, out, cost_model="scan")
    assert scan.mgmt_cpu_s >= rep.mgmt_cpu_s  # O(C) eviction costs more


def test_two_tier_validation():
    with pytest.raises(ValueError, match="share kind"):
        cdn.HierarchySpec(
            edges=(
                PolicySpec(kind="lru", n_objects=N, capacity=4),
                PolicySpec(kind="lfu", n_objects=N, capacity=4),
            ),
            parent=PolicySpec(kind="lfu", n_objects=N, capacity=8),
        )
    with pytest.raises(ValueError, match="share n_objects"):
        cdn.HierarchySpec(
            edges=(PolicySpec(kind="lfu", n_objects=N, capacity=4),),
            parent=PolicySpec(kind="lfu", n_objects=2 * N, capacity=8),
        )
    with pytest.raises(ValueError, match="unknown router"):
        cdn.two_tier("lfu", N, edge_capacity=4, parent_capacity=8, router="nope")


# ------------------------------------------------------------------- router
def test_router_range_and_determinism():
    trace = workloads.make_traces("stationary", N, 1, 2_000, seed=1)[0]
    for mode in router_mod.ROUTER_MODES:
        a = router_mod.route(trace, 5, mode, seed=3)
        b = router_mod.route(trace, 5, mode, seed=3)
        np.testing.assert_array_equal(a, b)
        assert a.dtype == np.int32
        assert a.min() >= 0 and a.max() < 5


def test_hash_router_is_content_addressed():
    trace = workloads.make_traces("stationary", N, 1, 2_000, seed=1)[0]
    assign = router_mod.route(trace, 4, "hash")
    for obj in np.unique(trace)[:20]:
        edges = np.unique(assign[trace == obj])
        assert len(edges) == 1  # an object always lives on one edge


def test_sticky_router_keeps_sessions_together():
    trace = workloads.make_traces("stationary", N, 1, 2_000, seed=1)[0]
    assign = router_mod.route(trace, 4, "sticky", session_len=100)
    blocks = assign.reshape(-1, 100)
    assert (blocks == blocks[:, :1]).all()  # constant within a session
    assert len(np.unique(blocks[:, 0])) > 1  # but sessions spread across edges


def test_round_robin_router_balances_exactly():
    trace = workloads.make_traces("stationary", N, 1, 2_000, seed=1)[0]
    assign = router_mod.route(trace, 4, "round_robin")
    counts = np.bincount(assign, minlength=4)
    assert counts.max() - counts.min() <= 1


@pytest.mark.parametrize("xp_name", ["numpy", "jax"])
@pytest.mark.parametrize("mode", router_mod.ROUTER_MODES)
def test_route_level_at_an_offset_equals_the_whole_trace_sliced(mode, xp_name):
    """A slice routed at its stream offset ``t0`` is the whole trace routed
    and sliced, for every router (sessions straddle the cuts: 16 divides
    none of them), numpy and jnp bit-identical, the jnp offset traced."""
    import jax
    import jax.numpy as jnp

    trace = workloads.make_traces("stationary", N, 1, 1_000, seed=2)[0].astype(np.int32)
    kw = dict(session_len=16, seed=2)
    whole = router_mod.route_level(trace, 5, mode, **kw)
    np.testing.assert_array_equal(
        np.asarray(router_mod.route_level(jnp.asarray(trace), 5, mode, xp=jnp, **kw)), whole
    )
    routed = jax.jit(lambda tr, t0: router_mod.route_device(tr, 5, mode, t0=t0, **kw))
    for lo, hi in [(0, 50), (37, 137), (450, 1_000), (999, 1_000)]:
        if xp_name == "numpy":
            part = router_mod.route_level(trace[lo:hi], 5, mode, t0=lo, **kw)
        else:
            part = np.asarray(routed(jnp.asarray(trace[lo:hi]), jnp.int32(lo)))
        assert part.dtype == np.int32
        np.testing.assert_array_equal(part, whole[lo:hi], err_msg=f"{mode} [{lo}:{hi}]")


def test_hash_router_balances_approximately():
    trace = np.arange(10_000, dtype=np.int64) % 997  # near-uniform object mix
    assign = router_mod.route(trace, 8, "hash")
    counts = np.bincount(assign, minlength=8) / assign.size
    assert counts.max() < 0.25 and counts.min() > 0.05
