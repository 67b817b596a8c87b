"""Fleet subsystem tests.

The acceptance matrix for the N-tier simulator: a 3-tier topology must match
the pure-Python reference oracle decision-for-decision — per-level hit
sequences, final cache contents, per-node hit/eviction counters — across
every workload scenario and policy kind (full sweep slow-marked; a smaller
matrix stays in the fast lane). Plus: depth-4 parity, topology validation,
report roll-ups, the two-tier wrapper equivalence, on-device trace
generation parity, and a forced-multi-device subprocess check of both
shard_map paths.
"""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO_ROOT = str(pathlib.Path(__file__).resolve().parents[1])

from repro import fleet, workloads
from repro.core.jax_cache import JAX_POLICY_KINDS, PolicySpec
from repro.workloads.device import DeviceTraceSpec

N, T = 128, 1_200
FAST_KINDS = ("lru", "plfua", "tinylfu")
FAST_SCENARIOS = ("churn", "multi_tenant")


def _topo3(kind, n=N, router="hash", **kw):
    """4 edges -> 2 regionals -> 1 root; capacities ~3/7/18% of the id space."""
    return fleet.tree(
        n_objects=n,
        widths=(4, 2, 1),
        kinds=kind,
        capacities=(4, 9, 23),
        window=48 if kind == "wlfu" else 0,
        router=router,
        **kw,
    )


def _assert_fleet_parity(topo, trace, assignment):
    out = fleet.simulate_fleet(topo, trace, assignment)
    ref = fleet.simulate_fleet_reference(topo, trace, assignment)
    contents = ref.in_cache(topo.n_objects)
    for l in range(topo.n_levels):
        np.testing.assert_array_equal(
            np.asarray(out["hit"][l]), ref.level_hit[l],
            err_msg=f"hit sequence, level {l}",
        )
        np.testing.assert_array_equal(
            np.asarray(out["states"][l]["in_cache"]), contents[l],
            err_msg=f"final contents, level {l}",
        )
        assert [int(v) for v in np.asarray(out["tiers"][l]["hits"])] == [
            p.hits for p in ref.levels[l]
        ], f"per-node hits, level {l}"
        assert [int(v) for v in np.asarray(out["tiers"][l]["evictions"])] == [
            p.evictions for p in ref.levels[l]
        ], f"per-node evictions, level {l}"
    return out, ref


@pytest.mark.parametrize("kind", FAST_KINDS)
@pytest.mark.parametrize("scenario", FAST_SCENARIOS)
def test_three_tier_matches_reference(kind, scenario):
    topo = _topo3(kind)
    trace = workloads.make_traces(scenario, N, n_samples=1, trace_len=T, seed=17)[0]
    _assert_fleet_parity(topo, trace, topo.assignment(trace))


@pytest.mark.slow  # the exhaustive acceptance matrix
@pytest.mark.parametrize("kind", JAX_POLICY_KINDS)
@pytest.mark.parametrize("scenario", workloads.SCENARIO_NAMES)
def test_three_tier_matrix(kind, scenario):
    topo = _topo3(kind)
    trace = workloads.make_traces(scenario, N, n_samples=1, trace_len=T, seed=29)[0]
    _assert_fleet_parity(topo, trace, topo.assignment(trace))


@pytest.mark.slow
@pytest.mark.parametrize("router", ("sticky", "round_robin"))
def test_three_tier_any_router(router):
    topo = _topo3("plfu", router=router)
    trace = workloads.make_traces("stationary", N, 1, T, seed=3)[0]
    _assert_fleet_parity(topo, trace, topo.assignment(trace))


def test_depth_four_heterogeneous_levels():
    """4 tiers, a different policy kind per level, non-uniform fan-in."""
    mk = lambda kind, cap, **kw: PolicySpec(kind=kind, n_objects=N, capacity=cap, **kw)
    topo = fleet.Topology(
        levels=(
            tuple(mk("lru", c) for c in (3, 5, 4, 6, 3, 5)),
            (mk("lfu", 9), mk("lfu", 11)),
            (mk("plfu", 16),),
            (mk("plfua", 24, hot_size=60),),
        ),
        parents=((0, 0, 0, 1, 1, 1), (0, 0), (0,)),
        router="hash",
    )
    trace = workloads.make_traces("flash_crowd", N, 1, T, seed=7)[0]
    out, _ = _assert_fleet_parity(topo, trace, topo.assignment(trace))
    # conservation: each level's requests are exactly the unserved stream
    served = np.zeros(T, bool)
    for l in range(4):
        assert int(np.asarray(out["tiers"][l]["requests"]).sum()) == int((~served).sum())
        served |= np.asarray(out["hit"][l])
    np.testing.assert_array_equal(np.asarray(out["origin_miss"]), ~served)


def test_doorkeeper_tinylfu_in_fleet():
    """The bloom front stays decision-parity inside a vmapped tier fleet."""
    topo = _topo3("tinylfu", doorkeeper=128, sketch_width=64)
    trace = workloads.make_traces("churn", N, 1, T, seed=11)[0]
    _assert_fleet_parity(topo, trace, topo.assignment(trace))


def test_batch_matches_single():
    topo = _topo3("lfu")
    traces = workloads.make_traces("diurnal", N, n_samples=3, trace_len=800, seed=2)
    assign = topo.assignment(traces)
    batched = fleet.simulate_fleet_batch(topo, traces, assign)
    for s in range(3):
        single = fleet.simulate_fleet(topo, traces[s], assign[s])
        for l in range(topo.n_levels):
            np.testing.assert_array_equal(
                np.asarray(batched["hit"][l])[s], np.asarray(single["hit"][l])
            )


def test_two_tier_wrapper_equivalence():
    """cdn.simulate_hierarchy is exactly the depth-2 fleet run, reshaped."""
    from repro import cdn

    hspec = cdn.two_tier("plfu", N, n_edges=4, edge_capacity=7, parent_capacity=24)
    trace = workloads.make_traces("stationary", N, 1, T, seed=13)[0]
    assign = hspec.assignment(trace)
    legacy = cdn.simulate_hierarchy(hspec, trace, assign)
    out = fleet.simulate_fleet(hspec.topology(), trace, assign)
    np.testing.assert_array_equal(
        np.asarray(legacy["edge_hit"]), np.asarray(out["hit"][0])
    )
    np.testing.assert_array_equal(
        np.asarray(legacy["parent_hit"]), np.asarray(out["hit"][1])
    )
    for k in legacy["edge"]:
        np.testing.assert_array_equal(
            np.asarray(legacy["edge"][k]), np.asarray(out["tiers"][0][k])
        )
        np.testing.assert_array_equal(
            np.asarray(legacy["parent"][k]), np.asarray(out["tiers"][1][k])[0]
        )


def test_fleet_report_rollup():
    topo = _topo3("plfua")
    traces = workloads.make_traces("flash_crowd", N, 2, 800, seed=9)
    out = fleet.simulate_fleet_batch(topo, traces, topo.assignment(traces))
    rep = fleet.fleet_report(topo, out)
    assert rep.n_requests == 2 * 800
    assert 0.0 <= rep.edge_chr <= 1.0 and 0.0 <= rep.total_chr <= 1.0
    assert rep.total_chr >= rep.edge_chr
    hits = sum(t.hits for t in rep.per_level)
    assert rep.origin_requests == rep.n_requests - hits >= 0
    assert rep.mgmt_cpu_s > 0 and rep.mgmt_energy_j > rep.mgmt_cpu_s  # ~5.9 W/core
    rows = rep.rows()
    # per-node + per-level aggregate + per-level placement row + origin row
    assert len(rows) == topo.n_nodes + 2 * topo.n_levels + 1
    assert rows[-1]["tier"] == "origin"
    assert rows[-1]["req_bytes"] == rep.origin_egress_bytes
    assert [t.tier for t in rep.per_level] == ["edge", "mid1", "root"]
    assert [t.tier for t in rep.per_level_placement] == [
        "edge:placement", "mid1:placement", "root:placement"
    ]
    assert all(t.policy == "lce" for t in rep.per_level_placement)
    assert rep.placement_energy_j > 0  # lce fills are priced too
    scan = fleet.fleet_report(topo, out, cost_model="scan")
    assert scan.mgmt_cpu_s >= rep.mgmt_cpu_s  # O(C) eviction costs more


def test_topology_validation():
    mk = lambda kind, cap: PolicySpec(kind=kind, n_objects=N, capacity=cap)
    with pytest.raises(ValueError, match="share kind"):
        fleet.Topology(levels=((mk("lru", 4), mk("lfu", 4)),), parents=())
    with pytest.raises(ValueError, match="share n_objects"):
        fleet.Topology(
            levels=(
                (mk("lfu", 4),),
                (PolicySpec(kind="lfu", n_objects=2 * N, capacity=8),),
            ),
            parents=((0,),),
        )
    with pytest.raises(ValueError, match="one parents tuple"):
        fleet.Topology(levels=((mk("lfu", 4),), (mk("lfu", 8),)), parents=())
    with pytest.raises(ValueError, match="out of range"):
        fleet.Topology(
            levels=((mk("lfu", 4),), (mk("lfu", 8),)), parents=((1,),)
        )
    with pytest.raises(ValueError, match="unknown router"):
        fleet.tree(n_objects=N, widths=(2, 1), kinds="lru", capacities=(4, 8), router="nope")
    with pytest.raises(ValueError, match="one entry per level"):
        fleet.tree(n_objects=N, widths=(2, 1), kinds="lru", capacities=(4, 8, 16))
    topo = fleet.tree(n_objects=N, widths=(6, 3, 1), kinds="lru", capacities=(4, 8, 16))
    assert topo.ancestry(5) == (5, 2, 0)
    assert topo.n_edges == 6 and topo.n_levels == 3 and topo.n_nodes == 10


# ------------------------------------------------------- on-device generation
def test_device_generation_matches_oracle():
    """Traces synthesized inside jit replay exactly through the pure-Python
    oracle (the generated stream + jnp-router assignment travel with the
    result, so parity is exact despite the different RNG)."""
    topo = fleet.tree(
        n_objects=200, widths=(4, 1), kinds="plfu", capacities=(6, 24)
    )
    dspec = DeviceTraceSpec("churn", 200, n_samples=2, trace_len=1_000, seed=21)
    out, traces, assigns = fleet.simulate_fleet_device(topo, dspec)
    traces, assigns = np.asarray(traces), np.asarray(assigns)
    assert traces.shape == (2, 1_000) and traces.min() >= 0 and traces.max() < 200
    for s in range(2):
        ref = fleet.simulate_fleet_reference(topo, traces[s], assigns[s])
        for l in range(topo.n_levels):
            np.testing.assert_array_equal(
                np.asarray(out["hit"][l])[s], ref.level_hit[l],
                err_msg=f"sample {s} level {l}",
            )


def test_device_generation_is_deterministic():
    topo = fleet.tree(n_objects=100, widths=(2, 1), kinds="lru", capacities=(4, 12))
    dspec = DeviceTraceSpec("flash_crowd", 100, n_samples=2, trace_len=500, seed=3)
    _, tr_a, as_a = fleet.simulate_fleet_device(topo, dspec)
    _, tr_b, as_b = fleet.simulate_fleet_device(topo, dspec)
    np.testing.assert_array_equal(np.asarray(tr_a), np.asarray(tr_b))
    np.testing.assert_array_equal(np.asarray(as_a), np.asarray(as_b))


# ----------------------------------------------------------- multi-device
@pytest.mark.slow
def test_sharded_paths_match_on_forced_devices():
    """Real 4-device run in a subprocess: the edge-sharded path (collective
    miss aggregation) and the sample-sharded on-device-generation path must
    both reproduce the single-device results exactly."""
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys
        sys.path.insert(0, "src")
        import numpy as np, jax
        from repro import fleet, workloads
        from repro.workloads.device import DeviceTraceSpec

        assert jax.device_count() == 4
        topo = fleet.tree(n_objects=160, widths=(8, 2, 1), kinds="plfu",
                          capacities=(5, 12, 28))
        trace = workloads.make_traces("churn", 160, 1, 1500, seed=5)[0]
        assign = topo.assignment(trace)
        mesh = fleet.fleet_mesh()
        a = fleet.simulate_fleet(topo, trace, assign)
        b = fleet.simulate_fleet_sharded(topo, trace, assign, mesh=mesh)
        for l in range(3):
            np.testing.assert_array_equal(np.asarray(a["hit"][l]),
                                          np.asarray(b["hit"][l]))
            for k in a["tiers"][l]:
                np.testing.assert_array_equal(np.asarray(a["tiers"][l][k]),
                                              np.asarray(b["tiers"][l][k]))

        dspec = DeviceTraceSpec("stationary", 160, n_samples=4,
                                trace_len=1500, seed=2)
        r1, t1, a1 = fleet.simulate_fleet_device(topo, dspec)
        r4, t4, a4 = fleet.simulate_fleet_device(topo, dspec, mesh=mesh)
        np.testing.assert_array_equal(np.asarray(t1), np.asarray(t4))
        for l in range(3):
            np.testing.assert_array_equal(np.asarray(r1["hit"][l]),
                                          np.asarray(r4["hit"][l]))
        print("SHARDED_OK")
        """
    )
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        cwd=REPO_ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert "SHARDED_OK" in out.stdout, (out.stdout[-1000:], out.stderr[-3000:])


def test_single_device_fallback():
    """mesh=None and 1-device meshes take the plain vmap path."""
    topo = fleet.tree(n_objects=100, widths=(2, 1), kinds="lru", capacities=(4, 12))
    trace = workloads.make_traces("stationary", 100, 1, 400, seed=1)[0]
    assign = topo.assignment(trace)
    base = fleet.simulate_fleet(topo, trace, assign)
    for mesh in (None, fleet.fleet_mesh(devices=__import__("jax").devices()[:1])):
        out = fleet.simulate_fleet_sharded(topo, trace, assign, mesh=mesh)
        np.testing.assert_array_equal(
            np.asarray(base["hit"][0]), np.asarray(out["hit"][0])
        )


# --------------------------------------------------------------- bench smoke
@pytest.mark.slow
def test_bench_record_roundtrip(tmp_path):
    """The --record harness writes valid JSON rows for the fleet groups."""
    out_path = tmp_path / "bench.json"
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--only", "fleet_depth",
         "--record", str(out_path)],
        capture_output=True, text=True, cwd=REPO_ROOT,
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"},
        timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    payload = json.loads(out_path.read_text())
    assert payload["config"]["groups"] == ["fleet_depth"]
    names = [r["name"] for r in payload["rows"]]
    assert any("fleet_depth/T3" in n for n in names), names
