"""Property-based invariant suite for cross-tier placement policies.

The fleet's placement subsystem (repro.fleet.placement: lce / lcd / prob(p)
/ admit) is locked down by invariants rather than hand-picked traces:

* **Served-mask partition** — whatever the placement, every request is
  served at exactly one level or the origin, and each tier's request count
  is exactly the unserved stream routed to it (placement changes *where
  copies land*, never the accounting identity).
* **lcd ⊆ lce occupancy** — with no eviction pressure, every object a
  leave-copy-down fleet stores is also stored by the leave-copy-everywhere
  fleet (lcd only ever withholds copies).
* **prob endpoints** — ``prob(1.0)`` reproduces ``lce`` and ``prob(0.0)``
  reproduces ``lcd`` *bit for bit*, full result pytree. Since all-lce trees
  run the legacy level-major engine and any prob tree runs the time-major
  placed engine, the prob(1.0) case is the cross-validation between the two
  simulator engines.
* **Oracle parity** — the jitted placed engine matches the pure-Python
  reference decision-for-decision (hit sequences, final contents, per-node
  counters) on a fast subset here; the exhaustive placement × kind ×
  scenario matrix lives in tests/test_differential.py.
* **Shard parity** — both shard_map paths reproduce the single-device
  placed results exactly on a real (forced host) 4-device mesh.
* **Determinism** — the ``prob(p)`` threshold-hash path is a pure function
  of (trace position, level), so two separate processes produce identical
  fleet reports for the same TraceSpec seed.

Trace parameters are drawn through the hypothesis shim (seeded random
examples when the real package is absent), with shapes pinned to small
fixed sets so jit recompiles stay bounded.
"""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - CI installs hypothesis; shim elsewhere
    from _hypothesis_fallback import given, settings, strategies as st

from repro import fleet, workloads
from repro.core import jax_cache
from repro.fleet import placement

REPO_ROOT = str(pathlib.Path(__file__).resolve().parents[1])
# child processes run on forced host devices, never on an accelerator
CPU_CHILD_ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}

N, T = 96, 700
PLACEMENTS = ("lce", "lcd", "prob(0.5)", "admit")
FAST_KINDS = ("lru", "lfu", "plfua", "tinylfu")


def _topo(kind, placements, *, caps=(4, 9, 23), widths=(4, 2, 1), n=N, **kw):
    return fleet.tree(
        n_objects=n,
        widths=widths,
        kinds=kind,
        capacities=caps,
        window=48 if kind == "wlfu" else 0,
        placements=placements,
        **kw,
    )


def _assert_oracle_parity(topo, trace, assignment):
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


def _assert_same_result(a, b, ctx=""):
    """Full result-pytree bit-parity between two simulate_fleet outputs."""
    for l in range(len(a["hit"])):
        np.testing.assert_array_equal(
            np.asarray(a["hit"][l]), np.asarray(b["hit"][l]),
            err_msg=f"{ctx}: hit, level {l}",
        )
        for k in a["tiers"][l]:
            np.testing.assert_array_equal(
                np.asarray(a["tiers"][l][k]), np.asarray(b["tiers"][l][k]),
                err_msg=f"{ctx}: tiers[{l}][{k}]",
            )
        for k in a["states"][l]:
            np.testing.assert_array_equal(
                np.asarray(a["states"][l][k]), np.asarray(b["states"][l][k]),
                err_msg=f"{ctx}: states[{l}][{k}]",
            )
    np.testing.assert_array_equal(
        np.asarray(a["origin_miss"]), np.asarray(b["origin_miss"]),
        err_msg=f"{ctx}: origin_miss",
    )


# ------------------------------------------------------------------ parsing
def test_placement_parse_and_validation():
    assert placement.parse("lce") == ("lce", None)
    assert placement.parse("lcd") == ("lcd", None)
    assert placement.parse("admit") == ("admit", None)
    assert placement.parse("prob(0.25)") == ("prob", 0.25)
    assert placement.parse("prob(1.0)") == ("prob", 1.0)
    for bad in ("lcx", "prob(1.5)", "prob(-0.1)", "prob()", "prob", ""):
        with pytest.raises(ValueError):
            placement.parse(bad)
    with pytest.raises(ValueError, match="placements must name every level"):
        fleet.tree(
            n_objects=N, widths=(2, 1), kinds="lru", capacities=(4, 8),
            placements=("lce",),
        )
    with pytest.raises(ValueError, match="unknown placement"):
        _topo("lru", "nope", widths=(2, 1), caps=(4, 8))
    # normalisation: scalars broadcast, defaults are all-lce on the old path
    t = _topo("lru", "lcd", widths=(2, 1), caps=(4, 8))
    assert t.placements == ("lcd", "lcd") and t.has_placement
    t = fleet.tree(n_objects=N, widths=(2, 1), kinds="lru", capacities=(4, 8))
    assert t.placements == ("lce", "lce") and not t.has_placement


def test_prob_hash_is_shared_and_deterministic():
    """numpy and jnp produce the same coin; endpoints are constant."""
    import jax.numpy as jnp

    t = np.arange(512)
    for level in (0, 1, 5):
        h_np = placement.fill_hash_u32(t, level, np)
        h_j = np.asarray(placement.fill_hash_u32(jnp.asarray(t), level, jnp))
        np.testing.assert_array_equal(h_np, h_j)
        assert bool(np.asarray(placement.prob_fill(t, level, 1.0, np)).all())
        assert not bool(np.asarray(placement.prob_fill(t, level, 0.0, np)).any())
        frac = float(np.asarray(placement.prob_fill(t, level, 0.5, np)).mean())
        assert 0.35 < frac < 0.65  # roughly fair coin
    # different levels decorrelate
    assert (
        placement.fill_hash_u32(t, 0, np) != placement.fill_hash_u32(t, 1, np)
    ).any()


# ------------------------------------------------- served-mask partition
@pytest.mark.parametrize("pl", PLACEMENTS)
@settings(max_examples=3, deadline=None)
@given(
    kind=st.sampled_from(FAST_KINDS),
    scenario=st.sampled_from(("stationary", "churn")),
    seed=st.integers(0, 10_000),
)
def test_served_mask_partitions_requests(pl, kind, scenario, seed):
    """Each request is served at exactly one level (or origin), and each
    tier's request count is exactly the unserved stream routed to it —
    placement-independent accounting identities."""
    topo = _topo(kind, pl)
    trace = workloads.make_traces(scenario, N, 1, T, seed=seed)[0]
    out = fleet.simulate_fleet(topo, trace, topo.assignment(trace))
    served = np.zeros(T, bool)
    for l in range(topo.n_levels):
        hit_l = np.asarray(out["hit"][l])
        assert not (served & hit_l).any(), "served twice"
        assert int(np.asarray(out["tiers"][l]["requests"]).sum()) == int(
            (~served).sum()
        )
        # per-node partition of the level's requests along the assignment
        assert int(np.asarray(out["tiers"][l]["hits"]).sum()) == int(hit_l.sum())
        served |= hit_l
    np.testing.assert_array_equal(np.asarray(out["origin_miss"]), ~served)
    # inserts/evictions/occupancy identity survives the fill gate
    for l in range(topo.n_levels):
        c = out["tiers"][l]
        np.testing.assert_array_equal(
            np.asarray(c["inserts"]) - np.asarray(c["evictions"]),
            np.asarray(c["count"]),
        )
        assert (np.asarray(c["evictions"]) >= 0).all()


# ------------------------------------------------------ prob endpoint parity
@pytest.mark.parametrize("kind", FAST_KINDS)
def test_prob_one_is_lce_bitwise(kind):
    """prob(1.0) must reproduce lce bit for bit — and since all-lce runs the
    level-major engine while prob runs the time-major placed engine, this is
    the cross-validation between the two simulator implementations."""
    trace = workloads.make_traces("flash_crowd", N, 1, T, seed=11)[0]
    t_lce, t_p1 = _topo(kind, ()), _topo(kind, "prob(1.0)")
    assert not t_lce.has_placement and t_p1.has_placement
    assign = t_lce.assignment(trace)
    _assert_same_result(
        fleet.simulate_fleet(t_lce, trace, assign),
        fleet.simulate_fleet(t_p1, trace, assign),
        ctx=f"{kind}: prob(1.0) vs lce",
    )


@pytest.mark.parametrize("kind", FAST_KINDS)
def test_prob_zero_is_lcd_bitwise(kind):
    trace = workloads.make_traces("churn", N, 1, T, seed=13)[0]
    t_lcd, t_p0 = _topo(kind, "lcd"), _topo(kind, "prob(0.0)")
    assign = t_lcd.assignment(trace)
    _assert_same_result(
        fleet.simulate_fleet(t_lcd, trace, assign),
        fleet.simulate_fleet(t_p0, trace, assign),
        ctx=f"{kind}: prob(0.0) vs lcd",
    )


@pytest.mark.slow
@pytest.mark.parametrize("kind", jax_cache.JAX_POLICY_KINDS)
def test_prob_endpoints_all_kinds(kind):
    trace = workloads.make_traces("diurnal", N, 1, T, seed=7)[0]
    assign = _topo(kind, ()).assignment(trace)
    _assert_same_result(
        fleet.simulate_fleet(_topo(kind, ()), trace, assign),
        fleet.simulate_fleet(_topo(kind, "prob(1.0)"), trace, assign),
        ctx=f"{kind}: prob(1.0) vs lce",
    )
    _assert_same_result(
        fleet.simulate_fleet(_topo(kind, "lcd"), trace, assign),
        fleet.simulate_fleet(_topo(kind, "prob(0.0)"), trace, assign),
        ctx=f"{kind}: prob(0.0) vs lcd",
    )


# ------------------------------------------------------- lcd subset of lce
@settings(max_examples=4, deadline=None)
@given(
    kind=st.sampled_from(jax_cache.JAX_POLICY_KINDS),
    scenario=st.sampled_from(workloads.SCENARIO_NAMES),
    router=st.sampled_from(("hash", "sticky", "round_robin")),
    seed=st.integers(0, 10_000),
)
def test_lcd_occupancy_subset_of_lce(kind, scenario, router, seed):
    """With no eviction pressure (capacity = id universe; plfua_dyn pinned
    to its initial hot set) every object lcd stores, lce stores too: lcd
    only withholds copies, it never places one lce would not."""
    kw = dict(
        caps=(N, N, N),
        router=router,
        # refresh > T: the dynamic hot set never diverges between the two
        # placement worlds (their sketches see different demand streams)
        refresh=4 * T if kind == "plfua_dyn" else 0,
    )
    trace = workloads.make_traces(scenario, N, 1, T, seed=seed)[0]
    t_lce, t_lcd = _topo(kind, (), **kw), _topo(kind, "lcd", **kw)
    assign = t_lce.assignment(trace)
    out_lce = fleet.simulate_fleet(t_lce, trace, assign)
    out_lcd = fleet.simulate_fleet(t_lcd, trace, assign)
    for l in range(t_lce.n_levels):
        lce_in = np.asarray(out_lce["states"][l]["in_cache"])
        lcd_in = np.asarray(out_lcd["states"][l]["in_cache"])
        assert not (lcd_in & ~lce_in).any(), (
            f"lcd stored an object lce did not at level {l} "
            f"({kind}/{scenario}/{router}/seed={seed})"
        )


# ------------------------------------------------------------ oracle parity
@pytest.mark.parametrize("pl", ("lcd", "prob(0.5)", "admit"))
@pytest.mark.parametrize("kind", FAST_KINDS + ("gdsf",))
def test_placed_engine_matches_oracle(pl, kind):
    """Fast-lane jit-vs-oracle cells (the exhaustive placement x kind x
    scenario matrix is slow-marked in tests/test_differential.py)."""
    topo = _topo(kind, pl)
    trace = workloads.make_traces("churn", N, 1, T, seed=17)[0]
    _assert_oracle_parity(topo, trace, topo.assignment(trace))


def test_lfu_parks_frequency_on_gated_miss():
    """PR 7 satellite: in-memory LFU follows the PLFU parked-frequency
    convention — a placement-gated (unfilled) miss still accumulates the
    object's counter, so a later filled miss inserts with the parked demand
    (the 'in-memory LFU excepted' carve-out from PR 5 is gone)."""
    from repro.core import policies

    pol = policies.LFUCache(2)
    pol.request(1, fill=True)
    pol.request(1, fill=True)  # 1: freq 2
    pol.request(2, fill=True)  # 2: freq 1
    for _ in range(3):
        assert not pol.request(7, fill=False)  # parked demand, no insert
    assert not pol.contains(7)
    assert pol.request(7, fill=False) is False
    pol.request(7, fill=True)  # inserts at freq 5 (4 parked + this one)
    assert pol.contains(7) and pol.contains(1) and not pol.contains(2)
    # eviction pressure respects the promoted frequency: 7 outlives a newcomer
    pol.request(3, fill=True)  # evicts 1 (freq 2) ... not 7 (freq 5)
    assert pol.contains(7) and pol.contains(3) and not pol.contains(1)
    # ... and the jitted step agrees on the same gated sequence
    import jax.numpy as jnp

    spec = jax_cache.PolicySpec(kind="lfu", n_objects=8, capacity=2)
    seq = [(1, True), (1, True), (2, True), (7, False), (7, False),
           (7, False), (7, False), (7, True), (3, True)]
    trace = jnp.asarray([x for x, _ in seq], jnp.int32)
    fill = jnp.asarray([f for _, f in seq])
    import jax

    def step_fn(s, xf):
        x, f = xf
        ns, hit = jax_cache.step(spec, s, x, spec.capacity, fill=f)
        return ns, hit

    state, hits = jax.lax.scan(
        step_fn, jax_cache.init_state(spec), (trace, fill)
    )
    in_cache = np.asarray(state["in_cache"]).astype(bool)
    np.testing.assert_array_equal(
        in_cache, [pol.contains(i) for i in range(8)]
    )


def test_mixed_placements_and_dyn_refresh_match_oracle():
    """Heterogeneous placements per level + plfua_dyn levels with *different*
    refresh periods (the gcd-chunked time scan) + a partial tail period."""
    from repro.core.jax_cache import PolicySpec

    mk = lambda cap, refresh: PolicySpec(
        kind="plfua_dyn", n_objects=N, capacity=cap, refresh=refresh,
        sketch_width=64,
    )
    topo = fleet.Topology(
        levels=((mk(4, 100),) * 4, (mk(9, 150),) * 2, (mk(23, 100),)),
        parents=((0, 0, 1, 1), (0, 0)),
        placements=("lcd", "prob(0.5)", "lce"),
    )
    trace = workloads.make_traces("churn", N, 1, 1030, seed=9)[0]
    _assert_oracle_parity(topo, trace, topo.assignment(trace))


# ------------------------------------------------------ per-level routing
def test_per_level_routers_match_oracle():
    """Sticky edges over hashed regionals (the ROADMAP item), with and
    without placement, jit vs oracle."""
    for pl in ((), "lcd"):
        topo = _topo(
            "plfu", pl, routers=("sticky", "hash", "tree"), session_len=32
        )
        trace = workloads.make_traces("stationary", N, 1, T, seed=3)[0]
        _assert_oracle_parity(topo, trace, topo.assignment(trace))


def test_router_validation():
    with pytest.raises(ValueError, match="cannot be 'tree'"):
        _topo("lru", (), routers=("tree", "hash", "tree"))
    with pytest.raises(ValueError, match="unknown level router"):
        _topo("lru", (), routers=("hash", "nope", "tree"))
    with pytest.raises(ValueError, match="routers must name every level"):
        _topo("lru", (), routers=("hash", "tree"))
    topo = _topo("lru", (), routers=("sticky", "hash", "tree"))
    assert topo.router == "sticky" and topo.has_level_routers


# ---------------------------------------------------- admit placement value
def test_admit_placement_filters_one_hit_wonders():
    """A one-hit-wonder stream: the admit gate keeps tail objects out of a
    full edge (fewer fills than lce) without giving up the head's hits."""
    rng = np.random.default_rng(0)
    head = rng.integers(0, 8, size=T)  # 8 hot objects
    tail = np.arange(T) % (N - 8) + 8  # every tail object at most ~8 times
    mix = np.where(rng.random(T) < 0.5, head, tail).astype(np.int32)
    t_lce = _topo("lru", (), caps=(6, 12, 24))
    t_admit = _topo("lru", "admit", caps=(6, 12, 24))
    assign = t_lce.assignment(mix)
    out_lce = fleet.simulate_fleet(t_lce, mix, assign)
    out_admit = fleet.simulate_fleet(t_admit, mix, assign)
    fills_lce = int(np.asarray(out_lce["tiers"][0]["inserts"]).sum())
    fills_admit = int(np.asarray(out_admit["tiers"][0]["inserts"]).sum())
    assert fills_admit < fills_lce, (fills_admit, fills_lce)
    chr_lce = int(np.asarray(out_lce["hit"][0]).sum())
    chr_admit = int(np.asarray(out_admit["hit"][0]).sum())
    assert chr_admit >= chr_lce - 0.02 * T  # no meaningful CHR cost


# ------------------------------------------------------- report + acceptance
def test_placement_report_rows_and_lcd_energy_win():
    """fleet_report prices placement as a distinct row per level, and lcd
    beats lce on management energy on stationary with CHR within 2 points
    (the PR's acceptance criterion, at bench-smoke scale)."""
    n = 2_000
    traces = workloads.make_traces("stationary", n, 2, 8_000, seed=0)
    reps = {}
    for pl in ("lce", "lcd"):
        topo = fleet.tree(
            n_objects=n, widths=(8, 2, 1), kinds="plfu",
            capacities=(60, 240, 480), placements=pl,
        )
        out = fleet.simulate_fleet_batch(topo, traces, topo.assignment(traces))
        reps[pl] = fleet.fleet_report(topo, out)
    for pl, rep in reps.items():
        rows = rep.rows()
        p_rows = [r for r in rows if r["tier"].endswith(":placement")]
        assert [r["tier"] for r in p_rows] == [
            "edge:placement", "mid1:placement", "root:placement"
        ]
        assert all(r["policy"] == pl for r in p_rows)
        assert rep.placement_energy_j > 0
        # nodes + (aggregate + placement)/level + the origin summary row
        assert len(rows) == 11 + 2 * 3 + 1
    assert reps["lcd"].mgmt_energy_j < reps["lce"].mgmt_energy_j
    assert abs(reps["lcd"].total_chr - reps["lce"].total_chr) <= 0.02


# ----------------------------------------------------------- determinism
def test_prob_placement_deterministic_across_processes():
    """Same TraceSpec seed -> identical fleet reports in two *separate*
    process invocations: the prob(p) threshold-hash path is a pure function
    of (trace position, level), never a platform RNG."""
    script = textwrap.dedent(
        """
        import hashlib, json, sys
        sys.path.insert(0, "src")
        import numpy as np
        from repro import fleet, workloads

        spec = workloads.TraceSpec("churn", 96, 1, 600, seed=23)
        trace = workloads.make_traces(
            spec.scenario, spec.n_objects, spec.n_samples, spec.trace_len,
            seed=spec.seed,
        )[0]
        topo = fleet.tree(
            n_objects=96, widths=(4, 2, 1), kinds="plfu",
            capacities=(4, 9, 23), placements="prob(0.3)", router="sticky",
        )
        out = fleet.simulate_fleet(topo, trace, topo.assignment(trace))
        rep = fleet.fleet_report(topo, out)
        digest = hashlib.sha256(
            b"".join(np.asarray(out["hit"][l]).tobytes() for l in range(3))
        ).hexdigest()
        print(json.dumps({"rows": rep.rows(), "hits": digest}, sort_keys=True))
        """
    )
    runs = [
        subprocess.run(
            [sys.executable, "-c", script], capture_output=True, text=True,
            cwd=REPO_ROOT, env=CPU_CHILD_ENV, timeout=600,
        )
        for _ in range(2)
    ]
    for r in runs:
        assert r.returncode == 0, r.stderr[-2000:]
    a, b = (r.stdout.strip().splitlines()[-1] for r in runs)
    assert a == b, "fleet report differs across processes for the same seed"
    assert json.loads(a)["rows"], "empty report"


# ----------------------------------------------------------- shard parity
@pytest.mark.slow
def test_sharded_placement_paths_match_on_forced_devices():
    """Real 4-device run in a subprocess: the edge-sharded placed path (the
    time-major scan inside shard_map, per-step psum) and the sample-sharded
    on-device-generation path must reproduce the single-device placed
    results exactly, for every placement kind."""
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
        mesh = fleet.fleet_mesh()
        for kind, pl in [
            ("plfu", "lcd"), ("plfu", "prob(0.5)"), ("plfu", "admit"),
            ("tinylfu", "lcd"), ("plfua_dyn", "prob(0.5)"),
        ]:
            topo = fleet.tree(n_objects=160, widths=(8, 2, 1), kinds=kind,
                              capacities=(5, 12, 28), placements=pl)
            trace = workloads.make_traces("churn", 160, 1, 1200, seed=5)[0]
            assign = topo.assignment(trace)
            a = fleet.simulate_fleet(topo, trace, assign)
            b = fleet.simulate_fleet_sharded(topo, trace, assign, mesh=mesh)
            ref = fleet.simulate_fleet_reference(topo, trace, assign)
            for l in range(3):
                np.testing.assert_array_equal(
                    np.asarray(a["hit"][l]), np.asarray(b["hit"][l]))
                np.testing.assert_array_equal(
                    np.asarray(a["hit"][l]), ref.level_hit[l])
                for k in a["tiers"][l]:
                    np.testing.assert_array_equal(
                        np.asarray(a["tiers"][l][k]),
                        np.asarray(b["tiers"][l][k]))
                for k in a["states"][l]:
                    np.testing.assert_array_equal(
                        np.asarray(a["states"][l][k]),
                        np.asarray(b["states"][l][k]))

        topo = fleet.tree(n_objects=160, widths=(4, 1), kinds="plfu",
                          capacities=(6, 24), placements="lcd")
        dspec = DeviceTraceSpec("stationary", 160, n_samples=4,
                                trace_len=1000, seed=2)
        r1, t1, a1 = fleet.simulate_fleet_device(topo, dspec)
        r4, t4, a4 = fleet.simulate_fleet_device(topo, dspec, mesh=mesh)
        np.testing.assert_array_equal(np.asarray(t1), np.asarray(t4))
        for l in range(2):
            np.testing.assert_array_equal(np.asarray(r1["hit"][l]),
                                          np.asarray(r4["hit"][l]))
        print("PLACED_SHARDED_OK")
        """
    )
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        cwd=REPO_ROOT, env=CPU_CHILD_ENV, timeout=900,
    )
    assert "PLACED_SHARDED_OK" in out.stdout, (
        out.stdout[-1000:], out.stderr[-3000:],
    )


# ------------------------------------------------------------- serving knob
def test_two_tier_serving_constructor_accepts_placement():
    """The legacy two-tier serving constructor exposes the placement knob."""
    from repro.serving import FleetContentCache

    fc = FleetContentCache(2, 4, 16, policy="lru", placements=("lcd", "lce"))
    assert fc.lookup(5) is None
    assert fc.offer(5, "p5")
    assert fc.levels[1][0].peek(5) == "p5"  # parent stored it
    assert all(e.peek(5) is None for e in fc.levels[0])  # edges did not


# ----------------------------------------------------------- padded tail pin
@pytest.mark.parametrize("r", [7, 29])
def test_placed_partial_tail_no_leakage(r):
    """A bounded run is one chunk of ``T = G*k + r`` requests, scanned in
    sub-chunks of gcd(refresh, T), with a partial refresh period at its
    tail. That tail must be invisible: the telemetry window series,
    occupancy snapshots, final states and counters of a prob(1.0) tree
    (placed engine) must equal the lce tree (level-major engine) bit for
    bit — a tail refresh fire, phantom occupancy samples or window spill on
    either side breaks the identity. The window is chosen to not divide T
    either."""
    import jax.numpy as jnp

    from repro.telemetry import TelemetrySpec

    G = 30  # the plfua_dyn refresh period = the placed engine's gcd chunk
    T = G * 4 + r
    tel = TelemetrySpec(window={7: 127, 29: 149}[r], n_groups=3)
    rng = np.random.default_rng(0)
    groups = rng.integers(0, 3, size=N).astype(np.int32)

    def mk(pl):
        return fleet.tree(
            n_objects=N, widths=(3, 1), kinds=("lru", "plfua_dyn"),
            capacities=(5, 13), refresh=(0, G), placements=("lce", pl),
        )

    trace = workloads.make_traces("churn", N, 1, T, seed=5)[0]
    t_lce = mk("lce")
    assignment = t_lce.assignment(trace)
    a = fleet.simulate_fleet(
        t_lce, jnp.asarray(trace), jnp.asarray(assignment), tel, groups=groups
    )
    b = fleet.simulate_fleet(
        mk("prob(1.0)"), jnp.asarray(trace), jnp.asarray(assignment), tel,
        groups=groups,
    )
    _assert_same_result(a, b, ctx=f"tail r={r}")
    for l in range(2):
        np.testing.assert_array_equal(
            np.asarray(a["telemetry"][l]), np.asarray(b["telemetry"][l]),
            err_msg=f"telemetry level {l}, tail r={r}",
        )
        np.testing.assert_array_equal(
            np.asarray(a["telemetry_pressure"][l]),
            np.asarray(b["telemetry_pressure"][l]),
            err_msg=f"pressure level {l}, tail r={r}",
        )
