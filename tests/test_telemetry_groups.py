"""Group-segmented telemetry acceptance suite (PR 8).

The contract under test, per docs/observability.md:

* with ``TelemetrySpec(window, n_groups)`` + an id→group catalogue, every
  simulator tier (core scan, both fleet engines, the Pallas kernel) emits a
  ``[..., n_windows, n_groups, N_METRICS]`` series that equals the grouped
  host-side oracle **exactly** for every policy kind;
* summing the grouped series over the group axis reproduces the ungrouped
  series bit-for-bit, and enabling the group axis perturbs no simulation
  output;
* the per-tenant rollups on top hold their schemas: ``tenant_rows`` (pinned
  ``TENANT_ROW_FIELDS``), the latency model's exact discrete percentiles,
  the cross-tenant eviction-pressure channel, the grouped exporter rows and
  the self-contained HTML dashboard.
"""
import numpy as np
import pytest

from repro import fleet, telemetry, workloads
from repro.core import jax_cache, policies, registry
from repro.fleet.report import TENANT_ROW_FIELDS
from repro.kernels.cache_sim.ops import cache_sim
from repro.telemetry import (
    LatencyModel,
    TelemetrySpec,
    export,
    group_onehot,
    oracle,
    percentile_us,
)
from repro.telemetry.spec import METRIC_INDEX, METRICS, N_METRICS

ALL_KINDS = registry.names(jax=True, grouped_telemetry=True)
N, CAP, T = 128, 12, 900
W = 128  # 900 = 7*128 + 4 -> the partial tail window is always exercised
G = 4
GROUPS = workloads.tenant_groups(N, G)

#: same sketch knobs as tests/test_telemetry.py so aging / refresh fire
_KNOBS = {
    "wlfu": {"window": 64},
    "tinylfu": {"window": 200, "doorkeeper": 64},
    "plfua_dyn": {"refresh": 250},
}


def _pair(kind, n=N, cap=CAP):
    kw = _KNOBS.get(kind, {})
    spec = jax_cache.PolicySpec(kind=kind, n_objects=n, capacity=cap, **kw)
    pol = policies.make_policy(kind, cap, n_objects=n, **kw)
    return spec, pol


def _trace(seed, n=N, t=T):
    return workloads.make_traces(
        "multi_tenant", n, n_samples=1, trace_len=t, seed=seed, n_tenants=G
    )[0]


# ---------------------------------------------------- core scan vs the oracle
@pytest.mark.parametrize("kind", ALL_KINDS)
def test_grouped_core_matches_oracle(kind):
    """Grouped jax series == grouped oracle, exactly, and both sum over the
    group axis to the (PR 6, already oracle-pinned) ungrouped series."""
    spec, pol = _pair(kind)
    trace = _trace(seed=23)
    tel = TelemetrySpec(W, n_groups=G)
    hits_g, state_g, series_g = jax_cache.simulate(spec, trace, tel, None, GROUPS)
    ref_g = oracle.windowed_reference(pol, trace, W, groups=GROUPS, n_groups=G)
    np.testing.assert_array_equal(
        np.asarray(series_g), ref_g,
        err_msg=f"grouped series diverges for {kind} (metric axis: {METRICS})",
    )
    # group-sum identity against the same-seed ungrouped run
    hits0, state0, series0 = jax_cache.simulate(spec, trace, TelemetrySpec(W))
    np.testing.assert_array_equal(
        np.asarray(series_g).sum(axis=1), np.asarray(series0),
        err_msg=f"group-sum != ungrouped series for {kind}",
    )
    # the group axis is observational: hits and final state are untouched
    np.testing.assert_array_equal(np.asarray(hits_g), np.asarray(hits0))
    for k in state0:
        np.testing.assert_array_equal(
            np.asarray(state_g[k]), np.asarray(state0[k]), err_msg=f"state[{k}]"
        )


def test_grouped_core_sized_matches_oracle():
    """Byte-mode (gdsf + size catalogue): grouped byte columns stay exact."""
    sizes = (np.arange(N, dtype=np.int32) % 9) + 1
    spec = jax_cache.PolicySpec(
        kind="gdsf", n_objects=N, capacity=CAP, capacity_bytes=64
    )
    pol = policies.make_policy(
        "gdsf", CAP, n_objects=N, capacity_bytes=64, sizes=sizes
    )
    trace = _trace(seed=29)
    tel = TelemetrySpec(W, n_groups=G)
    _, _, series_g = jax_cache.simulate(spec, trace, tel, sizes, GROUPS)
    ref_g = oracle.windowed_reference(pol, trace, W, groups=GROUPS, n_groups=G)
    np.testing.assert_array_equal(np.asarray(series_g), ref_g)
    hb = np.asarray(series_g)[..., METRIC_INDEX["hit_bytes"]]
    hits = np.asarray(series_g)[..., METRIC_INDEX["hits"]]
    assert hb.sum() >= hits.sum()  # every hit moved at least one byte


def test_grouped_batch_matches_single():
    spec, _ = _pair("plfua_dyn")
    tel = TelemetrySpec(W, n_groups=G)
    traces = workloads.make_traces(
        "multi_tenant", N, n_samples=3, trace_len=T, seed=9, n_tenants=G
    )
    hits_b, series_b = jax_cache.simulate_batch(spec, traces, tel, None, GROUPS)
    assert np.asarray(series_b).shape == (3, -(-T // W), G, N_METRICS)
    for s in range(3):
        h1, _, s1 = jax_cache.simulate(spec, traces[s], tel, None, GROUPS)
        np.testing.assert_array_equal(np.asarray(series_b)[s], np.asarray(s1))
        np.testing.assert_array_equal(np.asarray(hits_b)[s], np.asarray(h1))


# -------------------------------------------------------------- the catalogue
def test_tenant_groups_matches_multi_tenant_blocks():
    """The id→tenant catalogue and the trace generator share one block map:
    a single-tenant mixture only ever requests ids of that tenant's group."""
    n = 130  # not divisible by 4: exercises the remainder distribution
    g = workloads.tenant_groups(n, 4)
    assert g.shape == (n,) and g.dtype == np.int32
    assert (np.diff(g) >= 0).all()  # contiguous blocks
    np.testing.assert_array_equal(np.bincount(g), [33, 33, 32, 32])
    for t in range(4):
        w = tuple(1.0 if i == t else 0.0 for i in range(4))
        tr = workloads.make_traces(
            "multi_tenant", n, n_samples=1, trace_len=300, seed=3,
            n_tenants=4, weights=w,
        )[0]
        assert (g[tr] == t).all()
    with pytest.raises(ValueError):
        workloads.tenant_groups(4, 5)
    with pytest.raises(ValueError):
        workloads.tenant_groups(4, 0)


# ----------------------------------------------------------------- fleet tiers
def _topo3(kind, **kw):
    return fleet.tree(
        n_objects=N,
        widths=(4, 2, 1),
        kinds=kind,
        capacities=(4, 9, 23),
        window=48 if kind == "wlfu" else 0,
        **kw,
    )


@pytest.mark.parametrize("kind", ("lru", "plfua_dyn"))
def test_fleet_grouped_sum_identity(kind):
    """Level-major engine: grouped series sums to the ungrouped series per
    level, non-telemetry outputs stay bit-identical, and the pressure
    channel holds its (K_l, n_windows, n_groups) shape."""
    topo = _topo3(kind)
    trace = _trace(seed=17, t=700)
    assign = topo.assignment(trace)
    out0 = fleet.simulate_fleet(topo, trace, assign, TelemetrySpec(96))
    tel0 = out0.pop("telemetry")
    outg = fleet.simulate_fleet(
        topo, trace, assign, TelemetrySpec(96, n_groups=G), None, GROUPS
    )
    telg = outg.pop("telemetry")
    pressure = outg.pop("telemetry_pressure")
    assert out0.keys() == outg.keys()
    for k in out0:
        a, b = out0[k], outg[k]
        if isinstance(a, dict):
            for kk in a:
                np.testing.assert_array_equal(np.asarray(a[kk]), np.asarray(b[kk]))
        elif isinstance(a, (tuple, list)):
            for x, y in zip(a, b):
                if isinstance(x, dict):
                    for kk in x:
                        np.testing.assert_array_equal(
                            np.asarray(x[kk]), np.asarray(y[kk])
                        )
                else:
                    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    nw = -(-700 // 96)
    for l in range(topo.n_levels):
        sg = np.asarray(telg[l])
        assert sg.shape == (len(topo.levels[l]), nw, G, N_METRICS)
        np.testing.assert_array_equal(
            sg.sum(axis=2), np.asarray(tel0[l]),
            err_msg=f"group-sum != ungrouped series at level {l}",
        )
        p = np.asarray(pressure[l])
        assert p.shape == (len(topo.levels[l]), nw, G)
        assert (p >= 0).all()
        # pressure counts a subset of the level's evictions
        assert p.sum() <= sg[..., METRIC_INDEX["evictions"]].sum()


def test_fleet_grouped_placed_engine_matches_level_major():
    """prob(1.0) placement is behaviourally lce, so the time-major placed
    engine must emit the level-major engine's exact grouped series and
    pressure — the PR 6 cross-engine differential, now on the group axis."""
    trace = _trace(seed=41, t=700)
    tel = TelemetrySpec(96, n_groups=G)
    t_lce = _topo3("plfua_dyn")
    t_prob = _topo3("plfua_dyn", placements="prob(1.0)")
    assign = t_lce.assignment(trace)
    out_lce = fleet.simulate_fleet(t_lce, trace, assign, tel, None, GROUPS)
    out_prob = fleet.simulate_fleet(t_prob, trace, assign, tel, None, GROUPS)
    for l in range(t_lce.n_levels):
        np.testing.assert_array_equal(
            np.asarray(out_lce["telemetry"][l]),
            np.asarray(out_prob["telemetry"][l]),
            err_msg=f"grouped engine series diverge at level {l}",
        )
        np.testing.assert_array_equal(
            np.asarray(out_lce["telemetry_pressure"][l]),
            np.asarray(out_prob["telemetry_pressure"][l]),
            err_msg=f"pressure diverges at level {l}",
        )


def test_fleet_single_group_pressure_is_zero():
    """G=1 means no cross-tenant traffic, so eviction pressure must vanish
    even though evictions happen."""
    topo = _topo3("lru")
    trace = _trace(seed=7, t=700)
    assign = topo.assignment(trace)
    out = fleet.simulate_fleet(
        topo, trace, assign, TelemetrySpec(96, n_groups=1),
        None, np.zeros(N, np.int32),
    )
    ev = sum(
        np.asarray(s)[..., METRIC_INDEX["evictions"]].sum()
        for s in out["telemetry"]
    )
    assert ev > 0
    for p in out["telemetry_pressure"]:
        assert np.asarray(p).sum() == 0


# -------------------------------------------------------------- Pallas kernel
@pytest.mark.parametrize("kind", ("lru", "tinylfu", "plfua_dyn"))
def test_kernel_grouped_matches_jax(kind):
    n, cap, tlen, w, g = 64, 8, 300, 64, 4
    kw = {}
    if kind == "tinylfu":
        kw["window"] = 80
    if kind == "plfua_dyn":
        kw["refresh"] = 90
    groups = workloads.tenant_groups(n, g)
    traces = workloads.make_traces(
        "multi_tenant", n, n_samples=2, trace_len=tlen, seed=3, n_tenants=g
    )
    spec = jax_cache.PolicySpec(kind=kind, n_objects=n, capacity=cap, **kw)
    _, series_jax = jax_cache.simulate_batch(
        spec, traces, TelemetrySpec(w, n_groups=g), None, groups
    )
    args = dict(kind=kind, n_objects=n, capacity=cap, interpret=True, **kw)
    h0, f0, c0, series0 = cache_sim(traces, telemetry_window=w, **args)
    h1, f1, c1, series_g = cache_sim(
        traces, telemetry_window=w, n_groups=g, groups=groups, **args
    )
    # the group axis must not perturb the kernel's simulation outputs ...
    np.testing.assert_array_equal(np.asarray(h0), np.asarray(h1))
    np.testing.assert_array_equal(np.asarray(f0), np.asarray(f1))
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(c1))
    # ... its grouped series must equal the jax scan's (itself oracle-pinned)
    np.testing.assert_array_equal(np.asarray(series_g), np.asarray(series_jax))
    # ... and sum over groups to the kernel's own ungrouped series
    np.testing.assert_array_equal(
        np.asarray(series_g).sum(axis=2), np.asarray(series0)
    )


def test_kernel_grouped_sized():
    """Byte-capacity kernel path with the group axis (gdsf + sizes)."""
    n, cap, tlen, w, g = 64, 8, 300, 64, 4
    sizes = (np.arange(n, dtype=np.int32) % 7) + 1
    groups = workloads.tenant_groups(n, g)
    traces = workloads.make_traces(
        "multi_tenant", n, n_samples=2, trace_len=tlen, seed=5, n_tenants=g
    )
    spec = jax_cache.PolicySpec(
        kind="gdsf", n_objects=n, capacity=cap, capacity_bytes=40
    )
    _, series_jax = jax_cache.simulate_batch(
        spec, traces, TelemetrySpec(w, n_groups=g), sizes, groups
    )
    *_, series_g = cache_sim(
        traces, kind="gdsf", n_objects=n, capacity=cap, capacity_bytes=40,
        sizes=sizes, telemetry_window=w, n_groups=g, groups=groups,
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(series_g), np.asarray(series_jax))


def test_kernel_group_option_validation():
    traces = np.zeros((1, 8), np.int32)
    args = dict(kind="lru", n_objects=16, capacity=4, interpret=True)
    with pytest.raises(ValueError, match="n_groups must be >= 0"):
        cache_sim(traces, telemetry_window=4, n_groups=-1, **args)
    with pytest.raises(ValueError, match="telemetry"):
        cache_sim(traces, n_groups=2, groups=np.zeros(16, np.int32), **args)
    with pytest.raises(ValueError, match="groups"):
        cache_sim(traces, telemetry_window=4, n_groups=2, **args)


# ----------------------------------------------------------- latency SLO model
def test_percentile_us_discrete_inverse_cdf():
    counts = [90, 9, 1]
    values = [1000.0, 5000.0, 25000.0]
    assert percentile_us(counts, values, 0.5) == 1000.0
    assert percentile_us(counts, values, 0.9) == 1000.0
    assert percentile_us(counts, values, 0.95) == 5000.0
    assert percentile_us(counts, values, 1.0) == 25000.0
    assert percentile_us([0, 0], [1.0, 2.0], 0.5) == 0.0  # empty histogram
    # order-independence: the histogram need not come sorted
    assert percentile_us(counts[::-1], values[::-1], 0.95) == 5000.0
    with pytest.raises(ValueError):
        percentile_us(counts, values, 1.5)
    with pytest.raises(ValueError):
        percentile_us([1, 2], [1.0], 0.5)


def test_latency_model_buckets_and_stats():
    m = LatencyModel.default(3)
    assert m.n_levels == 3
    assert m.bucket_us == (1000.0, 5000.0, 25000.0, 125000.0)
    hist = np.array([90, 9, 1, 0])
    assert m.percentile(hist, 0.5) == 1000.0
    assert m.percentile(hist, 0.99) == 5000.0
    assert m.mean_us(hist) == pytest.approx((90 * 1000 + 9 * 5000 + 25000) / 100)
    # histogram() stacks per-level counts with the origin remainder
    h = m.histogram(np.array([[4], [2], [1]]), np.array([3]))
    np.testing.assert_array_equal(h, [[4], [2], [1], [3]])
    with pytest.raises(ValueError):
        m.histogram(np.zeros((2, 1)), np.zeros(1))
    with pytest.raises(ValueError):
        LatencyModel(service_us=(), origin_us=1.0)
    with pytest.raises(ValueError):
        LatencyModel(service_us=(1.0, -2.0), origin_us=5.0)


# ----------------------------------------------- tenant report + exporter rows
def _grouped_report(kind="plfua_dyn", sizes=None):
    topo = _topo3(kind)
    tel = TelemetrySpec(96, n_groups=G)
    traces = workloads.make_traces(
        "multi_tenant", N, n_samples=2, trace_len=700, seed=13, n_tenants=G
    )
    assigns = np.stack([topo.assignment(t) for t in traces])
    out = fleet.simulate_fleet_batch(topo, traces, assigns, tel, sizes, GROUPS)
    return topo, fleet.fleet_report(topo, out, telemetry=tel), traces


def test_tenant_rows_schema_and_accounting():
    """TENANT_ROW_FIELDS is pinned literally; the rows must balance the
    fleet's demand ledger (requests, bytes, hot-set share) and order their
    percentiles sanely."""
    expected = (
        "tenant", "requests", "hits", "chr", "req_bytes", "hit_bytes",
        "byte_chr", "egress_bytes", "p50_us", "p99_us", "mean_us",
        "eviction_pressure", "hot_share",
    )
    assert TENANT_ROW_FIELDS == expected
    topo, rep, traces = _grouped_report()
    rows = rep.tenant_rows()
    assert len(rows) == G
    for r in rows:
        assert tuple(r.keys()) == expected
        assert r["p50_us"] <= r["p99_us"]
        assert 0.0 <= r["chr"] <= 1.0
        # unit fallback: byte ledger degenerates to the request ledger
        assert r["req_bytes"] == r["requests"]
        assert r["hit_bytes"] == r["hits"]
        assert r["req_bytes"] == r["hit_bytes"] + r["egress_bytes"]
    assert sum(r["requests"] for r in rows) == traces.size
    assert sum(r["hot_share"] for r in rows) == pytest.approx(1.0)
    # multi_tenant shares one LRU-ish fleet: contention must register
    assert sum(r["eviction_pressure"] for r in rows) > 0
    # tenant 0 dominates the mixture -> strictly more demand than tenant 3
    assert rows[0]["requests"] > rows[-1]["requests"]
    # a mismatched latency model is refused loudly
    with pytest.raises(ValueError):
        rep.tenant_rows(LatencyModel.default(topo.n_levels + 1))
    # and an ungrouped report has no tenant view at all
    out = fleet.simulate_fleet_batch(
        topo, traces, np.stack([topo.assignment(t) for t in traces]),
        TelemetrySpec(96),
    )
    with pytest.raises(ValueError):
        fleet.fleet_report(topo, out, telemetry=TelemetrySpec(96)).tenant_rows()


def test_grouped_window_rows_and_export(tmp_path):
    topo, rep, _ = _grouped_report()
    nw = -(-700 // 96)
    rows = rep.window_rows()
    assert len(rows) == sum(len(lv) for lv in topo.levels) * nw * G
    r0 = rows[0]
    assert {"node", "window", "group", "t_start", "level", "policy"} <= set(r0)
    assert all(m in r0 for m in METRICS)
    assert sorted({r["group"] for r in rows}) == list(range(G))
    path = tmp_path / "grouped.jsonl"
    export.write_jsonl(path, rows)
    assert export.read_jsonl(path) == rows
    # the grouped exporter refuses a flat series (shape is ambiguous)
    with pytest.raises(ValueError):
        export.series_rows(np.zeros((3, N_METRICS), np.int32), 10, grouped=True)


# ------------------------------------------------------------------- dashboard
def test_dashboard_smoke(tmp_path):
    """The HTML artifact is entirely self-contained: inline SVG sparklines,
    no scripts, no external references of any kind."""
    from repro.telemetry import dashboard

    topo, rep, _ = _grouped_report()
    latency = LatencyModel.default(topo.n_levels)
    path = tmp_path / "dash.html"
    dashboard.write_dashboard(
        path, rep.window_rows(), latency=latency,
        tenant_rows=rep.tenant_rows(latency),
    )
    html_text = path.read_text()
    assert html_text.startswith("<!doctype html>")
    assert "<svg" in html_text and "polyline" in html_text
    assert "<script" not in html_text
    assert "http://" not in html_text and "https://" not in html_text
    assert "<link" not in html_text and "@import" not in html_text
    # the SLO table and every tenant section made it in
    for field in ("p99_us", "eviction_pressure"):
        assert field in html_text
    for g in range(G):
        assert f"tenant {g}" in html_text
    # degenerate input still renders (flat ungrouped rows, no tenant table)
    flat = export.series_rows(np.zeros((1, 3, N_METRICS), np.int32), 10)
    text = dashboard.render_dashboard(flat)
    assert "<svg" in text and "<script" not in text


# ------------------------------------------------------- spec-level validation
def test_grouped_spec_validation():
    with pytest.raises(ValueError):
        TelemetrySpec(W, n_groups=-1)
    assert TelemetrySpec(W).n_groups == 0
    # out-of-range ids vanish from every group (documented escape hatch)
    oh = group_onehot(np.array([0, 1, 7], np.int32), 2)
    np.testing.assert_array_equal(oh, [[1, 0], [0, 1], [0, 0]])
    # the oracle refuses a catalogue without a group count
    _, pol = _pair("lru")
    with pytest.raises(ValueError):
        oracle.windowed_reference(pol, np.zeros(8, np.int32), 4, groups=GROUPS)
