"""JAX simulator must match the Python reference decision-for-decision."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import fleet, workloads
from repro.core import jax_cache, policies, zipf
from repro.fleet.reference import build_policy


def _py_policy(kind, n, cap, window):
    if kind == "plfua":
        return policies.PLFUACache(cap, hot=range(min(n, 2 * cap)))
    if kind == "wlfu":
        return policies.WLFUCache(cap, window=window)
    return policies.make_policy(kind, cap, n_objects=n)


def _compare(kind, n, cap, trace, window=16):
    spec = jax_cache.PolicySpec(
        kind=kind, n_objects=n, capacity=cap,
        window=window if kind == "wlfu" else 0,
    )
    hits_jax, state = jax_cache.simulate(spec, np.asarray(trace, np.int32))
    hits_jax = np.asarray(hits_jax)

    pol = _py_policy(kind, n, cap, window)
    hits_py = np.array([pol.request(int(x)) for x in trace])

    np.testing.assert_array_equal(
        hits_jax, hits_py,
        err_msg=f"hit sequence diverges for {kind} n={n} cap={cap}",
    )
    cached_jax = np.asarray(state["in_cache"])
    cached_py = np.array([pol.contains(i) for i in range(n)])
    np.testing.assert_array_equal(cached_jax, cached_py)
    assert int(state["count"]) == int(cached_py.sum())


# A fixed set of static shapes keeps jit recompiles bounded.
CASES = [
    (8, 1), (8, 3), (16, 5), (16, 16), (30, 7),
]


@pytest.mark.parametrize("kind", jax_cache.JAX_POLICY_KINDS)
@pytest.mark.parametrize("n,cap", CASES)
def test_jax_matches_reference_random(kind, n, cap):
    rng = np.random.default_rng(hash((kind, n, cap)) % 2**32)
    trace = rng.integers(0, n, size=256)
    _compare(kind, n, cap, trace)


@pytest.mark.parametrize("kind", jax_cache.JAX_POLICY_KINDS)
def test_jax_matches_reference_zipf(kind):
    trace = zipf.sample_trace(64, 2000, seed=5)
    _compare(kind, 64, 9, trace)


def test_simulate_batch_matches_loop():
    spec = jax_cache.PolicySpec(kind="plfu", n_objects=32, capacity=5)
    traces = zipf.sample_traces(32, n_samples=4, trace_len=500, seed=1)
    batched = np.asarray(jax_cache.simulate_batch(spec, traces))
    for s in range(4):
        single, _ = jax_cache.simulate(spec, traces[s])
        np.testing.assert_array_equal(batched[s], np.asarray(single))


def test_metadata_entries_matches_reference():
    n, cap = 64, 9
    trace = zipf.sample_trace(n, 3000, seed=7)
    for kind in ("lfu", "plfu", "plfua"):
        spec = jax_cache.PolicySpec(kind=kind, n_objects=n, capacity=cap)
        _, state = jax_cache.simulate(spec, trace)
        pol = _py_policy(kind, n, cap, 0)
        pol.run(trace)
        assert int(jax_cache.metadata_entries(spec, state)) == pol.metadata_entries


def test_chr_improves_lfu_to_plfu_to_plfua_smallN():
    """Paper headline ordering on a small-N Zipf case."""
    n, cap = 200, 10
    traces = zipf.sample_traces(n, n_samples=6, trace_len=20_000, seed=9)
    out = {}
    for kind in ("lfu", "plfu", "plfua"):
        spec = jax_cache.PolicySpec(kind=kind, n_objects=n, capacity=cap)
        hits = np.asarray(jax_cache.simulate_batch(spec, traces))
        out[kind] = hits.mean()
    assert out["plfu"] > out["lfu"]
    assert out["plfua"] >= out["plfu"] - 0.005


# Static plfua scans the admissible prefix: (H + 1) slots, the trace clamped
# to min(x, H), the state padded back to n_objects. Each case is checked
# against the dense scan of `step` on the full spec and against the plain
# reference, sample by sample and through the vmapped batch.
PREFIX_CASES = {
    # name: (n_objects, capacity, hot_size, capacity_bytes, n_samples)
    "hot_below_n": (64, 5, 12, 0, 4),
    "hot_at_least_n": (16, 5, 20, 0, 4),
    "default_hot": (64, 5, 0, 0, 4),
    "bytes": (64, 5, 12, 12, 4),
    "zipf_12_samples": (1000, 20, 40, 0, 12),
}


def _dense_simulate(spec, trace, sizes=None):
    """The dense program: `lax.scan` of `step` over the full (N,) state."""
    sizes = None if sizes is None else jnp.asarray(sizes, jnp.int32)
    state, hits = jax.lax.scan(
        lambda s, x: jax_cache.step(spec, s, x, sizes=sizes),
        jax_cache.init_state(spec),
        jnp.asarray(trace, jnp.int32),
    )
    return hits, state


def _prefix_traces(name, n, h, n_samples):
    if name == "zipf_12_samples":
        return zipf.sample_traces(n, n_samples=n_samples, trace_len=600, seed=11)
    rng = np.random.default_rng(sum(map(ord, name)))
    # half the requests fall in the hot prefix so hits and evictions happen;
    # the ids on both sides of the boundary and the last id recur
    traces = np.where(
        rng.random((n_samples, 400)) < 0.5,
        rng.integers(0, h, (n_samples, 400)),
        rng.integers(0, n, (n_samples, 400)),
    )
    traces[:, ::7] = h - 1
    traces[:, 3::11] = min(h, n - 1)
    traces[:, 5::13] = n - 1
    return traces.astype(np.int32)


@pytest.mark.parametrize("name", list(PREFIX_CASES))
def test_plfua_prefix_scan_exact(name):
    n, cap, hot, cap_b, n_samples = PREFIX_CASES[name]
    spec = jax_cache.PolicySpec(
        "plfua", n_objects=n, capacity=cap, hot_size=hot, capacity_bytes=cap_b
    )
    h = spec.effective_hot
    sizes = np.random.default_rng(3).integers(1, 5, n).astype(np.int32) if cap_b else None
    traces = _prefix_traces(name, n, h, n_samples)
    dense = jax.jit(functools.partial(_dense_simulate, spec))
    batched = np.asarray(jax_cache.simulate_batch(spec, traces, None, sizes))
    for s, trace in enumerate(traces):
        hits, state = jax_cache.simulate(spec, trace, None, sizes)
        want_hits, want_state = dense(trace, sizes)
        np.testing.assert_array_equal(np.asarray(hits), np.asarray(want_hits))
        np.testing.assert_array_equal(batched[s], np.asarray(want_hits))
        assert sorted(state) == sorted(want_state)
        for key in state:
            np.testing.assert_array_equal(np.asarray(state[key]), np.asarray(want_state[key]))

        pol = policies.make_policy(
            "plfua", cap, hot=range(h), sizes=sizes, capacity_bytes=cap_b
        )
        ref_hits = np.array([pol.request(int(x)) for x in trace])
        np.testing.assert_array_equal(np.asarray(hits), ref_hits)
        plfu = pol._plfu
        ref_freq = np.zeros(n, np.int32)
        for obj, f in {**plfu._parked, **plfu._freq}.items():
            ref_freq[obj] = f
        np.testing.assert_array_equal(np.asarray(state["freq"]), ref_freq)
        np.testing.assert_array_equal(
            np.asarray(state["in_cache"]), [pol.contains(i) for i in range(n)]
        )
        np.testing.assert_array_equal(np.asarray(state["hot"]), np.arange(n) < h)
        assert int(state["count"]) == len(plfu._freq)
        assert jax_cache.eviction_count(spec, hits, trace, state) == pol.evictions
        assert int(jax_cache.metadata_entries(spec, state)) == pol.metadata_entries


def _scan_rows(jaxpr):
    """Shapes of the rank-2 carries of every scan in a jaxpr, sub-jaxprs
    (jit, vmap, nested scans) included."""
    rows = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            k, c = eqn.params["num_consts"], eqn.params["num_carry"]
            rows |= {v.aval.shape for v in eqn.invars[k:k + c] if v.aval.ndim == 2}
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    rows |= _scan_rows(sub)
    return rows


@pytest.mark.parametrize(
    "kind,hot,compact",
    [("plfua", 8, True), ("plfua", 0, True), ("plfua", 64, False),
     ("plfu", 0, False), ("plfua_dyn", 8, False)],
)
def test_plfua_prefix_scan_engages(kind, hot, compact):
    n, cap, S = 64, 3, 12
    spec = jax_cache.PolicySpec(kind, n_objects=n, capacity=cap, hot_size=hot)
    traces = jnp.zeros((S, 50), jnp.int32)
    rows = _scan_rows(jax.make_jaxpr(
        lambda tr: jax_cache.simulate_batch(spec, tr))(traces).jaxpr)
    h1 = spec.effective_hot + 1
    assert rows, "no rank-2 scan carry found"
    if compact:
        assert rows == {(S, h1)}
    else:
        assert (S, n) in rows and (S, h1) not in rows


# Lane access (`jax_cache._lane` / `_set_lane`): rows of at most
# `_ONE_HOT_LANES` lanes are read and written by a one-hot mask, longer ones
# by index. Every kind, in byte mode too where it has one, runs a Zipf trace
# on a row at the limit and on one lane past it, in the form its length
# picks and, with the limit moved, in the other: both must agree field for
# field with each other and hit for hit with the plain reference.
LANE_CASES = [(kind, False) for kind in jax_cache.JAX_POLICY_KINDS] + [
    (kind, True) for kind in jax_cache.JAX_POLICY_KINDS if kind != "arc"
]


def _lane_run(spec, trace, sizes):
    """One run traced afresh (the lane form is chosen at trace time).
    Static plfua scans its whole row: `simulate` would scan the admissible
    prefix alone."""
    jax.clear_caches()
    if spec.kind == "plfua":
        return jax.jit(functools.partial(_dense_simulate, spec))(trace, sizes)
    return jax_cache.simulate(spec, trace, None, sizes)


@pytest.mark.parametrize("past", [False, True], ids=["at_limit", "past_limit"])
@pytest.mark.parametrize("kind,sized", LANE_CASES)
def test_lane_forms_agree_with_reference(kind, sized, past, monkeypatch):
    limit = jax_cache._ONE_HOT_LANES
    n = limit + 1 if past else limit
    spec = jax_cache.PolicySpec(
        kind, n_objects=n, capacity=24, hot_size=48,
        window=48 if kind in ("wlfu", "tinylfu") else 0,
        refresh=97 if kind == "plfua_dyn" else 0,
        sketch_width=64 if kind in jax_cache.SKETCH_POLICY_KINDS else 0,
        capacity_bytes=200 if sized else 0,
    )
    sizes = (
        workloads.object_sizes(n, corr=0.5, seed=3, median=8, max_size=64)
        if sized or kind == "gdsf" else None
    )
    trace = zipf.sample_trace(n, 400, seed=13)
    hits, state = _lane_run(spec, trace, sizes)
    monkeypatch.setattr(jax_cache, "_ONE_HOT_LANES", n if past else n - 1)
    other_hits, other_state = _lane_run(spec, trace, sizes)
    np.testing.assert_array_equal(np.asarray(hits), np.asarray(other_hits))
    assert sorted(state) == sorted(other_state)
    for key in state:
        np.testing.assert_array_equal(
            np.asarray(state[key]), np.asarray(other_state[key]), err_msg=key
        )

    pol = build_policy(spec, sizes)
    ref_hits = np.array([pol.request(int(x)) for x in trace])
    hits = np.asarray(hits)
    np.testing.assert_array_equal(hits, ref_hits)
    cached = np.asarray(state["in_cache"])
    np.testing.assert_array_equal(
        np.flatnonzero(cached), [x for x in np.unique(trace) if pol.contains(int(x))]
    )
    assert int(state["count"]) == int(cached.sum())
    assert jax_cache.eviction_count(spec, hits, trace, state) == pol.evictions
    assert int(jax_cache.metadata_entries(spec, state)) == pol.metadata_entries


def test_fast_path_lanes_at_cell_shape_match_simulate():
    """The flat stream's compact fast path at the shape of the paper's
    largest grid case (N 100,000, C 2,000, hot set 4,000, chunks of 2,048:
    rows of 6,096 lanes, the one-hot form) against `simulate` over the
    concatenated chunks."""
    n, cap, hot, g, chunks = 100_000, 2_000, 4_000, 2_048, 3
    spec = jax_cache.PolicySpec("plfua", n_objects=n, capacity=cap, hot_size=hot)
    topo = fleet.tree(n_objects=n, widths=(1,), kinds="plfua", capacities=cap,
                      hot_size=(hot,))
    fs = fleet.FleetStream(fleet.StreamConfig(topo=topo, chunk_len=g, fast=True))
    assert fs._lanes == 6_096 <= jax_cache._ONE_HOT_LANES
    trace = zipf.sample_trace(n, chunks * g, seed=21).astype(np.int32)
    hits = [np.asarray(fs.push(jnp.asarray(trace[c * g:(c + 1) * g]))["hit"][0])
            for c in range(chunks)]
    want_hits, want_state = jax_cache.simulate(spec, trace)
    np.testing.assert_array_equal(np.concatenate(hits), np.asarray(want_hits))
    state = fs.states()[0]
    assert sorted(state) == sorted(want_state)
    for key in state:
        np.testing.assert_array_equal(
            np.asarray(state[key]), np.asarray(want_state[key]), err_msg=key
        )
