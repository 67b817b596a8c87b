"""``sim.masked_scan``'s compacted step rule against the dense masked scan.

Each node steps only its own requests (the active positions, packed in
stream order) in a loop to its own load, and under a level's ``vmap`` to
the busiest node's load. That must equal a plain ``lax.scan`` over every
position that freezes the state where the node is inactive, in final state
and in hit bits, for every load: none, all, and uneven loads across the
vmapped nodes of a level."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import jax_cache
from repro.core.jax_cache import PolicySpec
from repro.fleet import sim

N, C, T, K = 60, 7, 96, 4

_rng = np.random.default_rng(7)
SIZES = jnp.asarray(_rng.integers(1, 6, size=N), jnp.int32)
TRACE = jnp.asarray(
    np.minimum(_rng.zipf(1.3, size=T) - 1, N - 1), jnp.int32
)

#: the kinds whose levels take the compacted scan, and one byte-mode case
_SPECS = {
    "lru": PolicySpec("lru", N, C),
    "lfu": PolicySpec("lfu", N, C),
    "plfu": PolicySpec("plfu", N, C),
    "plfua": PolicySpec("plfua", N, C, hot_size=2 * C),
    "lru_bytes": PolicySpec("lru", N, C, capacity_bytes=3 * C, max_victims=4),
}

#: (K, T) activity masks: no request, every position, and uneven loads
#: (an idle node, a sparse one, a dense one, one active only at the end)
_LOADS = {
    "none": np.zeros((K, T), bool),
    "all": np.ones((K, T), bool),
    "uneven": np.stack([
        np.zeros(T, bool),
        _rng.random(T) < 0.1,
        _rng.random(T) < 0.8,
        np.arange(T) >= T - 5,
    ]),
}


def _dense_scan(spec, state, trace, active, cap, sizes=None, cap_bytes=None):
    """Every position stepped, the state frozen where the node is inactive."""

    def f(s, inp):
        x, a = inp
        ns, hit = jax_cache.step(spec, s, x, cap, sizes=sizes, cap_bytes=cap_bytes)
        ns = jax.tree_util.tree_map(lambda o, n: jnp.where(a, n, o), s, ns)
        return ns, hit & a

    return jax.lax.scan(f, state, (trace, active))


def _level(scan, spec, active):
    """One level of K nodes of ``spec`` (capacities differing per node) as
    the fleet engines run it: the scan vmapped over the nodes."""
    states = sim.stack_level_state((spec,) * K)
    caps = jnp.asarray([C, C - 1, C - 2, C - 3], jnp.int32)
    if spec.capacity_bytes:
        caps_b = caps * 3
        return jax.jit(jax.vmap(
            lambda st, a, cap, capb: scan(
                spec, st, TRACE, a, cap, sizes=SIZES, cap_bytes=capb
            )
        ))(states, active, caps, caps_b)
    return jax.jit(jax.vmap(
        lambda st, a, cap: scan(spec, st, TRACE, a, cap)
    ))(states, active, caps)


@pytest.mark.parametrize("load", sorted(_LOADS))
@pytest.mark.parametrize("kind", sorted(_SPECS))
def test_compacted_scan_matches_the_dense_scan(kind, load):
    spec = _SPECS[kind]
    assert sim.compacts(spec)
    active = jnp.asarray(_LOADS[load])
    want_states, want_hits = _level(_dense_scan, spec, active)
    got_states, got_hits = _level(sim.masked_scan, spec, active)
    np.testing.assert_array_equal(np.asarray(got_hits), np.asarray(want_hits))
    for k in want_states:
        np.testing.assert_array_equal(
            np.asarray(got_states[k]), np.asarray(want_states[k]), err_msg=k
        )
    if load != "none":
        assert np.asarray(got_hits).any()  # the trace repeats ids: some hit


def test_compacted_scan_outside_a_vmap():
    """One node on its own: the loop runs to the node's own load."""
    spec = _SPECS["plfua"]
    active = jnp.asarray(_LOADS["uneven"][1])
    state = jax_cache.init_state(spec)
    want = jax.jit(lambda s: _dense_scan(spec, s, TRACE, active, None))(state)
    got = jax.jit(lambda s: sim.masked_scan(spec, s, TRACE, active))(state)
    for a, b in zip(jax.tree_util.tree_leaves(want), jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dense_levels_keep_the_dense_scan():
    """plfua_dyn's global-time refresh and the telemetry twin's per-position
    series need every position: those levels are not compacted."""
    dyn = PolicySpec("plfua_dyn", N, C, hot_size=2 * C, refresh=16)
    assert not sim.compacts(dyn)
    assert not sim.compacts(_SPECS["lru"], instrument=True)
