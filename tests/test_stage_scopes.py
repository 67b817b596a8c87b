"""Profile names of the engines: the ``repro.<stage>`` scopes the lowered
programs carry, the module names of the stream's programs, the host spans of
``FleetStream.push``, and the fast path's compact-lane counter.

The scopes must change nothing but operation metadata: each program's
optimized HLO is compared with a twin built with ``jax.named_scope`` patched
to a null context, metadata and stack-frame tables stripped."""
import contextlib
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import fleet, workloads
from repro.core import jax_cache
from repro.core.jax_cache import PolicySpec
from repro.fleet.stream import FleetStream, StreamConfig

N, C, G = 400, 40, 16


def _fast(kind="plfua_dyn"):
    kw = {"hot_size": (2 * C,)}
    if kind == "plfua_dyn":
        kw["refresh"] = (2 * G,)
    topo = fleet.tree(n_objects=N, widths=(1,), kinds=kind, capacities=C, **kw)
    return FleetStream(StreamConfig(topo=topo, chunk_len=G, fast=True))


def _fast_arc():
    topo = fleet.tree(n_objects=N, widths=(1,), kinds="arc", capacities=C)
    return FleetStream(StreamConfig(topo=topo, chunk_len=G, fast=True))


def test_arc_fast_chunk_carries_list_scopes():
    """ARC's fast chunk steps its directory in one-hot lanes, its list sizes
    and adaptation under ``repro.lists``; plfua's has no ``repro.lists``."""
    fs = _fast_arc()
    text = fs._push_fn.lower(fs._carry, _CHUNK).as_text(debug_info=True)
    found = set(re.findall(r"\brepro\.([A-Za-z]\w*)", text))
    assert {"lists", "onehot", "victim", "step", "lanes", "scatter", "roster"} <= found
    assert "select" not in found  # the whole directory is the candidate set
    plfua = _fast("plfua")
    assert "repro.lists" not in plfua._push_fn.lower(plfua._carry, _CHUNK).as_text(
        debug_info=True
    )


def _level_major():
    topo = fleet.tree(n_objects=N, widths=(2, 1), kinds=("lru", "plfua_dyn"),
                      capacities=(C, 2 * C), refresh=(0, 2 * G), router="hash")
    return FleetStream(StreamConfig(topo=topo, chunk_len=G))


def _placed():
    topo = fleet.tree(n_objects=N, widths=(2, 1), kinds=("lru", "plfu"),
                      capacities=(C, 2 * C), placements=("lce", "lcd"), router="hash")
    return FleetStream(StreamConfig(topo=topo, chunk_len=G))


_CHUNK = jax.ShapeDtypeStruct((G,), jnp.int32)
_BATCH = jax.ShapeDtypeStruct((3, 64), jnp.int32)
_PLFUA = PolicySpec("plfua", N, C, hot_size=2 * C)


def _lowered(program):
    """The lowered program of ``program``, built afresh (so that a patched
    ``jax.named_scope`` takes effect)."""
    jax.clear_caches()
    if program == "simulate_batch":
        return jax_cache.simulate_batch.lower(_PLFUA, _BATCH)
    fs = {"fast_chunk": _fast, "level_major_chunk": _level_major,
          "placed_chunk": _placed}[program]()
    if program == "fast_chunk":
        return fs._push_fn.lower(fs._carry, _CHUNK)
    return fs._push_fn.lower(fs._carry, _CHUNK, _CHUNK)


_SCOPES = {
    "fast_chunk": ("step", "victim", "select", "lanes", "scatter", "roster", "refresh"),
    "level_major_chunk": ("step", "victim", "refresh", "level0", "level1", "route"),
    "placed_chunk": ("step", "victim", "probe", "level0", "level1", "route"),
    "simulate_batch": ("step", "victim", "prefix"),
}


@pytest.mark.parametrize("program", sorted(_SCOPES))
def test_lowered_programs_carry_stage_scopes(program):
    lowered = _lowered(program)
    found = set(re.findall(r"\brepro\.([A-Za-z]\w*)", lowered.as_text(debug_info=True)))
    assert set(_SCOPES[program]) <= found, sorted(found)
    # in the compiled program's operation names (what a profile shows) the
    # victim search lies inside the per-request scan
    assert re.search(r'op_name="[^"]*repro\.step[^"]*/repro\.victim', lowered.compile().as_text())


def _program_text(compiled_text: str) -> list:
    """Optimized HLO without operation metadata and stack-frame tables, its
    instructions and computations renamed in the order they are defined
    (the name uniquer may number them differently)."""
    text = re.sub(r",? metadata=\{[^{}]*\}", "", compiled_text)
    table = re.compile(r"(FileNames|FunctionNames|FileLocations|StackFrames)$|\d+ ")
    lines = [line for line in text.splitlines() if not table.match(line)]
    names: dict = {}
    for line in lines:
        m = re.match(r"\s*(?:ROOT |ENTRY )?%([\w.\-]+) [=(]", line)
        if m:
            names.setdefault(m.group(1), f"%v{len(names)}")
    return [re.sub(r"%([\w.\-]+)", lambda m: names.get(m.group(1), m.group(0)), line)
            for line in lines]


@pytest.mark.parametrize("program", ["fast_chunk", "level_major_chunk", "simulate_batch"])
def test_scopes_leave_the_optimized_program_unchanged(program, monkeypatch):
    scoped = _lowered(program).compile().as_text()
    assert "repro.step" in scoped
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = _lowered(program).compile().as_text()
    assert "repro." not in plain
    assert _program_text(scoped) == _program_text(plain)


def test_stream_programs_have_distinct_module_names():
    assert _lowered("fast_chunk").as_text().startswith("module @jit_fast_chunk")
    assert _lowered("level_major_chunk").as_text().startswith("module @jit_level_major_chunk")
    assert _lowered("placed_chunk").as_text().startswith("module @jit_placed_chunk")
    fs = _level_major()
    route = fs._route.lower(_CHUNK, jax.ShapeDtypeStruct((), jnp.int32))
    assert route.as_text().startswith("module @jit_route_chunk")
    assert "repro.route" in route.as_text(debug_info=True)


def _valid_lanes(kind, residents, key, xs, P):
    """Distinct real ids among the P lexicographically smallest (key, id)
    residents and the chunk's ids: the fast path's valid lanes."""
    cand = sorted(residents, key=lambda r: (key[r], r))[:P]
    return len(set(cand) | set(xs.tolist()))


@pytest.mark.parametrize("kind", ["plfua", "lru"])
def test_lanes_valid_matches_a_numpy_recount(kind):
    fs = _fast(kind)
    P = min(2 * G, C + G)  # C > 2G here, so the prefix cuts the residents
    assert C > 2 * G
    trace = workloads.make_traces("stationary", N, 1, 12 * G, seed=3)[0].astype(np.int32)
    want = 0
    for c in range(12):
        state = fs.states()[0]
        residents = np.flatnonzero(np.asarray(state["in_cache"]))
        key = np.asarray(state["last"] if kind == "lru" else state["freq"])
        xs = trace[c * G:(c + 1) * G]
        want += _valid_lanes(kind, residents, key, xs, P)
        fs.push(jnp.asarray(xs))
    st = fs.stats()
    assert st.lanes == 12 * (P + G)
    assert st.lanes_valid == want
    assert 0 < st.lanes_valid < st.lanes
    assert st.node_steps == st.lanes  # every compact lane is stepped


def test_lanes_are_none_off_the_fast_path():
    """Off the fast path the engines count node-steps: every node of the
    level-major tree steps every position, the placed engine one node a
    level; the valid ones are the active requests, the tiers' ``requests``."""
    for fs, stepped in ((_level_major(), 3), (_placed(), 2)):
        fs.push(jnp.arange(G, dtype=jnp.int32))
        fs.push(jnp.arange(G, dtype=jnp.int32))
        st = fs.stats()
        assert st.lanes == 2 * G * stepped
        # a cold pass of G distinct ids misses its edge and reaches the root;
        # the second pass hits its edge (C > G)
        assert st.lanes_valid == sum(int(np.asarray(t["requests"]).sum()) for t in st.tiers)
        assert st.lanes_valid == 3 * G
        # the placed engine steps its whole grid; the level-major tree's
        # plfua_dyn root keeps the dense scan, its lru edges are compacted
        assert st.lanes_valid <= st.node_steps <= st.lanes
        if stepped == 2:
            assert st.node_steps == st.lanes



# Lane access by shape (`jax_cache._ONE_HOT_LANES`): a step on short rows
# reads and writes a lane by a one-hot mask, under `repro.onehot`, and its
# loop body addresses no row by a computed index; a step on long rows keeps
# the index form and no `repro.onehot`.
_INDEXED = {"gather", "scatter", "scatter-add", "dynamic_slice", "dynamic_update_slice"}


def _primitives(jaxpr) -> set:
    """Names of the primitives of a jaxpr, sub-jaxprs included."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    names |= _primitives(sub)
    return names


def _step_loop_bodies(jaxpr) -> list:
    """The bodies of the per-request scans: those under `repro.step`."""
    bodies = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan" and "repro.step" in str(eqn.source_info.name_stack):
            bodies.append(eqn.params["jaxpr"].jaxpr)
            continue
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    bodies += _step_loop_bodies(sub)
    return bodies


def _cell_program(name):
    """(program, abstract args) of the two plfua cells at their shapes: the
    flat stream's fast chunk (rows of 6,096 compact lanes) and 12
    replications (rows of the 4,001-lane admissible prefix)."""
    n, cap, hot, g = 100_000, 2_000, 4_000, 2_048
    if name == "replications":
        spec = PolicySpec("plfua", n, cap, hot_size=hot)
        return (functools.partial(jax_cache.simulate_batch, spec),
                (jax.ShapeDtypeStruct((12, n), jnp.int32),))
    topo = fleet.tree(n_objects=n, widths=(1,), kinds="plfua", capacities=cap,
                      hot_size=(hot,))
    fs = FleetStream(StreamConfig(topo=topo, chunk_len=g, fast=True))
    return fs._push_fn, (fs._carry, jax.ShapeDtypeStruct((g,), jnp.int32))


@pytest.mark.parametrize("name", ["stream", "replications"])
def test_cells_step_loops_use_one_hot_lanes(name):
    fn, args = _cell_program(name)
    assert "repro.onehot" in jax.jit(fn).lower(*args).as_text(debug_info=True)
    bodies = _step_loop_bodies(jax.make_jaxpr(fn)(*args).jaxpr)
    assert len(bodies) == 1
    assert not _primitives(bodies[0]) & _INDEXED, sorted(_primitives(bodies[0]) & _INDEXED)


_SHORT_KINDS = [(k, False) for k in ("lru", "lfu", "plfu", "plfua", "wlfu", "gdsf", "arc")] + [
    (k, True) for k in ("lru", "lfu", "plfu", "plfua", "wlfu", "gdsf")
]


@pytest.mark.parametrize("batch", [None, 12])
@pytest.mark.parametrize("kind,sized", _SHORT_KINDS)
def test_short_row_step_has_no_indexed_access(kind, sized, batch):
    """Every kind without a sketch table (tinylfu and plfua_dyn gather their
    per-object hash rows, which this does not change), in byte mode too."""
    spec = PolicySpec(kind, N, C, hot_size=2 * C, window=8 if kind == "wlfu" else 0,
                      capacity_bytes=4 * C if sized else 0)
    assert N <= jax_cache._ONE_HOT_LANES
    sizes = jnp.ones((N,), jnp.int32) if spec.size_aware else None

    def one(s, x):
        return jax_cache.step(spec, s, x, sizes=sizes)

    fn = one if batch is None else jax.vmap(one)
    state = jax_cache.init_state(spec)
    x = jnp.int32(3)
    if batch is not None:
        state = jax.tree_util.tree_map(lambda a: jnp.broadcast_to(a, (batch,) + a.shape), state)
        x = jnp.full((batch,), 3, jnp.int32)
    assert not _primitives(jax.make_jaxpr(fn)(state, x).jaxpr) & _INDEXED
    assert "repro.onehot" in jax.jit(fn).lower(state, x).as_text(debug_info=True)


def test_long_rows_keep_the_index_form():
    """A step on 100,000-lane rows, alone and as the photo-CDN fleet's
    level-major chunk program (8 plfua edges over 4 plfu origin nodes)."""
    spec = PolicySpec("plfua", 100_000, 2_000, hot_size=4_000)
    one = functools.partial(jax_cache.step, spec)
    state, x = jax_cache.init_state(spec), jnp.int32(3)
    assert "repro.onehot" not in jax.jit(one).lower(state, x).as_text(debug_info=True)
    assert _primitives(jax.make_jaxpr(one)(state, x).jaxpr) & _INDEXED
    topo = fleet.tree(n_objects=100_000, widths=(8, 4), kinds=("plfua", "plfu"),
                      capacities=(2_000, 5_000), hot_size=(4_000, 0),
                      routers=("sticky", "hash"), session_len=64)
    fs = FleetStream(StreamConfig(topo=topo, chunk_len=2_048))
    chunk = jax.ShapeDtypeStruct((2_048,), jnp.int32)
    lowered = fs._push_fn.lower(fs._carry, chunk, chunk)
    assert "repro.onehot" not in lowered.as_text(debug_info=True)
