"""Multi-device fleet execution: shard_map over edges or over trace samples.

Two complementary shardings of :func:`repro.fleet.sim.simulate_fleet`:

* **Edge-sharded** (:func:`simulate_fleet_sharded`) — the edge fleet's
  vmapped scan splits across a 1-axis device mesh; each device runs its
  local slice of edges over the (replicated) trace, then a single ``psum``
  collective rebuilds the *global* served mask so the upper tiers — small,
  replicated on every device — consume exactly the fleet-wide miss stream.
  Decision-identical to the single-device path (tests run it under forced
  host devices).

* **Sample-sharded** (:func:`simulate_fleet_device`) — weak scaling: the
  sample axis splits across the mesh and every shard *synthesizes its own
  trace chunk on device* (``repro.workloads.device``), routes it with the
  jnp router, and simulates its full topology replica, all inside one jit.
  No host trace arrays are ever shipped; each sample's stream is a pure
  function of (seed, global sample index), so placement doesn't change
  results.

Both fall back to the plain vmapped simulator when no usable mesh is given
(``mesh=None`` or a single device) — the documented single-device path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.cdn.router import route_device
from repro.fleet import sim as sim_mod
from repro.fleet.topology import Topology
from repro.workloads.device import DeviceTraceSpec, gen_sample, sample_key

__all__ = [
    "fleet_mesh",
    "mesh_size",
    "simulate_fleet_sharded",
    "simulate_fleet_device",
]

AXIS = "shards"


def fleet_mesh(devices=None, axis: str = AXIS) -> Mesh:
    """1-axis mesh over the given (default: all) local devices."""
    devices = jax.devices() if devices is None else list(devices)
    return Mesh(np.array(devices), (axis,))


def mesh_size(mesh: Mesh | None) -> int:
    return 1 if mesh is None else int(np.prod(list(mesh.shape.values())))


# ------------------------------------------------------------- edge-sharded
@functools.lru_cache(maxsize=None)
def _edge_sharded_fn(topo: Topology, mesh: Mesh):
    axis = mesh.axis_names[0]
    D = mesh.shape[axis]
    specs0 = topo.levels[0]
    E = len(specs0)
    if E % D:
        raise ValueError(
            f"edge count {E} must divide over the {D}-device mesh"
        )
    s0 = specs0[0]

    def edge_shard(states, active, caps, trace):
        # local slice of the edge fleet: E/D masked scans on this device
        states, hits = jax.vmap(
            lambda st, act, cap: sim_mod.masked_scan(s0, st, trace, act, cap)
        )(states, active, caps)
        # cross-tier miss aggregation: one collective rebuilds the global
        # served mask (exactly one edge is active per t, so sum == any)
        served = jax.lax.psum(hits.any(axis=0).astype(jnp.int32), axis) > 0
        return states, hits, served

    sharded = jax.shard_map(
        edge_shard,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P()),
        out_specs=(P(axis), P(axis), P()),
    )

    @jax.jit
    def run(trace, assignment):
        trace = trace.astype(jnp.int32)
        assignment = assignment.astype(jnp.int32)
        assigns = sim_mod.level_assignments(topo, trace, assignment)
        active0 = assigns[0][None, :] == jnp.arange(E, dtype=jnp.int32)[:, None]
        states0 = sim_mod.stack_level_state(specs0)
        caps0 = jnp.array([s.capacity for s in specs0], jnp.int32)
        edge_states, edge_hits, edge_hit = sharded(states0, active0, caps0, trace)
        demand = ~edge_hit
        hits_up, counters_up, states_up, demand = sim_mod.upper_levels(
            topo, trace, assigns, demand
        )
        all_hits = [edge_hits, *hits_up]
        return {
            "hit": tuple(h.any(axis=0) for h in all_hits),
            "node_hit": tuple(all_hits),
            "tiers": (
                sim_mod.tier_counters(s0, edge_hits, active0, trace, edge_states),
                *counters_up,
            ),
            "states": (edge_states, *states_up),
            "origin_miss": demand,
        }

    return run


# ------------------------------------------- edge-sharded, placed topologies
@functools.lru_cache(maxsize=None)
def _edge_sharded_placed_fn(topo: Topology, mesh: Mesh):
    """Edge-sharded execution of a placement-enabled topology.

    Cross-tier placement couples the levels at every trace position, so the
    whole time-major scan (``sim._placed_run``) moves *inside* the shard_map
    body: each device carries its contiguous slice of the edge fleet plus a
    replica of the upper tiers, and one ``psum`` per step rebuilds the
    global edge-served bit (exactly one device owns the assigned edge).
    Upper-tier updates are pure functions of replicated inputs, so every
    device computes them identically — bit-parity with the single-device
    placed engine is asserted in tests/test_placement.py."""
    axis = mesh.axis_names[0]
    D = mesh.shape[axis]
    specs0 = topo.levels[0]
    E = len(specs0)
    if E % D:
        raise ValueError(f"edge count {E} must divide over the {D}-device mesh")
    L = topo.n_levels

    def body(states0, caps0, trace, assigns):
        states, pstates, fills, admitted, hit_lv = sim_mod._placed_run(
            topo,
            trace,
            list(assigns),
            level0_states=states0,
            level0_caps=caps0,
            edge_axis=axis,
        )
        return (
            tuple(states),
            pstates,
            tuple(fills),
            tuple(admitted),
            tuple(hit_lv),
        )

    edge_or_rep = lambda l: P(axis) if l == 0 else P()
    parsed_admit = [
        l for l, p in enumerate(topo.placements) if p == "admit"
    ]
    out_specs = (
        tuple(edge_or_rep(l) for l in range(L)),
        {l: edge_or_rep(l) for l in parsed_admit},
        tuple(edge_or_rep(l) for l in range(L)),
        tuple(edge_or_rep(l) for l in range(L)),
        tuple(P() for _ in range(L)),
    )
    sharded = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(), P()),
        out_specs=out_specs,
        check_vma=False,  # upper tiers are replicated by construction (the
        # per-step psum), which the varying-axes check cannot see through
        # the scan
    )

    @jax.jit
    def run(trace, assignment):
        trace = trace.astype(jnp.int32)
        assignment = assignment.astype(jnp.int32)
        assigns = sim_mod.level_assignments(topo, trace, assignment)
        states0 = sim_mod.stack_level_state(specs0)
        caps0 = jnp.array([s.capacity for s in specs0], jnp.int32)
        states, pstates, fills, admitted, hit_lv = sharded(
            states0, caps0, trace, tuple(assigns)
        )
        return sim_mod.assemble_placed(
            topo, assigns, list(states), pstates, list(fills),
            list(admitted), list(hit_lv),
        )

    return run


def simulate_fleet_sharded(
    topo: Topology, trace: jax.Array, assignment: jax.Array, mesh: Mesh | None = None
):
    """Edge-sharded fleet run; same result pytree as ``simulate_fleet``.

    Falls back to the single-device vmap path when ``mesh`` is absent or has
    one device (the documented single-device fallback). Placement-enabled
    topologies run the time-major scan inside the mesh (see
    ``_edge_sharded_placed_fn``); sample-sharded execution
    (``simulate_fleet_device``) honours placement automatically — every
    sample replica dispatches through ``sim._simulate_fleet_impl``."""
    if mesh_size(mesh) == 1:
        return sim_mod.simulate_fleet(topo, trace, assignment)
    if topo.has_placement:
        return _edge_sharded_placed_fn(topo, mesh)(trace, assignment)
    return _edge_sharded_fn(topo, mesh)(trace, assignment)


# ----------------------------------------------------------- sample-sharded
def _per_sample_fn(topo: Topology, dspec: DeviceTraceSpec, route_seed: int):
    def per_sample(sid):
        trace = gen_sample(dspec, sample_key(dspec, sid))
        assignment = route_device(
            trace, topo.n_edges, topo.router,
            session_len=topo.session_len, seed=route_seed,
        )
        out = sim_mod._simulate_fleet_impl(topo, trace, assignment)
        return out, trace, assignment

    return per_sample


@functools.lru_cache(maxsize=None)
def _device_fleet_fn(
    topo: Topology, dspec: DeviceTraceSpec, route_seed: int, mesh: Mesh | None
):
    per_sample = _per_sample_fn(topo, dspec, route_seed)
    S = dspec.n_samples
    if mesh_size(mesh) == 1:

        @jax.jit
        def run():
            return jax.vmap(per_sample)(jnp.arange(S, dtype=jnp.int32))

        return run

    axis = mesh.axis_names[0]
    D = mesh.shape[axis]
    if S % D:
        raise ValueError(
            f"n_samples {S} must divide over the {D}-device mesh"
        )

    # each shard receives its own chunk of global sample ids and synthesizes
    # + simulates those traces entirely on its device
    sharded = jax.shard_map(
        lambda ids: jax.vmap(per_sample)(ids),
        mesh=mesh,
        in_specs=(P(axis),),
        out_specs=P(axis),
        # shards are independent replicas with no collective, so there is
        # nothing for the varying-axes check to verify; left on, it would
        # demand a pcast on every engine scan's constant initial carry
        check_vma=False,
    )

    @jax.jit
    def run():
        return sharded(jnp.arange(S, dtype=jnp.int32))

    return run


def simulate_fleet_device(
    topo: Topology,
    dspec: DeviceTraceSpec,
    *,
    mesh: Mesh | None = None,
    route_seed: int = 0,
):
    """On-device trace generation + simulation, optionally sample-sharded.

    Returns ``(result, traces, assignments)`` where ``result`` is the batched
    ``simulate_fleet`` pytree (leading sample axis) and ``traces`` /
    ``assignments`` are the device-generated (S, T) arrays — returned so
    parity tests can replay the exact streams through the reference oracle.
    """
    return _device_fleet_fn(topo, dspec, route_seed, mesh)()
