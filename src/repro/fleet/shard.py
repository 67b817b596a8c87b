"""Multi-device fleet execution: shard_map over edges or over trace samples.

Two complementary shardings of :func:`repro.fleet.sim.simulate_fleet`:

* **Edge-sharded** (:func:`simulate_fleet_sharded`) — the engine's chunk
  program (:mod:`repro.fleet.sim`, the one a bounded run applies once and
  ``FleetStream`` pushes) runs inside a ``shard_map`` over a 1-axis device
  mesh. Each device holds its contiguous slice of the edge level and a
  replica of the upper levels; level 0 scans only the local edges over the
  (replicated) trace, then one ``psum`` rebuilds the *global* served mask —
  once for the whole chunk in the level-major engine, once a step in the
  time-major one — so the upper tiers consume exactly the fleet-wide miss
  stream. Decision-identical to the single-device path (tests run it under
  forced host devices).

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
    E = len(topo.levels[0])
    if E % D:
        raise ValueError(f"edge count {E} must divide over the {D}-device mesh")
    # level 0's carry and per-node series split over the edge axis,
    # everything else replicated
    lvl = tuple(P(axis) if l == 0 else P() for l in range(topo.n_levels))

    @jax.jit
    def run(trace, assignment):
        trace = trace.astype(jnp.int32)
        assignment = assignment.astype(jnp.int32)
        chunk, carry0 = sim_mod._chunk_program(topo, trace.shape[0], edge_axis=axis)
        carry_specs = {k: P() for k in carry0}
        carry_specs["acc"] = lvl
        if topo.has_placement:
            pstates = carry0["placed"][1]
            carry_specs["placed"] = (lvl, {l: lvl[l] for l in pstates}, lvl, lvl)
        else:
            carry_specs["states"] = lvl
        sharded = jax.shard_map(
            chunk,
            mesh=mesh,
            in_specs=(carry_specs, P(), P()),
            out_specs=(
                carry_specs,
                {"hit": (P(),) * len(lvl), "node_hit": lvl, "origin_miss": P()},
            ),
            # the upper levels are replicated by construction (the psum of
            # the edge-served mask), which the varying-axes check cannot see
            # through the scans; the carry's node_steps is per device and
            # left out of the result
            check_vma=False,
        )
        return sim_mod._bounded_result(topo, *sharded(carry0, trace, assignment))

    return run


def simulate_fleet_sharded(
    topo: Topology, trace: jax.Array, assignment: jax.Array, mesh: Mesh | None = None
):
    """Edge-sharded fleet run; same result pytree as ``simulate_fleet``.

    Falls back to the single-device vmap path when ``mesh`` is absent or has
    one device (the documented single-device fallback). Placement-enabled
    topologies run the time-major chunk program inside the mesh;
    sample-sharded execution (``simulate_fleet_device``) honours placement
    too — every sample replica is one bounded run."""
    if mesh_size(mesh) == 1:
        return sim_mod.simulate_fleet(topo, trace, assignment)
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
