"""Fleet subsystem: declarative N-tier cache topologies, a jitted
multi-device simulator, a pure-Python reference oracle, and per-tier
report roll-ups.

    from repro import fleet, workloads
    topo = fleet.tree(n_objects=10_000, widths=(8, 2, 1),
                      kinds=("lru", "plfu", "plfu"),
                      capacities=(60, 240, 960))
    traces = workloads.make_traces("churn", 10_000, n_samples=4,
                                   trace_len=20_000)
    assign = topo.assignment(traces)
    out = fleet.simulate_fleet_batch(topo, traces, assign)
    print(fleet.fleet_report(topo, out).rows())

Cross-tier placement (``placements=`` per level: ``lce`` / ``lcd`` /
``prob(p)`` / ``admit``, see :mod:`repro.fleet.placement`) decides which
tiers store a copy on the fill path, and ``routers=`` picks a router kind
per level (sticky edges over hashed regionals, or the ``"tree"`` parent
map). Multi-device: ``fleet.simulate_fleet_sharded`` splits the edge tier
over a mesh (collective miss aggregation); ``fleet.simulate_fleet_device``
shards the sample axis with on-device trace generation (weak scaling) —
both honour placement. The legacy two-tier API in :mod:`repro.cdn` is a
thin wrapper over depth-2 topologies. For unbounded request streams (line
rate, not one bounded trace per call) see :mod:`repro.fleet.stream`:
``FleetStream`` / ``stream_fleet`` push fixed-shape chunks of the same
chunk program a bounded ``simulate_fleet`` applies once, through a donated
carry, bit-identical to ``simulate_fleet`` on the concatenated trace.
"""
from repro.fleet import placement
from repro.fleet.topology import (
    Topology,
    from_hierarchy,
    level_assignments,
    tree,
)
from repro.fleet.sim import (
    masked_scan,
    simulate_fleet,
    simulate_fleet_batch,
)
from repro.fleet.reference import (
    FleetReferenceResult,
    build_policy,
    simulate_fleet_reference,
)
from repro.fleet.report import (
    TENANT_ROW_FIELDS,
    TIER_ROW_FIELDS,
    FleetReport,
    TierReport,
    fleet_report,
    mgmt_ops,
    placement_ops,
)
from repro.fleet.shard import (
    fleet_mesh,
    mesh_size,
    simulate_fleet_device,
    simulate_fleet_sharded,
)
from repro.fleet.stream import (
    FAST_KINDS,
    FleetStream,
    StreamConfig,
    StreamStats,
    stream_fleet,
)

__all__ = [
    "Topology",
    "tree",
    "from_hierarchy",
    "placement",
    "placement_ops",
    "level_assignments",
    "simulate_fleet",
    "simulate_fleet_batch",
    "simulate_fleet_sharded",
    "simulate_fleet_device",
    "simulate_fleet_reference",
    "FleetReferenceResult",
    "build_policy",
    "FleetReport",
    "TierReport",
    "TIER_ROW_FIELDS",
    "TENANT_ROW_FIELDS",
    "fleet_report",
    "mgmt_ops",
    "masked_scan",
    "fleet_mesh",
    "mesh_size",
    "FAST_KINDS",
    "FleetStream",
    "StreamConfig",
    "StreamStats",
    "stream_fleet",
]
