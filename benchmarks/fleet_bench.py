"""Fleet benchmarks: N-tier depth sweeps, per-policy throughput, and
multi-device weak scaling.

Rows follow the repo convention ``name,us_per_call,derived``; us_per_call is
device wall-time per simulated request and derived carries steps/sec,
per-level CHR and the management-energy roll-up.

Groups:
  * ``fleet_policies``  — every registry policy kind on a 3-tier topology
    under stationary and churn: CHR + wall-clock + steps/sec (the perf-
    trajectory rows recorded into BENCH_PR3.json).
  * ``fleet_depth``     — 2/3/4-tier topologies over the same edge fleet:
    how depth buys origin-traffic reduction and what it costs to manage.
  * ``fleet_placement`` — cross-tier placement (lce / lcd / prob / admit,
    repro.fleet.placement) x {stationary, churn, flash_crowd}: per-level +
    total CHR, management energy with the distinct placement row, and
    steps/sec on the time-major placed engine. The acceptance row: ``lcd``
    cuts management energy vs ``lce`` on ``stationary`` at <= 2 points of
    total CHR (recorded into BENCH_PR5.json).
  * ``fleet_scale``     — weak scaling, edges x devices: every added device
    hosts a full topology replica serving its own on-device-generated
    traffic (``fleet.simulate_fleet_device`` sample-sharding). Runs in
    subprocesses so each device count gets a fresh
    ``--xla_force_host_platform_device_count`` backend.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.cdn_bench import policy_window  # one window convention
from repro import fleet, telemetry, workloads
from repro.core import registry

FLEET_POLICIES = registry.names(jax=True)


def _three_tier(kind: str, n: int, *, edge_cap: int, router: str = "hash"):
    """The benchmark topology: 8 edges -> 2 regionals -> 1 root."""
    return fleet.tree(
        n_objects=n,
        widths=(8, 2, 1),
        kinds=kind,
        capacities=(edge_cap, 4 * edge_cap, 8 * edge_cap),
        window=policy_window(kind),
        router=router,
    )


def _run(topo, traces):
    """Measured run on the telemetry.measure harness (warmup + full
    block_until_ready + compile/execute split); the extra call is jit-cached
    and only exists to hand the outputs to fleet_report."""
    assign = topo.assignment(traces)
    tr = telemetry.measure(
        fleet.simulate_fleet_batch, topo, traces, assign,
        static=(0,), steps=traces.size,
    )
    out = fleet.simulate_fleet_batch(topo, traces, assign)
    return out, tr.us_per_step, tr.steps_per_s


def fleet_policy_sweep(full: bool = False):
    """3-tier fleet, every policy x {stationary, churn}: CHR + steps/sec."""
    n, edge_cap = (10_000, 300) if full else (2_000, 60)
    samples, tlen = (8, 100_000) if full else (2, 10_000)
    rows = []
    for scenario in ("stationary", "churn"):
        traces = workloads.make_traces(
            scenario, n, n_samples=samples, trace_len=tlen, seed=0
        )
        for kind in FLEET_POLICIES:
            topo = _three_tier(kind, n, edge_cap=edge_cap)
            out, us, sps = _run(topo, traces)
            rep = fleet.fleet_report(topo, out)
            chrs = " ".join(
                f"{name}_chr={t.chr:.4f}"
                for name, t in zip(topo.names, rep.per_level)
            )
            rows.append(
                (
                    f"fleet/{scenario}/{kind}",
                    us,
                    f"steps_per_s={sps:.0f} {chrs} "
                    f"total_chr={rep.total_chr:.4f} origin={rep.origin_requests} "
                    f"mgmt_J={rep.mgmt_energy_j:.4f}",
                )
            )
    return rows


def fleet_depth_sweep(full: bool = False):
    """Same 8-edge fleet under 2/3/4-tier trees: depth vs origin traffic."""
    n, edge_cap = (10_000, 300) if full else (2_000, 60)
    samples, tlen = (8, 100_000) if full else (2, 10_000)
    traces = workloads.make_traces(
        "stationary", n, n_samples=samples, trace_len=tlen, seed=2
    )
    shapes = {
        2: ((8, 1), (edge_cap, 8 * edge_cap)),
        3: ((8, 2, 1), (edge_cap, 4 * edge_cap, 8 * edge_cap)),
        4: ((8, 4, 2, 1), (edge_cap, 2 * edge_cap, 4 * edge_cap, 8 * edge_cap)),
    }
    rows = []
    for depth, (widths, caps) in shapes.items():
        topo = fleet.tree(n_objects=n, widths=widths, kinds="plfu", capacities=caps)
        out, us, sps = _run(topo, traces)
        rep = fleet.fleet_report(topo, out)
        rows.append(
            (
                f"fleet_depth/T{depth}/plfu",
                us,
                f"steps_per_s={sps:.0f} edge_chr={rep.edge_chr:.4f} "
                f"total_chr={rep.total_chr:.4f} origin={rep.origin_requests} "
                f"mgmt_J={rep.mgmt_energy_j:.4f}",
            )
        )
    return rows


FLEET_PLACEMENTS = ("lce", "lcd", "prob(0.5)", "admit")
PLACEMENT_SCENARIOS = ("stationary", "churn", "flash_crowd")


def fleet_placement_sweep(full: bool = False):
    """3-tier plfu fleet, every placement x {stationary, churn, flash_crowd}.

    Derived fields carry the trade the placement subsystem exists to expose:
    per-level and total CHR, total management energy, the placement row's
    own share, and origin traffic. The final row per scenario asserts the
    acceptance property on stationary: lcd's management energy below lce's
    with total CHR within two points."""
    n, edge_cap = (10_000, 300) if full else (2_000, 60)
    samples, tlen = (4, 50_000) if full else (2, 8_000)
    rows = []
    reports: dict[tuple[str, str], object] = {}
    for scenario in PLACEMENT_SCENARIOS:
        traces = workloads.make_traces(
            scenario, n, n_samples=samples, trace_len=tlen, seed=7
        )
        for pl in FLEET_PLACEMENTS:
            topo = fleet.tree(
                n_objects=n,
                widths=(8, 2, 1),
                kinds="plfu",
                capacities=(edge_cap, 4 * edge_cap, 8 * edge_cap),
                placements=pl,
            )
            out, us, sps = _run(topo, traces)
            rep = fleet.fleet_report(topo, out)
            reports[(scenario, pl)] = rep
            chrs = " ".join(
                f"{name}_chr={t.chr:.4f}"
                for name, t in zip(topo.names, rep.per_level)
            )
            rows.append(
                (
                    f"fleet_placement/{scenario}/{pl}",
                    us,
                    f"steps_per_s={sps:.0f} {chrs} "
                    f"total_chr={rep.total_chr:.4f} origin={rep.origin_requests} "
                    f"mgmt_J={rep.mgmt_energy_j:.4f} "
                    f"placement_J={rep.placement_energy_j:.4f}",
                )
            )
    # the acceptance comparison, recorded as its own row so BENCH_PR5.json
    # carries the evidence (and a failed property shows up as /ERROR)
    lce, lcd = reports[("stationary", "lce")], reports[("stationary", "lcd")]
    saving = 1.0 - lcd.mgmt_energy_j / lce.mgmt_energy_j
    dchr = lcd.total_chr - lce.total_chr
    ok = lcd.mgmt_energy_j < lce.mgmt_energy_j and abs(dchr) <= 0.02
    rows.append(
        (
            "fleet_placement/stationary/lcd_vs_lce" + ("" if ok else "/ERROR"),
            0.0,
            f"mgmt_saving={saving:.4f} dchr={dchr:+.4f} "
            f"lce_J={lce.mgmt_energy_j:.4f} lcd_J={lcd.mgmt_energy_j:.4f}",
        )
    )
    return rows


# one weak-scaling worker: D forced host devices, D x samples_per_device
# topology replicas, traces synthesized on device (sample-sharded shard_map)
_SCALE_WORKER = r"""
import os, sys, time, json
# appended AFTER any inherited flags: XLA parses sequentially and the last
# occurrence wins, so the worker's forced device count always takes effect
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=%(devices)d"
)
sys.path.insert(0, %(src)r)
import jax
from repro import fleet
from repro.workloads.device import DeviceTraceSpec

D = %(devices)d
assert jax.device_count() == D, jax.device_count()
topo = fleet.tree(n_objects=%(n)d, widths=(%(edges)d, 1), kinds="plfu",
                  capacities=(%(edge_cap)d, %(root_cap)d))
dspec = DeviceTraceSpec("stationary", %(n)d, n_samples=%(spd)d * D,
                        trace_len=%(tlen)d, seed=0)
mesh = fleet.fleet_mesh() if D > 1 else None
out, traces, assigns = fleet.simulate_fleet_device(topo, dspec, mesh=mesh)
out["hit"][0].block_until_ready()  # compile + warm
t0 = time.perf_counter()
out, traces, assigns = fleet.simulate_fleet_device(topo, dspec, mesh=mesh)
out["hit"][0].block_until_ready()
dt = time.perf_counter() - t0
steps = dspec.n_samples * dspec.trace_len
print(json.dumps({"devices": D, "steps": steps, "dt": dt,
                  "steps_per_s": steps / dt}))
"""


def fleet_weak_scaling(full: bool = False):
    """Aggregate steps/sec as devices (and with them, edge replicas) grow.

    Per-device work is constant (``spd`` samples x ``tlen`` steps), so ideal
    weak scaling holds aggregate steps/sec x D. Two caveats the derived rows
    make visible: speedup saturates at the *physical core count* (forced host
    devices share the machine — ``host_cores`` is printed for exactly this),
    and per-device work must be large enough to amortise per-step dispatch
    (the single-device fallback row is the D=1 entry)."""
    # per-step work must be non-trivial (n x E state) or dispatch overhead
    # hides the overlap — these sizes scale ~2.0x/device up to the core count
    n, edges, edge_cap = 4_000, 8, 120
    spd, tlen = (2, 100_000) if full else (2, 50_000)
    device_counts = (1, 2, 4, 8) if full else (1, 2, 4)
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    rows = []
    base_sps = None  # D=1 throughput; speedups are only quoted against it
    for D in device_counts:
        script = _SCALE_WORKER % dict(
            devices=D, src=src, n=n, edges=edges, edge_cap=edge_cap,
            root_cap=8 * edge_cap, spd=spd, tlen=tlen,
        )
        proc = None
        try:
            proc = subprocess.run(
                [sys.executable, "-c", script],
                capture_output=True, text=True, timeout=600,
                # forced host devices: the worker must never reach for an
                # accelerator the parent process may already hold
                env={**os.environ, "JAX_PLATFORMS": "cpu"},
            )
            res = json.loads(proc.stdout.strip().splitlines()[-1])
        except Exception as e:  # pragma: no cover - worker diagnostics
            detail = proc.stderr[-300:] if proc is not None else e
            # the /ERROR suffix is run.py's failure signal: the row (and any
            # successful device counts) still lands in the recorded JSON, but
            # the process exits non-zero so CI can't stay green
            rows.append(
                (f"fleet_scale/D{D}/ERROR", 0.0, f"{type(e).__name__}: {detail}")
            )
            continue
        sps = res["steps_per_s"]
        if D == device_counts[0]:
            base_sps = sps
        speedup = (
            f"speedup_vs_D{device_counts[0]}={sps / base_sps:.2f}x"
            if base_sps
            else "speedup=n/a (baseline worker failed)"
        )
        rows.append(
            (
                f"fleet_scale/D{D}",
                1e6 / sps,
                f"steps_per_s={sps:.0f} edges_per_replica={edges} "
                f"replicas={spd * D} edge_instances={edges * spd * D} "
                f"{speedup} host_cores={os.cpu_count()} "
                f"devices=host-cpu-forced",
            )
        )
    return rows


ALL = {
    "fleet_policies": fleet_policy_sweep,
    "fleet_depth": fleet_depth_sweep,
    "fleet_placement": fleet_placement_sweep,
    "fleet_scale": fleet_weak_scaling,
}
