"""Chip smoke: drive the cache simulator's main paths once on a TPU and check
every result exactly against the repository's own references.

    python chip_smoke.py             # one chip: phases (a), (b), (c)
    python chip_smoke.py --chips 4   # only the multi-chip phase, over 4 chips

Phases (one chip):
  (a) flat stream: ``stream_fleet`` on the fast compact-lane path, LRU at
      N = 2^20 objects, capacity 2^16, 512 chunks of 2,048 requests generated
      on the device (``stationary``). The stream is regenerated chunk by chunk
      with the same on-device generator and replayed through the pure-Python
      LRU of ``core.policies``: hits and final occupancy must be equal.
  (b) 3-tier fleet stream on the general engine: ``fleet.tree`` with widths
      (8, 2, 1), kinds (lru, plfu, plfu), capacities (4096, 16384, 65536) over
      N = 2^20 objects, hash-routed on device, 4 ``churn`` chunks of 2,048.
      Per-node hits and origin misses must equal
      ``fleet.simulate_fleet_reference`` on the same trace and routing.
  (c) the native ``cache_sim`` Pallas kernel (``ops.cache_sim``) against the
      jitted scan (``jax_cache.simulate`` under ``vmap``) for all 9 kinds, at
      the paper's largest grid case (N = 100,000, capacity 2,000, 12 samples).
      The trace is cut from the paper's 100,000 requests to 20,000 so the
      phase fits the run's time. hits, freq and in_cache must be identical.

With ``--chips 4`` the script runs only the multi-chip phase: the phase (b)
tree through ``simulate_fleet_sharded`` (plain, and with placements
(lcd, lce, lce)) and through ``simulate_fleet_device`` with 8 samples, on a
``fleet_mesh()`` over 4 chips, each compared with the single-device run of
the same inputs in the same process.

Every phase prints one line: sizes, XLA compile seconds (backend compile or
persistent-cache load, from JAX's monitoring events), execution seconds (the
rest of the phase's device wall clock), the device kind and the exactness
verdict. The ``smoke_req_per_s`` there is one smoke run's timing, not a
benchmark. Any mismatch or error exits non-zero. The last line of a passing
run is one JSON object naming the device. Without a TPU the script exits
non-zero before any phase; it also needs the repository's ``src/`` next to it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import fleet  # noqa: E402
from repro.cdn.router import route_device  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import jax_cache, policies, zipf  # noqa: E402
from repro.fleet.reference import cache_count  # noqa: E402
from repro.kernels.cache_sim.ops import KERNEL_KINDS, cache_sim  # noqa: E402
from repro.workloads.device import DeviceTraceSpec, gen_stream_chunk  # noqa: E402

#: kernel-phase knobs: a wlfu window (the fleet benchmarks' 2,048) and sketch
#: periods short enough that tinylfu ages and plfua_dyn refreshes mid-trace
KERNEL_KNOBS = {"wlfu": {"window": 2048}, "tinylfu": {"window": 5000},
                "plfua_dyn": {"refresh": 5000}}

TREE = dict(widths=(8, 2, 1), kinds=("lru", "plfu", "plfu"),
            capacities=(4096, 16384, 65536), router="hash")


class _Phase:
    """Wall clock of the device work inside ``with``, split into XLA compile
    seconds (backend compile or persistent-cache load, from JAX's monitoring
    events) and the rest; ``cache_hits`` counts persistent-cache loads."""

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __enter__(self):
        self.compile_s, self.cache_hits = 0.0, 0
        self._listeners = (self._on_duration, self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._listeners[0])
        jax.monitoring.register_event_listener(self._listeners[1])
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.wall_s = time.perf_counter() - self._t0
        jax.monitoring.unregister_event_duration_listener(self._listeners[0])
        jax.monitoring.unregister_event_listener(self._listeners[1])
        self.exec_s = self.wall_s - self.compile_s


def _report(name: str, sizes: dict, ph: _Phase, requests: int, exact: bool,
            **extra) -> dict:
    row = {"phase": name, **sizes, "compile_s": ph.compile_s,
           "compile_cache_hits": ph.cache_hits, "exec_s": ph.exec_s,
           "smoke_req_per_s": requests / ph.exec_s,
           "device": jax.devices()[0].device_kind, "exact": exact, **extra}
    print(" ".join(f"{k}={v}" for k, v in row.items()), flush=True)
    return row


def _stream_chunks(dspec: DeviceTraceSpec, n_chunks: int):
    """The on-device stream of sample 0, one jitted generator call per chunk
    (exactly what ``stream_fleet`` feeds the engine)."""
    return [gen_stream_chunk(dspec, jnp.int32(0), jnp.int32(c)) for c in range(n_chunks)]


# --------------------------------------------------------------- phases
def phase_flat_stream(n_objects=1 << 20, capacity=1 << 16, chunk_len=2048,
                      n_chunks=512, seed=0) -> dict:
    topo = fleet.tree(n_objects=n_objects, widths=(1,), kinds="lru",
                      capacities=(capacity,))
    cfg = fleet.StreamConfig(topo=topo, chunk_len=chunk_len, fast=True)
    dspec = DeviceTraceSpec("stationary", n_objects, n_samples=1,
                            trace_len=chunk_len, seed=seed)
    with _Phase() as ph:
        st = fleet.stream_fleet(cfg, dspec, n_chunks)
    trace = np.concatenate([np.asarray(c) for c in _stream_chunks(dspec, n_chunks)])
    ref = policies.make_policy("lru", capacity, n_objects=n_objects)
    ref.run(trace.tolist())
    count = int(np.asarray(st.tiers[0]["count"])[0])
    exact = st.hits == ref.hits and count == cache_count(ref)
    return _report(
        "a_flat_stream", dict(kind="lru", n_objects=n_objects, capacity=capacity,
                              chunk_len=chunk_len, chunks=n_chunks),
        ph, st.requests, exact, hits=st.hits, ref_hits=ref.hits,
        total_chr=st.total_chr,
    )


def phase_tree_stream(n_objects=1 << 20, capacities=TREE["capacities"],
                      chunk_len=2048, n_chunks=4, seed=0) -> dict:
    topo = fleet.tree(n_objects=n_objects, **{**TREE, "capacities": capacities})
    dspec = DeviceTraceSpec("churn", n_objects, n_samples=1, trace_len=chunk_len,
                            seed=seed)
    chunks = _stream_chunks(dspec, n_chunks)
    fs = fleet.FleetStream(fleet.StreamConfig(topo=topo, chunk_len=chunk_len))
    with _Phase() as ph:
        for c in chunks:
            fs.push(c)  # no assignment: routed on device
        fs.block()
    st = fs.stats()
    trace = np.concatenate([np.asarray(c) for c in chunks])
    assign = np.asarray(route_device(jnp.asarray(trace), topo.n_edges, topo.router,
                                     session_len=topo.session_len))
    ref = fleet.simulate_fleet_reference(topo, trace, assign)
    node_hits = [np.asarray(t["hits"]).tolist() for t in st.tiers]
    ref_node_hits = [[p.hits for p in lvl] for lvl in ref.levels]
    ref_origin = int((~np.any(ref.level_hit, axis=0)).sum())
    exact = node_hits == ref_node_hits and st.origin_misses == ref_origin
    return _report(
        "b_tree_stream", dict(n_objects=n_objects, widths=TREE["widths"],
                              kinds=TREE["kinds"], capacities=capacities,
                              scenario="churn", chunk_len=chunk_len, chunks=n_chunks),
        ph, st.requests, exact, tier_hits=[sum(h) for h in node_hits],
        origin_misses=st.origin_misses,
    )


_scan_batch = jax.jit(
    lambda spec, traces: jax.vmap(lambda tr: jax_cache.simulate(spec, tr))(traces),
    static_argnums=0,
)


def _kernel_matches_scan(kind, k_out, j_out) -> bool:
    hits_k, freq_k, cache_k = (np.asarray(a) for a in k_out)
    hits_j, state = j_out
    cached = np.asarray(state["in_cache"])
    if kind == "lru":  # stamps t + 1 (0 = never), meaningful where cached
        freq_ok = np.array_equal(freq_k[cached], (np.asarray(state["last"]) + 1)[cached])
    else:  # arc ships its stamp row through the freq slot
        freq_ok = np.array_equal(freq_k, np.asarray(state["stamp" if kind == "arc" else "freq"]))
    return (np.array_equal(hits_k, np.asarray(hits_j).sum(-1))
            and np.array_equal(cache_k, cached) and freq_ok)


def phase_kernel(n_objects=100_000, capacity=2_000, n_samples=12,
                 trace_len=20_000, seed=0, kinds=KERNEL_KINDS,
                 knobs=KERNEL_KNOBS) -> dict:
    traces = jnp.asarray(zipf.sample_traces(n_objects, n_samples, trace_len,
                                            seed=seed), jnp.int32)
    rows = []
    for kind in kinds:
        kw = knobs.get(kind, {})
        spec = jax_cache.PolicySpec(kind, n_objects, capacity, **kw)
        with _Phase() as ph_k:
            k_out = jax.block_until_ready(
                cache_sim(traces, kind=kind, n_objects=n_objects, capacity=capacity, **kw)
            )
        with _Phase() as ph_j:
            j_out = jax.block_until_ready(_scan_batch(spec, traces))
        rows.append(_report(
            f"c_kernel/{kind}", dict(n_objects=n_objects, capacity=capacity,
                                     samples=n_samples, trace_len=trace_len, **kw),
            ph_k, n_samples * trace_len, _kernel_matches_scan(kind, k_out, j_out),
            scan_compile_s=ph_j.compile_s, scan_exec_s=ph_j.exec_s,
            chr=float(np.asarray(k_out[0]).sum()) / (n_samples * trace_len),
        ))
    return {"phase": "c_kernel", "exact": all(r["exact"] for r in rows), "rows": rows}


def _same(a, b) -> bool:
    return jax.tree.structure(a) == jax.tree.structure(b) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def phase_multichip(mesh, n_objects=1 << 20, capacities=TREE["capacities"],
                    trace_len=8192, n_samples=8, seed=0) -> dict:
    dspec = DeviceTraceSpec("churn", n_objects, n_samples=n_samples,
                            trace_len=trace_len, seed=seed)
    trace = gen_stream_chunk(dspec, jnp.int32(0), jnp.int32(0))
    verdicts = {}
    with _Phase() as ph:
        for name, placements in (("edge_sharded", None),
                                 ("edge_sharded_lcd", ("lcd", "lce", "lce"))):
            kw = {} if placements is None else {"placements": placements}
            topo = fleet.tree(n_objects=n_objects,
                              **{**TREE, "capacities": capacities}, **kw)
            assign = route_device(trace, topo.n_edges, topo.router,
                                  session_len=topo.session_len)
            one = fleet.simulate_fleet(topo, trace, assign)
            many = fleet.simulate_fleet_sharded(topo, trace, assign, mesh=mesh)
            verdicts[name] = _same(one, many)
        topo = fleet.tree(n_objects=n_objects, **{**TREE, "capacities": capacities})
        one = fleet.simulate_fleet_device(topo, dspec)
        many = fleet.simulate_fleet_device(topo, dspec, mesh=mesh)
        verdicts["sample_sharded"] = _same(one, many)
        jax.block_until_ready(many)
    n_dev = fleet.mesh_size(mesh)
    return _report(
        "multichip", dict(n_objects=n_objects, widths=TREE["widths"],
                          capacities=capacities, trace_len=trace_len,
                          samples=n_samples, mesh_devices=n_dev),
        ph, 2 * (2 + n_samples) * trace_len, all(verdicts.values()), **verdicts,
    )


# ----------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the multi-chip phase over a 4-chip mesh")
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {devices[0].platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"jax found {len(devices)}", file=sys.stderr)
        return 2
    print(f"compile_cache={enable_compile_cache()}", flush=True)
    if args.chips == 4:
        results = [phase_multichip(fleet.fleet_mesh(devices[:4]))]
    else:
        results = [phase_flat_stream(), phase_tree_stream(), phase_kernel()]
    bad = [r["phase"] for r in results if not r["exact"]]
    if bad:
        print(f"chip_smoke: results differ from the reference in {bad}", file=sys.stderr)
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {"platform": d.platform,
                                             "kind": d.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
