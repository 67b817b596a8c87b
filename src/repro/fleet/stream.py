"""Streaming fleet engine: an unbounded request stream as fixed-shape chunks.

A bounded fleet run (:func:`repro.fleet.sim.simulate_fleet`) takes one trace
array per call — fine for the paper's 500k-request replications, useless
for "millions of users": production load is a *stream*, and the headline
metric (management CPU time = energy) only means something at sustained
line rate. This module runs that stream as a sequence of
``chunk_len``-shaped chunks with three invariants:

* **Donated carry.** Every push consumes the carry (cache directory, sketch
  rows, ARC lists, placement sketches, counter accumulators) via
  ``jax.jit(..., donate_argnums=0)``: state buffers round-trip in place
  instead of being copied once per chunk, so steady-state memory traffic is
  the chunk itself, not the fleet state. The caller-visible contract is that
  :meth:`FleetStream.push` owns the carry — user code never touches it.

* **One chunk program.** The level-major and placed engines *are* chunk
  programs (:mod:`repro.fleet.sim`); a bounded run applies the same
  function once to a chunk as long as its trace. So K pushed chunks equal
  one chunk of their concatenation — hit series, final states, tier
  counters, grouped telemetry series, eviction-pressure channels — when
  the carry, its stream position ``t0`` and the series stitch: plfua_dyn's
  global-time hot-set refresh fires on a *traced* boundary test against
  ``t0`` in gcd(refresh, chunk_len) sub-chunks, so any chunk length runs
  the same refresh schedule; telemetry stitches because the window divides
  the chunk (enforced at config time), so every chunk emits whole windows.
  The flat fast path below is its own program, pinned against
  ``jax_cache.simulate``.

* **Double-buffered on-device synthesis.** :func:`stream_fleet` dispatches
  the jitted generator for chunk ``t+1`` (``workloads.device
  .gen_stream_chunk``, traced chunk index — one compiled program) *before*
  blocking on chunk ``t``'s simulation, so on an asynchronous-dispatch
  backend generation overlaps simulation and the host loop never holds the
  pipeline.

The **fast path** (``StreamConfig(fast=True)``, single flat cache) replaces
the dense (n_objects,)-per-step scan with a compact working-set engine: per
chunk it selects the ``P = min(2*chunk_len, capacity + chunk_len)``
lexicographically smallest ``(eviction_key, id)`` cached candidates from a
sorted roster, unions them with the chunk's ids, and runs the unchanged
``jax_cache.step`` on the ``P + chunk_len`` compact lanes (sentinel-padded,
scattered back with ``mode="drop"``). Correctness rests on the candidate-
prefix bound: one step invalidates at most two prefix entries (the touched
object and the evicted victim; every other cached object's eviction key is
constant within a chunk for the FAST_KINDS), so a ``2*chunk_len`` prefix
always contains the true victim, ties included — the compact lanes are
id-sorted, making the masked argmin's tie-break identical to the dense
engine's lowest-id rule. Pinned bit-exact against ``jax_cache.simulate`` in
tests/test_stream.py.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.cdn import router as router_mod
from repro.core import energy, jax_cache
from repro.fleet import sim as sim_mod
from repro.fleet.topology import Topology
from repro.telemetry.spec import TelemetrySpec
from repro.workloads import device as device_mod

__all__ = [
    "FAST_KINDS",
    "FleetStream",
    "StreamConfig",
    "StreamStats",
    "stream_fleet",
]

#: kinds whose eviction key is per-object and touch-local (untouched cached
#: objects keep their key within a chunk), which is what the fast path's
#: candidate-prefix bound needs. wlfu is out (the ring slide retires other
#: objects' window counts every step), arc is out (REPLACE moves whole-list
#: LRU positions), byte mode is out (one insert can evict many victims).
FAST_KINDS = ("lru", "lfu", "plfu", "plfua", "plfua_dyn", "gdsf", "tinylfu")

@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Static streaming-run configuration (hashable; the jit key).

    ``chunk_len`` is the fixed shape of every pushed chunk. With telemetry,
    the window must divide it so chunks emit whole windows (stitching is
    then concatenation). ``fast=True`` selects the compact working-set
    engine — single flat cache (depth-1, one node), FAST_KINDS, object-count
    capacity, no telemetry; plfua_dyn additionally needs its refresh period
    to be a multiple of ``chunk_len`` so hot-set refreshes land on chunk
    boundaries."""

    topo: Topology
    chunk_len: int
    telemetry: TelemetrySpec | None = None
    fast: bool = False

    def __post_init__(self):
        if self.chunk_len < 1:
            raise ValueError(f"chunk_len must be >= 1, got {self.chunk_len}")
        if self.telemetry is not None and self.chunk_len % self.telemetry.window:
            raise ValueError(
                f"telemetry window ({self.telemetry.window}) must divide "
                f"chunk_len ({self.chunk_len}) so every chunk emits whole "
                f"windows (series stitch by concatenation)"
            )
        if self.fast:
            if self.topo.n_levels != 1 or len(self.topo.levels[0]) != 1:
                raise ValueError("fast=True needs a depth-1, single-node topology")
            spec = self.topo.levels[0][0]
            if spec.kind not in FAST_KINDS:
                raise ValueError(
                    f"fast=True supports kinds {FAST_KINDS}, got {spec.kind!r}"
                )
            if spec.capacity_bytes:
                raise ValueError("fast=True is object-count only (no byte mode)")
            if self.telemetry is not None:
                raise ValueError("fast=True does not support telemetry")
            if (
                spec.kind == "plfua_dyn"
                and spec.effective_refresh % self.chunk_len
            ):
                raise ValueError(
                    f"fast plfua_dyn needs refresh % chunk_len == 0 "
                    f"(refresh={spec.effective_refresh}, "
                    f"chunk_len={self.chunk_len}) so hot-set refreshes land "
                    f"on chunk boundaries"
                )


@dataclasses.dataclass(frozen=True)
class StreamStats:
    """Rollup of one streaming run.

    ``tiers`` follows ``simulate_fleet``'s per-level counter-dict layout
    (the engines' shared ``sim._tier_from_acc``); the fast path reports the
    reduced dict its carry can derive (requests/hits/count[, inserts]).
    ``telemetry``/``telemetry_pressure`` are the stitched per-level series,
    shaped exactly like ``simulate_fleet``'s on the concatenated trace.
    ``lanes`` is the engine's lane grid and ``lanes_valid`` the lanes that
    held a real request; ``node_steps`` is what the engine's loops ran.
    Fast path: compact lanes, chunks x ``P + chunk_len``, all stepped, and
    those holding a real object (an int32 device counter, like ``hits``,
    exact below 2**31). Level-major engine: the dense node x position grid,
    chunks x ``chunk_len`` x the tree's node count, and the node-steps
    whose node held an active request (the tiers' ``requests`` summed);
    each node steps its own requests compacted (``sim.masked_scan``), so a
    level's loop runs ``K_l`` x its busiest node's load a chunk, or ``K_l``
    x ``chunk_len`` for a level that keeps the dense scan (plfua_dyn,
    telemetry) — ``node_steps`` sums them in an int32 device counter and
    ``lanes_valid / node_steps`` is the share of stepped work that served a
    request. Placed engine: one gathered node-step per level and position,
    chunks x ``chunk_len`` x levels, all stepped, and the same active
    count."""

    requests: int
    chunks: int
    chunk_len: int
    hits: int
    origin_misses: int
    tiers: tuple
    elapsed_s: float | None = None
    telemetry: tuple | None = None
    telemetry_pressure: tuple | None = None
    lanes: int | None = None
    lanes_valid: int | None = None
    node_steps: int | None = None

    @property
    def total_chr(self) -> float:
        """Fleet-level hit ratio: served by any tier / total requests."""
        return self.hits / max(1, self.requests)

    @property
    def req_per_s(self) -> float | None:
        """Sustained throughput over the measured wall-clock window."""
        if not self.elapsed_s:
            return None
        return self.requests / self.elapsed_s

    @property
    def j_per_step(self) -> float | None:
        """Measured management energy per request (core.energy's single-core
        CPU model over the sustained wall clock)."""
        if not self.elapsed_s:
            return None
        return energy.mgmt_energy_j(self.elapsed_s) / max(1, self.requests)


# --------------------------------------------------- fast compact-lane path
#: per-object state fields gathered into compact lanes (everything else in
#: a FAST_KINDS state — count/t/L/sketch/seen/inserts/bloom — is a scalar or
#: a small table that passes through unchanged)
_PER_OBJECT_FIELDS = ("last", "freq", "score", "hot")


def _build_fast(cfg: StreamConfig, sizes):
    spec = cfg.topo.levels[0][0]
    N, G = spec.n_objects, cfg.chunk_len
    R = spec.capacity + G  # roster slots: residents never exceed cap (+G slack)
    P = min(2 * G, R)  # candidate prefix (>= 2 invalidations/step bound)
    M = P + G
    cspec = dataclasses.replace(spec, n_objects=M)
    sketchy = spec.kind in jax_cache.SKETCH_POLICY_KINDS
    big_table = spec._bucket_table() if sketchy else None
    big_bloom = spec._bloom_table() if spec.kind == "tinylfu" and spec.doorkeeper else None

    def fast_chunk(carry, trace):
        state, roster, t0 = carry["state"], carry["roster"], carry["t0"]
        xs = trace.astype(jnp.int32)
        # ---- candidates: the P lex-smallest (eviction_key, id) cached pairs,
        # selected over the roster (every resident), sentinel-padded with N
        with jax.named_scope("repro.select"):
            key = sim_mod._victim_key(spec, state)
            rc = jnp.minimum(roster, N - 1)
            rkey = jnp.where(roster < N, key[rc], jax_cache._I32_MAX)
            _, sid = jax.lax.sort((rkey, roster), num_keys=2)
            cand = jax.lax.slice_in_dim(sid, 0, P)
        # ---- lanes: candidates ∪ chunk ids, id-sorted, deduped to sentinel
        with jax.named_scope("repro.lanes"):
            ids = jnp.sort(jnp.concatenate([cand, xs]))
            dup = jnp.concatenate([jnp.zeros((1,), jnp.bool_), ids[1:] == ids[:-1]])
            ids = jnp.sort(jnp.where(dup, N, ids))
            valid = ids < N
            idc = jnp.minimum(ids, N - 1)
            cstate = {}
            for k, v in state.items():
                if k == "in_cache":
                    # invalid lanes must read not-cached (they hold garbage rows)
                    cstate[k] = valid & v[idc]
                elif k in _PER_OBJECT_FIELDS:
                    cstate[k] = v[idc]
                else:
                    cstate[k] = v
            table_c = None if big_table is None else jnp.asarray(big_table)[idc]
            bloom_c = None if big_bloom is None else jnp.asarray(big_bloom)[idc]
            sizes_c = None if sizes is None else sizes[idc]
            lx = jnp.searchsorted(ids, xs).astype(jnp.int32)

        def f(cs, xl):
            return jax_cache.step(
                cspec, cs, xl, sizes=sizes_c, table=table_c, bloom_tab=bloom_c
            )

        with jax.named_scope("repro.step"):
            cstate, hits = jax.lax.scan(f, cstate, lx)
        # ---- scatter the compact lanes back (sentinel id N is out of bounds
        # for the dense (N,) arrays, so mode="drop" discards invalid lanes)
        with jax.named_scope("repro.scatter"):
            new_state = {}
            for k, v in state.items():
                if k == "in_cache" or k in _PER_OBJECT_FIELDS:
                    new_state[k] = v.at[ids].set(cstate[k], mode="drop")
                else:
                    new_state[k] = cstate[k]
        if spec.kind == "plfua_dyn":
            # refresh periods are whole multiples of the chunk (config
            # invariant), so the only possible boundary is the chunk end
            new_state = jax.lax.cond(
                (t0 + jnp.int32(G)) % jnp.int32(spec.effective_refresh) == 0,
                lambda s: jax_cache.refresh_hot(spec, s),
                lambda s: s,
                new_state,
            )
        # ---- roster rebuild: residents ⊆ old roster ∪ chunk ids
        with jax.named_scope("repro.roster"):
            r2 = jnp.sort(jnp.concatenate([roster, xs]))
            dup2 = jnp.concatenate([jnp.zeros((1,), jnp.bool_), r2[1:] == r2[:-1]])
            keep = (~dup2) & (r2 < N) & new_state["in_cache"][jnp.minimum(r2, N - 1)]
            new_roster = jax.lax.slice_in_dim(jnp.sort(jnp.where(keep, r2, N)), 0, R)
        new_carry = {
            "state": new_state,
            "roster": new_roster,
            "hits": carry["hits"] + hits.sum(dtype=jnp.int32),
            "lanes_valid": carry["lanes_valid"] + valid.sum(dtype=jnp.int32),
            "t0": t0 + jnp.int32(G),
        }
        return new_carry, {
            "hit": (hits,),
            "node_hit": (hits[None, :],),
            "origin_miss": ~hits,
        }

    carry0 = {
        "state": jax_cache.init_state(spec),
        "roster": jnp.full((R,), N, jnp.int32),
        "hits": jnp.zeros((), jnp.int32),
        "lanes_valid": jnp.zeros((), jnp.int32),
        "t0": jnp.zeros((), jnp.int32),
    }
    return jax.jit(fast_chunk, donate_argnums=0), carry0, M


class FleetStream:
    """Push-driven streaming run of one topology (see module docstring).

    Construct once per stream; :meth:`push` consumes fixed-shape chunks and
    returns the per-chunk results (hit series, per-node hits, origin
    misses — device arrays, lazy); :meth:`stats` rolls the stream up into a
    :class:`StreamStats`. The carry is donated into every push, so no
    simulation state is ever copied host-side or duplicated on device."""

    def __init__(self, cfg: StreamConfig, *, sizes=None, groups=None):
        self.cfg = cfg
        self._sizes = None if sizes is None else jnp.asarray(sizes, jnp.int32)
        telemetry, topo, G = cfg.telemetry, cfg.topo, cfg.chunk_len
        self._groups, og = sim_mod._group_tables(telemetry, groups)
        if cfg.fast:
            self._push_fn, self._carry, self._lanes = _build_fast(cfg, self._sizes)
        else:
            chunk, self._carry = sim_mod._chunk_program(
                topo, G, telemetry, sizes=self._sizes, og=og, groups=self._groups
            )
            self._push_fn = jax.jit(chunk, donate_argnums=0)
            # the dense node x position grid (level-major), or one gathered
            # node-step per level and position (time-major)
            self._lanes = G * (topo.n_levels if topo.has_placement else topo.n_nodes)
        self.chunks = 0
        self._series = (
            [[] for _ in cfg.topo.levels] if telemetry is not None else None
        )
        self._pressure = [[] for _ in cfg.topo.levels] if og is not None else None

        def route_chunk(tr, t0):
            with jax.named_scope("repro.route"):
                return router_mod.route_device(
                    tr, cfg.topo.n_edges, cfg.topo.router,
                    session_len=cfg.topo.session_len, t0=t0,
                )

        self._route = jax.jit(route_chunk)

    def push(self, trace, assignment=None):
        """Run one chunk. ``trace`` must be ``(chunk_len,)``; ``assignment``
        is the per-request edge node (int32, same shape) — omit it to route
        on device with the topology's edge router, at the chunk's stream
        position (``chunks x chunk_len``, a traced int32, so positions wrap
        past 2**31 requests as the carry's do): any router, ``sticky`` and
        ``round_robin`` included, routes a chunked stream as it would the
        whole trace.

        In a profile the call is the host span ``repro:push``, holding
        ``repro:route`` (the edge assignment), ``repro:dispatch`` (the chunk
        program's dispatch) and ``repro:stitch`` (telemetry series kept)."""
        with jax.profiler.TraceAnnotation("repro:push"):
            return self._push(trace, assignment)

    def _push(self, trace, assignment):
        G = self.cfg.chunk_len
        if trace.shape != (G,):
            raise ValueError(f"expected chunk of shape ({G},), got {trace.shape}")
        if self.cfg.fast:
            with jax.profiler.TraceAnnotation("repro:dispatch"):
                self._carry, out = self._push_fn(self._carry, trace)
            self.chunks += 1
            return out
        with jax.profiler.TraceAnnotation("repro:route"):
            if assignment is None:
                if self.cfg.topo.n_edges == 1:
                    assignment = jnp.zeros((G,), jnp.int32)
                else:
                    t0 = np.int64(self.chunks * G).astype(np.int32)
                    assignment = self._route(trace, t0)
            assignment = jnp.asarray(assignment, jnp.int32)
        with jax.profiler.TraceAnnotation("repro:dispatch"):
            self._carry, out = self._push_fn(self._carry, trace, assignment)
        self.chunks += 1
        if self._series is not None or self._pressure is not None:
            with jax.profiler.TraceAnnotation("repro:stitch"):
                if self._series is not None:
                    for l, s in enumerate(out["telemetry"]):
                        self._series[l].append(s)
                if self._pressure is not None:
                    for l, p in enumerate(out["telemetry_pressure"]):
                        self._pressure[l].append(p)
        return out

    def block(self):
        """Wait for every dispatched chunk to finish (throughput timing)."""
        jax.block_until_ready(self._carry)
        return self

    def states(self):
        """Per-level stacked final policy states (fast path: the one dense
        state), laid out exactly like ``simulate_fleet``'s ``states``."""
        if self.cfg.fast:
            return (self._carry["state"],)
        return sim_mod._final_states(self.cfg.topo, self._carry)

    def stats(self, elapsed_s: float | None = None) -> StreamStats:
        """Roll the stream up. Counters are folded from the carry as a
        bounded run folds them; telemetry series are the per-chunk window
        series concatenated (bit-identical to one chunk of the concatenated
        trace). The host waits for the
        device's counters inside the span ``repro:sync``."""
        with jax.profiler.TraceAnnotation("repro:sync"):
            return self._stats(elapsed_s)

    def _stats(self, elapsed_s):
        cfg = self.cfg
        requests = self.chunks * cfg.chunk_len
        if cfg.fast:
            carry = self._carry
            hits = int(carry["hits"])
            tier = {
                "requests": jnp.asarray([requests], jnp.int32),
                "hits": jnp.asarray([hits], jnp.int32),
                "count": carry["state"]["count"][None],
            }
            if "inserts" in carry["state"]:
                tier["inserts"] = carry["state"]["inserts"][None]
                tier["evictions"] = tier["inserts"] - tier["count"]
            return StreamStats(
                requests=requests,
                chunks=self.chunks,
                chunk_len=cfg.chunk_len,
                hits=hits,
                origin_misses=requests - hits,
                tiers=(tier,),
                elapsed_s=elapsed_s,
                lanes=self.chunks * self._lanes,
                lanes_valid=int(carry["lanes_valid"]),
                node_steps=self.chunks * self._lanes,
            )
        carry = self._carry
        origin = int(carry["origin"])
        tiers = sim_mod._final_tiers(cfg.topo, carry)
        telemetry = pressure = None
        if self._series is not None:
            telemetry = tuple(
                jnp.concatenate(chunks, axis=1) for chunks in self._series
            )
        if self._pressure is not None:
            pressure = tuple(
                jnp.concatenate(chunks, axis=1) for chunks in self._pressure
            )
        return StreamStats(
            requests=requests,
            chunks=self.chunks,
            chunk_len=cfg.chunk_len,
            hits=requests - origin,
            origin_misses=origin,
            tiers=tuple(tiers),
            elapsed_s=elapsed_s,
            telemetry=telemetry,
            telemetry_pressure=pressure,
            lanes=self.chunks * self._lanes,
            lanes_valid=sum(int(np.asarray(t["requests"]).sum()) for t in tiers),
            node_steps=(
                self.chunks * self._lanes
                if cfg.topo.has_placement
                else int(carry["node_steps"])
            ),
        )


def stream_fleet(
    cfg: StreamConfig,
    dspec: device_mod.DeviceTraceSpec,
    n_chunks: int,
    *,
    sample: int = 0,
    sizes=None,
    groups=None,
) -> StreamStats:
    """Run ``n_chunks`` chunks of an on-device synthesized stream, double-
    buffered: the jitted generator for chunk ``t+1`` is dispatched before
    chunk ``t``'s simulation is consumed, so generation and simulation
    overlap on an asynchronous-dispatch backend. ``dspec.trace_len`` is the
    chunk length and must equal ``cfg.chunk_len``. Returns the
    :class:`StreamStats` rollup with the measured wall clock (sustained
    req/s and J/step over generation + simulation)."""
    if dspec.trace_len != cfg.chunk_len:
        raise ValueError(
            f"dspec.trace_len ({dspec.trace_len}) must equal cfg.chunk_len "
            f"({cfg.chunk_len})"
        )
    if n_chunks < 1:
        raise ValueError(f"n_chunks must be >= 1, got {n_chunks}")
    fs = FleetStream(cfg, sizes=sizes, groups=groups)
    sample = jnp.int32(sample)
    nxt = device_mod.gen_stream_chunk(dspec, sample, jnp.int32(0))
    start = time.perf_counter()
    for c in range(n_chunks):
        cur = nxt
        if c + 1 < n_chunks:
            # dispatch next chunk's synthesis before consuming this one:
            # the generator runs while the simulator chews on `cur`
            nxt = device_mod.gen_stream_chunk(dspec, sample, jnp.int32(c + 1))
        fs.push(cur)
    fs.block()
    elapsed = time.perf_counter() - start
    return fs.stats(elapsed_s=elapsed)
