"""Vectorised, fixed-shape JAX formulation of the paper's cache policies.

This is the TPU-native re-architecture (DESIGN.md §3): object ids are array
indices, the cache is an ``in_cache`` mask, the LFU frequency container and the
PLFU parked-list collapse into a single dense ``freq`` vector (parked = freq of
non-cached ids; LFU simply zeroes the victim's entry on eviction), and the
request loop is a ``lax.scan`` whose step is branch-free. Eviction is a masked
argmin — ties break to the lowest id, matching the reference implementation in
:mod:`repro.core.policies` decision-for-decision.

``simulate_batch`` vmaps over the paper's 12 samples; the Pallas kernel in
``repro.kernels.cache_sim`` runs the same step out of VMEM with a grid over
(case, sample) and is validated against :func:`simulate` as its oracle.

PR 7 adds *byte-capacity* mode (``PolicySpec.capacity_bytes > 0``): the limit
becomes a byte budget over a per-object ``sizes`` array (a traced argument,
unit when omitted) and one insertion may evict several victims — a bounded
``lax.fori_loop`` of at most ``effective_max_victims`` masked argmins, after
which an object that still does not fit is simply not inserted (an object
larger than the whole budget evicts nothing). With unit sizes and
``capacity_bytes == capacity`` the trajectory is bit-identical to
object-count mode. The ``gdsf`` kind (GreedyDual-Size-Frequency) scores
``L + (freq << GDSF_SHIFT) // size`` with the global aging credit ``L``
ratcheted to each evicted victim's score — all int32, so the Python
reference, this scan, and the Pallas kernel agree bit for bit.

Static ``plfua`` bounds its metadata by its admission: an id ``x >= H``
(``H = effective_hot``) is never admitted, so it never hits, is never
inserted or evicted, and its ``freq`` is never touched — its slot stays
``(False, 0)`` for the whole run. :func:`simulate` (and through it
:func:`simulate_batch`) therefore scans a spec of ``H + 1`` objects on the
trace clamped to ``min(x, H)``: slot ``H`` stands for every non-admissible
id and, with ``hot[H]`` false, is never admitted either. The final state is
padded back to ``n_objects``, so hits, state, :func:`eviction_count` and
:func:`metadata_entries` are those of the dense scan, bit for bit. Dense
still: the telemetry path (:func:`instrumented_scan`), ``plfua_dyn``
(its hot set moves), and every direct caller of :func:`step` (the streaming
fast path, the fleet engines, :func:`run_chunk`).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import registry, sketch
from repro.telemetry import spec as telemetry_spec

_I32_MAX = np.iinfo(np.int32).max

JAX_POLICY_KINDS = registry.names(jax=True)
SKETCH_POLICY_KINDS = registry.names(sketch=True)

GDSF_SHIFT = registry.GDSF_SHIFT
DEFAULT_MAX_VICTIMS = registry.DEFAULT_MAX_VICTIMS


@dataclasses.dataclass(frozen=True)
class PolicySpec:
    """Static (hashable) policy configuration for the jitted simulator."""

    kind: str
    n_objects: int
    capacity: int
    hot_size: int = 0  # plfua/plfua_dyn; 0 means "2 * capacity" convention applied in init
    window: int = 0  # wlfu (required) and tinylfu aging (0 -> sketch.default_window)
    refresh: int = 0  # plfua_dyn hot-set period (0 -> sketch.default_refresh)
    sketch_width: int = 0  # sketch kinds (0 -> sketch.default_width)
    doorkeeper: int = 0  # tinylfu bloom front, in bits (0 = off, the default)
    capacity_bytes: int = 0  # >0 switches the limit to a byte budget (PR 7)
    max_victims: int = 0  # byte mode eviction bound (0 -> DEFAULT_MAX_VICTIMS)

    def __post_init__(self):
        if self.kind not in JAX_POLICY_KINDS:
            raise ValueError(f"kind={self.kind!r} not in {JAX_POLICY_KINDS}")
        if self.kind == "wlfu" and self.window < 1:
            raise ValueError("wlfu requires window >= 1")
        if self.doorkeeper < 0:
            raise ValueError(f"doorkeeper must be >= 0, got {self.doorkeeper}")
        if self.doorkeeper and self.kind != "tinylfu":
            raise ValueError("doorkeeper is a tinylfu-only option")
        if self.capacity_bytes < 0:
            raise ValueError(f"capacity_bytes must be >= 0, got {self.capacity_bytes}")
        if self.kind == "arc" and self.capacity_bytes:
            # the T1/T2 balance target p is defined in object slots; a byte
            # budget has no analogue (mirrors the reference ARCCache raise)
            raise ValueError("arc does not support byte-capacity mode")
        if self.max_victims < 0:
            raise ValueError(f"max_victims must be >= 0, got {self.max_victims}")
        if self.max_victims and not self.capacity_bytes:
            raise ValueError("max_victims is a byte-capacity (capacity_bytes) option")

    @property
    def size_aware(self) -> bool:
        """Whether the step consults per-object sizes at all (gdsf always
        scores by size; every kind does under a byte budget)."""
        return self.capacity_bytes > 0 or self.kind == "gdsf"

    @property
    def effective_max_victims(self) -> int:
        return self.max_victims or DEFAULT_MAX_VICTIMS

    @property
    def effective_hot(self) -> int:
        if self.kind not in ("plfua", "plfua_dyn"):
            return self.n_objects
        h = self.hot_size or 2 * self.capacity
        return min(self.n_objects, h)

    @property
    def effective_window(self) -> int:
        """TinyLFU sketch-aging window (wlfu keeps its mandatory window)."""
        if self.kind == "tinylfu":
            return self.window or sketch.default_window(self.capacity)
        return self.window

    @property
    def effective_refresh(self) -> int:
        return self.refresh or sketch.default_refresh(self.capacity)

    @property
    def effective_sketch_width(self) -> int:
        return self.sketch_width or sketch.default_width(self.capacity)

    def _bucket_table(self) -> np.ndarray:
        """Host-side (n_objects, DEPTH) bucket constant, folded into the jit."""
        return sketch.bucket_table(
            np.arange(self.n_objects), self.effective_sketch_width
        )

    def _bloom_table(self) -> np.ndarray:
        """Host-side (n_objects, BLOOM_DEPTH) doorkeeper bit constant."""
        return sketch.bloom_table(np.arange(self.n_objects), self.doorkeeper)


def init_state(spec: PolicySpec) -> dict[str, jax.Array]:
    """Zero state. ``hot`` is the PLFUA admission mask (rank-prefix hot set,
    which for plfua_dyn is only the prior until the first sketch refresh)."""
    n = spec.n_objects
    state: dict[str, Any] = {
        "in_cache": jnp.zeros((n,), jnp.bool_),
        "count": jnp.zeros((), jnp.int32),
    }
    if spec.kind == "lru":
        state["last"] = jnp.zeros((n,), jnp.int32)
        state["t"] = jnp.zeros((), jnp.int32)
    elif spec.kind == "arc":
        # per-object list membership (0=unlisted 1=T1 2=T2 3=B1 4=B2) and an
        # entry stamp: the LRU of a list is its min-stamp member (within one
        # list stamps are unique — at most one object joins a list per step)
        state["lst"] = jnp.zeros((n,), jnp.int32)
        state["stamp"] = jnp.zeros((n,), jnp.int32)
        state["p"] = jnp.zeros((), jnp.int32)  # adaptive T1 size target
        state["t"] = jnp.zeros((), jnp.int32)
    else:
        state["freq"] = jnp.zeros((n,), jnp.int32)
    if spec.kind in ("plfua", "plfua_dyn"):
        state["hot"] = jnp.arange(n, dtype=jnp.int32) < spec.effective_hot
    if spec.kind == "wlfu":
        state["ring"] = jnp.full((spec.window,), -1, jnp.int32)
        state["ptr"] = jnp.zeros((), jnp.int32)
    if spec.kind in SKETCH_POLICY_KINDS:
        state["sketch"] = jnp.zeros((sketch.DEPTH, spec.effective_sketch_width), jnp.int32)
        # admissions are data-dependent for sketch kinds, so the insert count
        # is carried in state (evictions = inserts - final occupancy)
        state["inserts"] = jnp.zeros((), jnp.int32)
    if spec.kind == "tinylfu":
        state["seen"] = jnp.zeros((), jnp.int32)  # aging-window position
        if spec.doorkeeper:
            state["bloom"] = jnp.zeros((spec.doorkeeper,), jnp.bool_)
    if spec.kind == "gdsf":
        state["score"] = jnp.zeros((n,), jnp.int32)  # cached priority H
        state["L"] = jnp.zeros((), jnp.int32)  # global aging credit
    if spec.capacity_bytes:
        state["bytes"] = jnp.zeros((), jnp.int32)  # resident bytes
        if spec.kind not in SKETCH_POLICY_KINDS:
            # in byte mode insertion success is data-dependent for every kind
            # (the object may not fit), so the insert count joins the state
            state["inserts"] = jnp.zeros((), jnp.int32)
    return state


def _masked_argmin(values: jax.Array, mask: jax.Array) -> jax.Array:
    """argmin over ``values`` where mask, lowest index on ties (int32 values).
    Every kind's victim search in every engine comes through here, so its
    operations carry the ``repro.victim`` scope in a profile."""
    with jax.named_scope("repro.victim"):
        return jnp.argmin(jnp.where(mask, values, _I32_MAX)).astype(jnp.int32)


def _sz(sizes: jax.Array | None, i: jax.Array) -> jax.Array:
    """Per-object size lookup; ``sizes=None`` is the unit-size convention."""
    return jnp.int32(1) if sizes is None else sizes[i]


def _evict_bytes_loop(spec, key, in_cache, count, nbytes, size_x, want, cap_b, sizes, L=None):
    """Byte mode's bounded multi-victim eviction (the reference's
    ``CachePolicy._room_for``, iteration for iteration): evict the masked
    argmin of ``key`` until ``size_x`` more bytes fit, the cache is empty,
    or ``effective_max_victims`` victims are gone. An object larger than the
    whole budget evicts nothing. Returns ``(in_cache, count, nbytes, key,
    L)`` — ``key`` is mutated only for the metadata-destroying kinds
    (lfu/tinylfu zero the victim's frequency) and ``L`` only for gdsf (the
    aging credit ratchets to each victim's score)."""
    destroy = spec.kind in ("lfu", "tinylfu")
    fits_ever = size_x <= cap_b

    def body(_, carry):
        ic, cnt, nb, keyarr, credit = carry
        need = want & fits_ever & (nb + size_x > cap_b) & (cnt > 0)
        v = _masked_argmin(keyarr, ic)
        if spec.kind == "gdsf":
            credit = jnp.where(need, keyarr[v], credit)
        ic = ic.at[v].set(ic[v] & ~need)
        cnt = cnt - need.astype(jnp.int32)
        nb = nb - jnp.where(need, _sz(sizes, v), 0)
        if destroy:
            keyarr = keyarr.at[v].set(jnp.where(need, 0, keyarr[v]))
        return ic, cnt, nb, keyarr, credit

    return jax.lax.fori_loop(
        0,
        spec.effective_max_victims,
        body,
        (in_cache, count, nbytes, key, jnp.int32(0) if L is None else L),
    )


def step(
    spec: PolicySpec,
    state: dict[str, jax.Array],
    x: jax.Array,
    cap: jax.Array | None = None,
    fill: jax.Array | None = None,
    sizes: jax.Array | None = None,
    cap_bytes: jax.Array | None = None,
    table: jax.Array | None = None,
    bloom_tab: jax.Array | None = None,
):
    """One request. Returns (new_state, hit: bool). Order of operations matches
    the Python reference exactly (see tests/test_jax_cache.py).

    ``cap`` optionally overrides ``spec.capacity`` with a *traced* value so a
    fleet of edges sharing one compiled step can differ in cache size
    (repro.cdn vmaps this step over edge nodes).

    ``fill`` optionally gates *insertion* (and the eviction that makes room
    for it) — the fleet's cross-tier placement hook (repro.fleet.placement):
    with ``fill`` False a miss still updates policy metadata (window slide,
    sketch feed, parked-frequency bump — since PR 7 in-memory LFU parks too;
    only its *eviction* still destroys metadata) but the object is not
    stored. ``fill=None`` means unconditional insertion (flat-cache).

    ``sizes`` is the per-object byte-size array (traced, ``None`` = unit
    sizes); ``cap_bytes`` optionally overrides ``spec.capacity_bytes`` with a
    traced per-node budget, mirroring ``cap``. Both are only consulted when
    ``spec.size_aware``.

    ``table``/``bloom_tab`` optionally override the sketch bucket / bloom-bit
    constants with *traced* per-object rows ((n, DEPTH) / (n, BLOOM_DEPTH)) —
    the streaming fast path (repro.fleet.stream) runs this step on a compact
    working-set state whose lane ids are not the global ids, so it gathers
    the true hash rows and passes them in. ``None`` (the default) keeps the
    host-side ``spec._bucket_table()`` constants folded into the jit,
    bit-identical to the pre-override behaviour."""
    x = x.astype(jnp.int32)
    in_cache = state["in_cache"]
    count = state["count"]
    cap = jnp.int32(spec.capacity) if cap is None else jnp.asarray(cap, jnp.int32)
    fill = jnp.bool_(True) if fill is None else jnp.asarray(fill, jnp.bool_)
    if spec.capacity_bytes:
        cap_b = (
            jnp.int32(spec.capacity_bytes)
            if cap_bytes is None
            else jnp.asarray(cap_bytes, jnp.int32)
        )

    if spec.kind == "wlfu":
        # Slide the window *before* the hit test, as the reference does.
        freq, ring, ptr = state["freq"], state["ring"], state["ptr"]
        old = ring[ptr]
        freq = freq.at[jnp.maximum(old, 0)].add(jnp.where(old >= 0, -1, 0))
        ring = ring.at[ptr].set(x)
        ptr = (ptr + 1) % spec.window
        freq = freq.at[x].add(1)
        hit = in_cache[x]
        insert = (~hit) & fill
        if spec.capacity_bytes:
            size_x = _sz(sizes, x)
            in_cache, count, nbytes, _, _ = _evict_bytes_loop(
                spec, freq, in_cache, count, state["bytes"], size_x, insert, cap_b, sizes
            )
            insert = insert & (nbytes + size_x <= cap_b)
            in_cache = in_cache.at[x].set(in_cache[x] | insert)
            count = count + insert.astype(jnp.int32)
            nbytes = nbytes + jnp.where(insert, size_x, 0)
            return dict(
                in_cache=in_cache, count=count, freq=freq, ring=ring, ptr=ptr,
                bytes=nbytes, inserts=state["inserts"] + insert.astype(jnp.int32),
            ), hit
        need_evict = insert & (count >= cap)
        victim = _masked_argmin(freq, in_cache)
        in_cache = in_cache.at[victim].set(in_cache[victim] & ~need_evict)
        in_cache = in_cache.at[x].set(in_cache[x] | insert)
        count = count + insert.astype(jnp.int32) - need_evict.astype(jnp.int32)
        return dict(in_cache=in_cache, count=count, freq=freq, ring=ring, ptr=ptr), hit

    if spec.kind == "lru":
        last, t = state["last"], state["t"]
        hit = in_cache[x]
        insert = (~hit) & fill
        if spec.capacity_bytes:
            size_x = _sz(sizes, x)
            in_cache, count, nbytes, _, _ = _evict_bytes_loop(
                spec, last, in_cache, count, state["bytes"], size_x, insert, cap_b, sizes
            )
            insert = insert & (nbytes + size_x <= cap_b)
            in_cache = in_cache.at[x].set(in_cache[x] | insert)
            last = last.at[x].set(t)
            count = count + insert.astype(jnp.int32)
            nbytes = nbytes + jnp.where(insert, size_x, 0)
            return dict(
                in_cache=in_cache, count=count, last=last, t=t + 1,
                bytes=nbytes, inserts=state["inserts"] + insert.astype(jnp.int32),
            ), hit
        need_evict = insert & (count >= cap)
        victim = _masked_argmin(last, in_cache)
        in_cache = in_cache.at[victim].set(in_cache[victim] & ~need_evict)
        in_cache = in_cache.at[x].set(in_cache[x] | insert)
        last = last.at[x].set(t)
        count = count + insert.astype(jnp.int32) - need_evict.astype(jnp.int32)
        return dict(in_cache=in_cache, count=count, last=last, t=t + 1), hit

    if spec.kind == "arc":
        # Branch-free ARC mirroring policies.ARCCache case for case. Every
        # list operation is a masked write on the (lst, stamp) pair; list
        # sizes are mask sums, LRUs are masked stamp argmins.
        lst, stamp, p, t = state["lst"], state["stamp"], state["p"], state["t"]
        lx = lst[x]
        hit = (lx == 1) | (lx == 2)
        g1 = lx == 3
        g2 = lx == 4
        ghost = g1 | g2
        cold = lx == 0
        t1n = (lst == 1).sum().astype(jnp.int32)
        t2n = (lst == 2).sum().astype(jnp.int32)
        b1n = (lst == 3).sum().astype(jnp.int32)
        b2n = (lst == 4).sum().astype(jnp.int32)
        total = t1n + t2n + b1n + b2n
        # adaptation (ghost hits only, filled or not): a B1 hit grows the
        # recency target p, a B2 hit shrinks it — integer deltas
        d1 = jnp.maximum(1, b2n // jnp.maximum(1, b1n))
        d2 = jnp.maximum(1, b1n // jnp.maximum(1, b2n))
        p = jnp.where(
            g1, jnp.minimum(cap, p + d1), jnp.where(g2, jnp.maximum(0, p - d2), p)
        )
        # Case IV ghost trimming (cold misses only). Filled: IV(a) drops the
        # LRU of B1 when the recency side T1+B1 is at capacity (B1 empty ->
        # hard-drop T1's LRU instead, no ghost left behind), IV(b) drops the
        # LRU of B2 when the directory holds 2c entries. Unfilled: the same
        # trims make room to park x in B1, but a trim that would need a
        # *resident* eviction (IV(a) with B1 empty) skips parking entirely.
        caseA = cold & (t1n + b1n >= cap)
        hard_t1 = caseA & (b1n == 0) & fill
        park_skip = caseA & (b1n == 0) & (~fill)
        gone_b1 = caseA & (b1n > 0)
        gone_b2 = cold & (~caseA) & (total >= 2 * cap) & (b2n > 0)
        b1_lru = _masked_argmin(stamp, lst == 3)
        b2_lru = _masked_argmin(stamp, lst == 4)
        lst = lst.at[b1_lru].set(jnp.where(gone_b1, 0, lst[b1_lru]))
        lst = lst.at[b2_lru].set(jnp.where(gone_b2, 0, lst[b2_lru]))
        # REPLACE: a filled miss about to insert into a full cache demotes
        # the LRU of T1 (when |T1| > p, or == p on a B2 hit, or T2 is empty)
        # to B1's MRU, else T2's LRU to B2's MRU. Flat ARC is provably full
        # whenever it replaces, so the fullness guard is bit-neutral there;
        # under placement gating it stops evictions out of a non-full cache.
        need_evict = fill & (~hit) & (~hard_t1) & (t1n + t2n >= cap)
        from_t1 = (t1n >= 1) & ((g2 & (t1n == p)) | (t1n > p) | (t2n == 0))
        t1_lru = _masked_argmin(stamp, lst == 1)
        t2_lru = _masked_argmin(stamp, lst == 2)
        victim = jnp.where(hard_t1 | from_t1, t1_lru, t2_lru)
        evict = need_evict | hard_t1
        vdst = jnp.where(hard_t1, 0, jnp.where(from_t1, 3, 4))
        lst = lst.at[victim].set(jnp.where(evict, vdst, lst[victim]))
        stamp = stamp.at[victim].set(jnp.where(need_evict, t, stamp[victim]))
        # x's destination: any hit and every filled ghost hit land at T2's
        # MRU, a filled cold miss at T1's MRU; an unfilled ghost hit refreshes
        # in place (parked demand) and an unfilled cold miss parks in B1
        dst = jnp.where(
            hit | (ghost & fill),
            2,
            jnp.where(cold & fill, 1, jnp.where(ghost, lx, 3)),
        )
        write_x = ~park_skip
        lst = lst.at[x].set(jnp.where(write_x, dst, lst[x]))
        stamp = stamp.at[x].set(jnp.where(write_x, t, stamp[x]))
        in_cache = (lst == 1) | (lst == 2)
        count = in_cache.sum().astype(jnp.int32)
        return dict(
            in_cache=in_cache, count=count, lst=lst, stamp=stamp, p=p, t=t + 1
        ), hit

    if spec.kind == "tinylfu":
        # sketch first (add, then age), exactly as TinyLFUCache.request does
        freq, rows, seen = state["freq"], state["sketch"], state["seen"]
        if table is None:
            table = jnp.asarray(spec._bucket_table())
        idx = table[x]
        if spec.doorkeeper:
            # doorkeeper gate: first touch per window marks the bloom only;
            # the sketch increments from the second touch on. bloom_set is
            # idempotent, so the update stays branch-free.
            btab = jnp.asarray(spec._bloom_table()) if bloom_tab is None else bloom_tab
            bidx = btab[x]
            in_dk = sketch.bloom_contains(state["bloom"], bidx)
            rows = jnp.where(in_dk, sketch.rows_add(rows, idx), rows)
            bloom = sketch.bloom_set(state["bloom"], bidx)
        else:
            rows = sketch.rows_add(rows, idx)
        seen = seen + 1
        age = seen >= spec.effective_window
        rows = jnp.where(age, sketch.rows_halve(rows), rows)
        seen = jnp.where(age, 0, seen)
        if spec.doorkeeper:
            bloom = jnp.where(age, jnp.zeros_like(bloom), bloom)

        hit = in_cache[x]
        if spec.capacity_bytes:
            # byte mode: "full" means the object does not fit as-is; a full
            # duel win frees room via the bounded loop (empty cache = no
            # victim to duel, so an over-budget object is simply rejected)
            size_x = _sz(sizes, x)
            full = state["bytes"] + size_x > cap_b
        else:
            full = count >= cap
        victim = _masked_argmin(freq, in_cache)
        # admission duel: incoming vs victim, by (post-aging) sketch estimate,
        # with the doorkeeper'd occurrence added back when the front is on
        est_x = sketch.rows_estimate(rows, idx)
        est_v = sketch.rows_estimate(rows, table[victim])
        if spec.doorkeeper:
            est_x = est_x + sketch.bloom_contains(bloom, bidx).astype(jnp.int32)
            est_v = est_v + sketch.bloom_contains(bloom, btab[victim]).astype(jnp.int32)
        admit = est_x > est_v
        if spec.capacity_bytes:
            want = (~hit) & ((~full) | ((count > 0) & admit)) & fill
            in_cache, count, nbytes, freq, _ = _evict_bytes_loop(
                spec, freq, in_cache, count, state["bytes"], size_x, want, cap_b, sizes
            )
            insert = want & (nbytes + size_x <= cap_b)
            freq = freq.at[x].set(
                jnp.where(hit, freq[x] + 1, jnp.where(insert, 1, freq[x]))
            )
            in_cache = in_cache.at[x].set(in_cache[x] | insert)
            count = count + insert.astype(jnp.int32)
            nbytes = nbytes + jnp.where(insert, size_x, 0)
            out = dict(
                in_cache=in_cache, count=count, freq=freq, sketch=rows, seen=seen,
                inserts=state["inserts"] + insert.astype(jnp.int32), bytes=nbytes,
            )
            if spec.doorkeeper:
                out["bloom"] = bloom
            return out, hit
        insert = (~hit) & ((~full) | admit) & fill
        need_evict = (~hit) & full & admit & fill
        in_cache = in_cache.at[victim].set(in_cache[victim] & ~need_evict)
        # LFU eviction semantics: metadata dies with the victim, entry restarts at 1
        freq = freq.at[victim].set(jnp.where(need_evict, 0, freq[victim]))
        freq = freq.at[x].set(
            jnp.where(hit, freq[x] + 1, jnp.where(insert, 1, freq[x]))
        )
        in_cache = in_cache.at[x].set(in_cache[x] | insert)
        count = count + insert.astype(jnp.int32) - need_evict.astype(jnp.int32)
        inserts = state["inserts"] + insert.astype(jnp.int32)
        out = dict(
            in_cache=in_cache, count=count, freq=freq,
            sketch=rows, seen=seen, inserts=inserts,
        )
        if spec.doorkeeper:
            out["bloom"] = bloom
        return out, hit

    # frequency family: lfu / plfu / plfua / plfua_dyn / gdsf
    freq = state["freq"]
    hit = in_cache[x]
    if spec.kind == "plfua_dyn":
        # the step only feeds the sketch; hot-set recomputation is *global-time*
        # and lives at the chunk boundaries of _chunked_scan / refresh_hot, so
        # vmapped fleets never pay a per-step estimate-all + top-k
        rows = sketch.rows_add(
            state["sketch"],
            (jnp.asarray(spec._bucket_table()) if table is None else table)[x],
        )
        # dynamic hot gates admission only: a cached object keeps hitting (and
        # bumping) after it leaves the hot set, until PLFU eviction removes it
        admitted = state["hot"][x] | hit
    elif spec.kind == "plfua":
        admitted = state["hot"][x]
    else:
        admitted = jnp.bool_(True)
    want = (~hit) & admitted & fill
    # an unfilled admitted miss still bumps the parked frequency (demand
    # evidence for the tier); since PR 7 in-memory LFU parks too — only its
    # *eviction* destroys metadata (the PR 5 carve-out is gone, so `lcd`
    # promotes LFU objects with their accumulated counts)
    touch = hit | admitted
    if spec.kind == "gdsf":
        score, L = state["score"], state["L"]
    key = score if spec.kind == "gdsf" else freq
    if spec.capacity_bytes:
        size_x = _sz(sizes, x)
        in_cache, count, nbytes, key, credit = _evict_bytes_loop(
            spec, key, in_cache, count, state["bytes"], size_x, want, cap_b, sizes,
            L=state["L"] if spec.kind == "gdsf" else None,
        )
        if spec.kind == "lfu":
            freq = key  # the loop zeroed the evicted victims' metadata
        if spec.kind == "gdsf":
            L = credit
        insert = want & (nbytes + size_x <= cap_b)
        count = count + insert.astype(jnp.int32)
        nbytes = nbytes + jnp.where(insert, size_x, 0)
    else:
        need_evict = want & (count >= cap)
        victim = _masked_argmin(key, in_cache)
        if spec.kind == "gdsf":
            # the aging credit ratchets to the evicted victim's priority
            L = jnp.where(need_evict, score[victim], L)
        in_cache = in_cache.at[victim].set(in_cache[victim] & ~need_evict)
        if spec.kind == "lfu":
            # in-memory LFU: eviction destroys the metadata -> restart from 1
            freq = freq.at[victim].set(jnp.where(need_evict, 0, freq[victim]))
        insert = want
        count = count + insert.astype(jnp.int32) - need_evict.astype(jnp.int32)
    # PLFU/PLFUA/GDSF: freq[x] of a non-cached object *is* the parked-list
    # entry, so `freq[x] + 1` resumes from it; for LFU eviction zeroed it.
    freq = freq.at[x].set(jnp.where(touch, freq[x] + 1, freq[x]))
    if spec.kind == "gdsf":
        # re-price under the post-eviction L; a merely-parked touch writes a
        # score the next insert overwrites, so cached lanes never see it
        score = score.at[x].set(
            jnp.where(touch, L + ((freq[x] << GDSF_SHIFT) // _sz(sizes, x)), score[x])
        )
    in_cache = in_cache.at[x].set(in_cache[x] | insert)
    out = dict(in_cache=in_cache, count=count, freq=freq)
    if spec.kind == "gdsf":
        out.update(score=score, L=L)
    if spec.kind == "plfua":
        out["hot"] = state["hot"]
    if spec.kind == "plfua_dyn":
        out.update(hot=state["hot"], sketch=rows)
    if spec.kind == "plfua_dyn" or spec.capacity_bytes:
        out["inserts"] = state["inserts"] + insert.astype(jnp.int32)
    if spec.capacity_bytes:
        out["bytes"] = nbytes
    return out, hit


def refresh_hot(spec: PolicySpec, state: dict[str, jax.Array]) -> dict[str, jax.Array]:
    """plfua_dyn hot-set refresh: new mask = sketch top-k (est desc, ties to
    the lowest id — lax.top_k's order, matching the reference's lexsort), then
    halve the sketch so estimates stay recency-weighted (profile scope
    ``repro.refresh``, in every engine)."""
    with jax.named_scope("repro.refresh"):
        table = jnp.asarray(spec._bucket_table())
        est = sketch.rows_estimate_all(state["sketch"], table)
        _, top = jax.lax.top_k(est, spec.effective_hot)
        hot = jnp.zeros((spec.n_objects,), jnp.bool_).at[top].set(True)
        return {**state, "hot": hot, "sketch": sketch.rows_halve(state["sketch"])}


def _step_events(spec: PolicySpec, s, ns, hit, x, a, sizes=None, og=None):
    """Derive the telemetry events of one applied step from the state
    transition: a fill is a miss whose object ended up cached; the eviction
    *count* falls out of the occupancy delta (int32 — a byte-capacity step
    can evict several victims for one insert; in object-count mode this
    equals the old boolean event); a tinylfu aging event is the ``seen``
    reset (the counter just incremented, so 0 means the window closed). All
    masked by ``a`` so frozen (inactive / padded) steps emit nothing. With
    ``sizes`` the request's bytes are bucketed into hit/miss byte events.
    With ``og`` (the (n_objects, n_groups) int32 group one-hot) the step
    also emits the per-group victim counts and per-group occupancy the
    grouped series needs — the membership diff ``in_cache & ~in_cache'``
    is exactly the victims, so its group-sum matches ``evict``."""
    fill = a & (~hit) & ns["in_cache"][x]
    evict = (s["count"] - ns["count"]) + fill.astype(jnp.int32)
    ev = {"fill": fill, "evict": evict, "count": ns["count"]}
    if og is not None:
        vmask = s["in_cache"] & ~ns["in_cache"]
        ev["evict_g"] = vmask.astype(jnp.int32) @ og
        ev["count_g"] = ns["in_cache"].astype(jnp.int32) @ og
    if sizes is not None:
        sz = sizes[x]
        ev["hit_bytes"] = jnp.where(a & hit, sz, 0)
        ev["miss_bytes"] = jnp.where(a & (~hit), sz, 0)
    if spec.kind == "tinylfu":
        ev["aging"] = a & (ns["seen"] == 0)
    return ev


def _refresh_cell(spec: PolicySpec, cap, instrument, sizes, cap_bytes, og):
    """The scan bodies shared by :func:`_chunked_scan` (bounded, host-side
    fire schedule) and :func:`stream_chunked_scan` (unbounded, traced global
    time): a masked per-request ``step`` scan over one refresh chunk, then a
    per-chunk ``refresh_hot`` applied where the chunk's fire flag is set.
    The refresh sits under a ``lax.cond`` (the flag depends on the stream
    position alone, so vmapped samples and nodes share it), so a sub-chunk
    that ends no refresh period skips the estimate-all + top-k: a chunk
    whose length shares little with the period scans many of them.
    Keeping one cell guarantees the two drivers are the same program on the
    same inputs — the streaming equivalence tests pin exactly that."""

    def f(s, xa):
        x, a = xa
        ns, hit = step(spec, s, x, cap, sizes=sizes, cap_bytes=cap_bytes)
        ns = jax.tree_util.tree_map(lambda o, n_: jnp.where(a, n_, o), s, ns)
        if instrument:
            return ns, (hit & a, _step_events(spec, s, ns, hit, x, a, sizes, og))
        return ns, hit & a

    def chunk(s, inp):
        xs, acts, fire_c = inp
        s, out = jax.lax.scan(f, s, (xs, acts))
        refreshed = jax.lax.cond(
            fire_c, functools.partial(refresh_hot, spec), lambda s: s, s
        )
        if instrument:
            diff = s["hot"] != refreshed["hot"]  # all False where none fired
            chunk_ev = {"fired": fire_c, "churn": diff.sum().astype(jnp.int32)}
            if og is not None:
                chunk_ev["churn_g"] = diff.astype(jnp.int32) @ og
        s = refreshed
        if instrument:
            return s, (out, chunk_ev)
        return s, out

    return chunk


def _chunked_scan(
    spec: PolicySpec, state, trace, active=None, cap=None, instrument=False,
    sizes=None, cap_bytes=None, og=None,
):
    """plfua_dyn driver: scan refresh-length chunks of ``step`` with the hot
    mask frozen, then :func:`refresh_hot` at every chunk boundary.

    The refresh cadence is *global-time* (one refresh per ``effective_refresh``
    trace positions, whether or not this instance processed them — exactly a
    periodic wall-clock admission re-optimisation), which is what lets the
    expensive estimate-all + top-k run once per chunk instead of hiding inside
    a per-step ``cond`` that vmap would lower to always-on selects. ``active``
    masks out requests routed elsewhere (cdn) and the tail padding.

    With ``instrument`` (static) the scan additionally emits the telemetry
    event series — per-step fill/evict/count plus per-chunk refresh-fired and
    hot-churn — and returns ``(state, hits, events)``.
    """
    L = spec.effective_refresh
    (T,) = trace.shape
    n_chunks = -(-T // L)
    pad = n_chunks * L - T
    trace_p = jnp.concatenate([trace.astype(jnp.int32), jnp.zeros((pad,), jnp.int32)])
    if active is None:
        active = jnp.ones((T,), jnp.bool_)
    active_p = jnp.concatenate([active, jnp.zeros((pad,), jnp.bool_)])

    # a refresh fires only when its whole period lies within the real trace —
    # the padded tail chunk must not refresh, or the final hot/sketch state
    # would diverge from the reference whenever T % L != 0
    fire = (jnp.arange(n_chunks) + 1) * L <= T

    chunk = _refresh_cell(spec, cap, instrument, sizes, cap_bytes, og)
    state, out = jax.lax.scan(
        chunk,
        state,
        (trace_p.reshape(n_chunks, L), active_p.reshape(n_chunks, L), fire),
    )
    if not instrument:
        return state, out.reshape(-1)[:T]
    (hits, ev), chunk_ev = out
    # per-step events unpad to (T, ...); grouped events keep their trailing
    # group axis through the chunk flattening
    unpad = lambda arr: arr.reshape((-1,) + arr.shape[2:])[:T]
    events = {k: unpad(v) for k, v in ev.items()}
    events.update(chunk_ev)  # (n_chunks, ...) fired/churn stay chunk-shaped
    return state, unpad(hits), events


def stream_sub_len(spec: PolicySpec, chunk_len: int) -> int:
    """Refresh sub-chunk length of one streaming chunk: ``gcd(L, G)`` tiles
    any chunk length exactly, and every whole multiple of the refresh period
    ``L`` lands on a sub-chunk boundary — so the traced fire test in
    :func:`stream_chunked_scan` reproduces the bounded engine's refresh
    schedule for *any* chunk length, not just divisors of ``L``."""
    return math.gcd(spec.effective_refresh, chunk_len)


def stream_chunked_scan(
    spec: PolicySpec, state, trace, active=None, cap=None, *, t0,
    instrument=False, sizes=None, cap_bytes=None, og=None,
):
    """The unbounded-stream twin of :func:`_chunked_scan`: one fixed-shape
    chunk of a request stream whose global start position is the *traced*
    scalar ``t0``. Refresh boundaries are global-time — a sub-chunk ending at
    global position ``p`` refreshes iff ``p % effective_refresh == 0`` — so
    running K chunks of length G back to back is bit-identical to one
    bounded ``_chunked_scan`` over the concatenated trace (the same
    :func:`_refresh_cell` program, fed the same fire schedule).

    Returns ``(state, hits)`` or, with ``instrument``, ``(state, hits,
    events)`` where the chunk-shaped ``fired``/``churn`` events cover this
    chunk's ``G // stream_sub_len(spec, G)`` sub-chunks.
    """
    (G,) = trace.shape
    sub = stream_sub_len(spec, G)
    n_sub = G // sub
    if active is None:
        active = jnp.ones((G,), jnp.bool_)
    t0 = jnp.asarray(t0, jnp.int32)
    ends = t0 + (jnp.arange(n_sub, dtype=jnp.int32) + 1) * sub
    fire = ends % jnp.int32(spec.effective_refresh) == 0

    chunk = _refresh_cell(spec, cap, instrument, sizes, cap_bytes, og)
    state, out = jax.lax.scan(
        chunk,
        state,
        (
            trace.astype(jnp.int32).reshape(n_sub, sub),
            active.reshape(n_sub, sub),
            fire,
        ),
    )
    if not instrument:
        return state, out.reshape(-1)
    (hits, ev), chunk_ev = out
    flat = lambda arr: arr.reshape((-1,) + arr.shape[2:])
    events = {k: flat(v) for k, v in ev.items()}
    events.update(chunk_ev)  # (n_sub, ...) fired/churn stay sub-chunk-shaped
    return state, flat(hits), events


@functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
def run_chunk(spec: PolicySpec, state, trace, t0=0, sizes=None):
    """One donated streaming chunk of a flat cache: scan ``step`` over a
    fixed-shape trace chunk, *consuming* the carry buffers (``state`` is
    donated, so directory/sketch/ARC-list arrays round-trip in place instead
    of being copied every chunk). ``t0`` is the chunk's traced global start
    position — only plfua_dyn consults it (global-time refresh). Returns
    ``(new_state, hits)``; K calls over consecutive chunks are bit-identical
    to one :func:`simulate` over the concatenated trace.

    Note the donation contract: the caller must not reuse the ``state`` it
    passed in — time it with ``telemetry.measure(..., make_args=...)``, which
    re-materializes donated arguments per call."""
    if sizes is not None:
        sizes = jnp.asarray(sizes, jnp.int32)
    with jax.named_scope("repro.step"):
        if spec.kind == "plfua_dyn":
            return stream_chunked_scan(spec, state, trace, t0=t0, sizes=sizes)
        return jax.lax.scan(
            lambda s, x: step(spec, s, x, sizes=sizes), state, trace.astype(jnp.int32)
        )


def instrumented_scan(
    spec: PolicySpec, state, trace, active=None, cap=None, sizes=None,
    cap_bytes=None, og=None,
):
    """The telemetry-enabled twin of the plain ``lax.scan`` over ``step`` /
    the masked fleet scan: identical state trajectory and hit series, plus
    the per-step event series telemetry buckets (fill/evict/count, tinylfu
    aging, plfua_dyn chunk refresh/churn, hit/miss bytes when sized; with
    ``og`` — the (n_objects, n_groups) group one-hot — also per-group
    victim counts / occupancy / churn). Only compiled when a
    :class:`repro.telemetry.TelemetrySpec` is passed, so the disabled path
    stays byte-for-byte the uninstrumented program."""
    if spec.kind == "plfua_dyn":
        return _chunked_scan(
            spec, state, trace, active, cap, instrument=True,
            sizes=sizes, cap_bytes=cap_bytes, og=og,
        )
    if active is None:
        active = jnp.ones(trace.shape, jnp.bool_)

    def f(s, xa):
        x, a = xa
        ns, hit = step(spec, s, x, cap, sizes=sizes, cap_bytes=cap_bytes)
        ns = jax.tree_util.tree_map(lambda o, n_: jnp.where(a, n_, o), s, ns)
        return ns, (hit & a, _step_events(spec, s, ns, hit, x, a, sizes, og))

    state, (hits, events) = jax.lax.scan(f, state, (trace.astype(jnp.int32), active))
    return state, hits, events


def telemetry_series(
    spec: PolicySpec, telemetry, trace_len: int, hits, events, active=None,
    groups_t=None, chunk_len=None,
):
    """Bucket one node's event series into [..., n_windows, N_METRICS]
    (int32) under jit — or, when ``telemetry.n_groups > 0``, into the
    group-segmented [..., n_windows, n_groups, N_METRICS] layout
    (``groups_t`` = per-trace-position group ids required). ``active=None``
    is the flat-cache convention (every position is a request and every
    miss a fill offer). ``chunk_len`` overrides the length of the chunks
    that produced the chunk-shaped ``fired``/``churn`` events — streaming
    callers pass their gcd sub-chunk length; the default is the bounded
    plfua_dyn convention (one chunk per refresh period)."""
    if chunk_len is None:
        chunk_len = spec.effective_refresh if spec.kind == "plfua_dyn" else None
    if telemetry.n_groups:
        if groups_t is None:
            raise ValueError("telemetry.n_groups > 0 requires a groups catalogue")
        return telemetry_spec.grouped_series_from_run(
            telemetry.window,
            trace_len,
            telemetry.n_groups,
            groups_t,
            hits=hits,
            active=active,
            fills=events["fill"],
            evictions_g=events["evict_g"],
            occupancy_g=events["count_g"],
            aging=events.get("aging"),
            fired=events.get("fired"),
            churn_g=events.get("churn_g"),
            hit_bytes=events.get("hit_bytes"),
            miss_bytes=events.get("miss_bytes"),
            chunk_len=chunk_len,
            xp=jnp,
        )
    return telemetry_spec.series_from_run(
        telemetry.window,
        trace_len,
        hits=hits,
        active=active,
        fills=events["fill"],
        evictions=events["evict"],
        occupancy=events["count"],
        aging=events.get("aging"),
        fired=events.get("fired"),
        churn=events.get("churn"),
        hit_bytes=events.get("hit_bytes"),
        miss_bytes=events.get("miss_bytes"),
        chunk_len=chunk_len,
        xp=jnp,
    )


def group_scatter_arrays(telemetry, groups, trace):
    """(one-hot (N, G), per-position group ids (T,)) for a grouped run, or
    (None, None) when grouping is off. Raises if ``n_groups > 0`` but no
    catalogue was passed — a silent all-zero series would be worse."""
    if telemetry is None or not telemetry.n_groups:
        return None, None
    if groups is None:
        raise ValueError("telemetry.n_groups > 0 requires a groups catalogue")
    g = jnp.asarray(groups, jnp.int32)
    og = telemetry_spec.group_onehot(g, telemetry.n_groups, jnp)
    return og, g[trace.astype(jnp.int32)]


def _prefix_spec(spec: PolicySpec) -> PolicySpec | None:
    """The (H + 1)-object spec static plfua's bounded scan runs on, or
    ``None`` where the scan stays dense (another kind, or ``H >= N``)."""
    h = spec.effective_hot
    if spec.kind != "plfua" or h >= spec.n_objects:
        return None
    return dataclasses.replace(spec, n_objects=h + 1, hot_size=h)


def _prefix_scan(spec: PolicySpec, small: PolicySpec, trace, sizes):
    """:func:`simulate`'s scan on the admissible prefix (see its docstring
    for why it is exact): clamp the trace to ``min(x, H)``, scan ``step`` on
    ``small = _prefix_spec(spec)``, then pad ``in_cache``/``freq`` back with
    zeros for ids ``H..N-1`` and rebuild ``hot``. The clamp and the pad-back
    carry the profile scope ``repro.prefix``."""
    h = small.hot_size
    with jax.named_scope("repro.prefix"):
        trace = jnp.minimum(trace.astype(jnp.int32), h)
        if sizes is not None:
            sizes = sizes[: h + 1]
    with jax.named_scope("repro.step"):
        state, hits = jax.lax.scan(
            lambda s, x: step(small, s, x, sizes=sizes), init_state(small), trace
        )
    with jax.named_scope("repro.prefix"):
        pad = (0, spec.n_objects - h)
        state = {
            **state,
            "in_cache": jnp.pad(state["in_cache"][:h], pad),
            "freq": jnp.pad(state["freq"][:h], pad),
            "hot": jnp.arange(spec.n_objects, dtype=jnp.int32) < h,
        }
    return hits, state


@functools.partial(jax.jit, static_argnums=(0, 2))
def simulate(
    spec: PolicySpec, trace: jax.Array, telemetry=None, sizes=None, groups=None
):
    """Run a full trace. Returns (hits: bool[T], final_state), or with a
    static :class:`repro.telemetry.TelemetrySpec` third argument
    (hits, final_state, series[n_windows, N_METRICS]) — the windowed
    telemetry accumulated inside the scan (docs/observability.md).
    ``sizes`` is the per-object byte-size array (``None`` = unit sizes),
    consulted when ``spec.size_aware``; ``groups`` the per-object int32
    group catalogue consulted when ``telemetry.n_groups > 0`` (the series
    gains a group axis: [n_windows, n_groups, N_METRICS]).

    Static ``plfua`` with ``effective_hot = H < n_objects`` and no telemetry
    scans the admissible prefix alone (:func:`_prefix_scan`): ids ``>= H``
    are never admitted, so they never hit, are never inserted or evicted,
    and their ``freq`` is never touched (``touch = hit | admitted`` is
    false) — their slots stay ``(False, 0)`` for the whole run. The scan
    runs on ``n_objects = H + 1`` with the trace clamped to ``min(x, H)``:
    slot ``H`` stands for every such id and, with ``hot[H]`` false, is never
    admitted either. The final state is padded back to ``n_objects``, so
    hits and state are bit-identical to the dense scan. Every other kind,
    ``hot_size >= n_objects``, and the telemetry path run dense."""
    if sizes is not None:
        sizes = jnp.asarray(sizes, jnp.int32)
    small = None if telemetry is not None else _prefix_spec(spec)
    if small is not None:
        return _prefix_scan(spec, small, trace, sizes)
    state = init_state(spec)
    if telemetry is None:
        with jax.named_scope("repro.step"):
            if spec.kind == "plfua_dyn":
                state, hits = _chunked_scan(spec, state, trace, sizes=sizes)
            else:
                state, hits = jax.lax.scan(
                    lambda s, x: step(spec, s, x, sizes=sizes), state, trace
                )
        return hits, state
    og, groups_t = group_scatter_arrays(telemetry, groups, trace)
    with jax.named_scope("repro.step"):
        state, hits, events = instrumented_scan(spec, state, trace, sizes=sizes, og=og)
    series = telemetry_series(
        spec, telemetry, trace.shape[0], hits, events, groups_t=groups_t
    )
    return hits, state, series


@functools.partial(jax.jit, static_argnums=(0, 2))
def simulate_batch(
    spec: PolicySpec, traces: jax.Array, telemetry=None, sizes=None, groups=None
):
    """vmap over samples: traces (S, T) -> hits (S, T). The paper's 12-sample
    replication in one device launch. With ``telemetry`` set, returns
    (hits (S, T), series (S, n_windows, N_METRICS)) — plus a group axis
    before N_METRICS when ``telemetry.n_groups > 0``. ``sizes``/``groups``
    are shared across samples (one object universe)."""
    if telemetry is None:
        return jax.vmap(lambda tr: simulate(spec, tr, None, sizes)[0])(traces)
    out = jax.vmap(lambda tr: simulate(spec, tr, telemetry, sizes, groups))(traces)
    return out[0], out[2]


def chr_of(hits: jax.Array) -> jax.Array:
    return hits.mean(axis=-1)


def metadata_entries(spec: PolicySpec, state: dict[str, jax.Array]) -> jax.Array:
    """Live metadata entries, matching CachePolicy.metadata_entries semantics."""
    if spec.kind == "lru":
        return state["count"]
    if spec.kind == "arc":
        # residents (T1+T2) plus ghosts (B1+B2): the full ARC directory
        return (state["lst"] != 0).sum()
    if spec.kind == "wlfu":
        return (state["freq"] > 0).sum() + state["count"]
    if spec.kind == "lfu":
        # since PR 7 LFU parks demand from unfilled/unfit misses (eviction
        # still zeroes the victim, so flat runs keep metadata == occupancy)
        parked = ((state["freq"] > 0) & ~state["in_cache"]).sum()
        return state["count"] + parked
    if spec.kind == "tinylfu":
        return state["count"] + state["sketch"].size + spec.doorkeeper
    # plfu / plfua / plfua_dyn / gdsf: cached + parked entries (+ sketch)
    parked = ((state["freq"] > 0) & ~state["in_cache"]).sum()
    meta = state["count"] + parked
    if spec.kind == "plfua_dyn":
        meta = meta + state["sketch"].size
    return meta


def eviction_count(spec: PolicySpec, hits, trace, state) -> int:
    """Total evictions implied by one ``simulate`` run (host-side).

    Every admitted miss inserts, so evictions = inserts - final occupancy.
    Sketch kinds and byte-capacity runs carry the insert count in state
    (admission / fitting is data-dependent); for the others it is derivable
    from the hit sequence alone.
    """
    count = int(np.asarray(state["count"]))
    if spec.kind in SKETCH_POLICY_KINDS or spec.capacity_bytes:
        return int(np.asarray(state["inserts"])) - count
    hits = np.asarray(hits)
    if spec.kind == "plfua":
        hot = np.arange(spec.n_objects) < spec.effective_hot
        inserts = int((~hits & hot[np.asarray(trace)]).sum())
    else:
        inserts = int((~hits).sum())
    return inserts - count
