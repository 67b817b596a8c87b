"""Pallas TPU kernel: VMEM-resident cache-policy simulation — all 9 kinds.

The paper's experiment is 60 cases x 12 samples = 720 independent simulations
of a 100k-request trace. On TPU we map samples (same-shape sims) to the Pallas
grid; each program keeps the *entire* policy state — the dense ``freq`` table
(the LFU container + PLFU parked-list collapsed, see DESIGN.md §3), the
``in_cache`` mask, and for the sketch-admission policies the 4 x width
count-min rows, the doorkeeper bloom bits, and the dynamic hot mask — in VMEM
for the whole trace. For the paper's largest case (N = 100 000) the dense
state is ~0.9 MB and a default sketch adds 4 x 4C x 4 B, far under the ~16 MB
VMEM budget, so the inner loop never touches HBM except to stream the trace
block in.

TPU-native formulation (no gathers/scatters):
  * hit test     -> lane-wise compare against a broadcasted iota + mask AND +
                    any-reduction (VPU friendly),
  * eviction     -> masked argmin over the freq vector (ties: lowest id,
                    matching the reference implementation),
  * all updates  -> one-hot selects; the request id never indexes an array,
  * sketch touch -> the lowbias32 bucket tables are computed *inside* the
                    kernel from a broadcasted iota (pure uint32 arithmetic,
                    bit-identical to ``repro.core.sketch.bucket_table``), and
                    the per-step row scatter-increment is a one-hot add per
                    row — the id never indexes the count-min rows either.

``tinylfu`` runs the sketch-vs-victim admission duel (optional doorkeeper
bloom front) over LFU eviction; ``plfua_dyn`` hoists the hot-mask refresh out
of the inner step exactly like ``jax_cache._chunked_scan`` does: the trace is
walked in ``refresh``-length chunks with the hot mask frozen, and the
estimate-all + top-k selection runs once per chunk boundary (global-time
cadence — a partial tail chunk never fires). The top-k is sort-free: two
bisections (the k-th largest estimate, then the id cut among its ties)
select exactly ``lax.top_k``'s set (estimate desc, ties to the lowest id).

Layout (what Mosaic accepts for a TPU): every per-id row is a dense
``(n_pad // 128, 128)`` tile array (id = sublane * 128 + lane, n_pad a
multiple of 8 * 128), and so are the sketch rows, the bloom bits and the wlfu
ring; per-sample blocks squeeze the leading sample dim. The trace is a dense
``(T_pad // 128, 128)`` block too, read one aligned (8, 128) tile per step
with the id picked by a one-hot sum (a dynamic scalar load from VMEM does not
lower). Bool state is carried through loops as int32 (:func:`_fori`), the
argmin is ``min`` + lowest iota at the min, and ``hits`` leaves via SMEM.

PR 7 additions: the ``gdsf`` kind (score row ``L + (freq << GDSF_SHIFT) //
size`` with the aging credit ``L`` as a scalar carry) and *byte-capacity*
mode for the base-step family (lru/lfu/plfu/plfua/plfua_dyn/gdsf): per-object
sizes arrive as a second, grid-shared per-id input (padding lanes are
size 1) and one insertion runs a bounded multi-victim eviction loop — at most
``max_victims`` masked argmins — mirroring ``jax_cache.step`` decision for
decision. ``wlfu``/``tinylfu`` under a byte budget are a JAX-scan-only
combination (``cache_sim_pallas`` raises).

The ``arc`` kind: the four ARC lists live as one per-id ``lst``
row (0 = untracked, 1 = T1, 2 = T2, 3 = B1, 4 = B2) plus a ``stamp`` row of
last-touch times: list sizes are lane-sums over ``lst == L``, each list's LRU
is a masked argmin over ``stamp``, and the adaptation target ``p`` is a
scalar carry — the same encoding as the jitted scan, decision for decision.
The final ``stamp`` row ships through the ``freq`` output slot (exactly like
lru's recency stamps) and ``(lst == 1) | (lst == 2)`` through the cache mask.
``arc`` under a byte budget is unsupported everywhere (the spec raises).

PR 8: group-segmented telemetry. With ``n_groups=G`` (static) and a
grid-shared id -> group catalogue row, the windowed accumulator stacks one
16-row metric block per group (row = g*16 + m): request-attributed metrics
scatter into the requester's block at the dynamic row ``gx*16 + m`` and the
membership-attributed events (evictions, occupancy, hot churn) are per-group
lane-sums over a static Python loop — the kernel-shaped spelling of the jax
tier's one-hot group matmuls, summing over groups to the ungrouped series
bit for bit. The n_groups=0 program is unchanged.

The only dynamic access is the aligned trace-tile load per step.
Every kind in ``repro.core.registry`` is implemented here; differential
parity against both ``jax_cache.simulate`` and the pure-Python references is
asserted in tests/test_kernels_cache_sim.py and tests/test_differential.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import registry, sketch
from repro.telemetry import spec as telemetry_spec

_I32_MAX = np.iinfo(np.int32).max

KERNEL_KINDS = registry.names(pallas=True)
_SKETCH_KINDS = registry.names(sketch=True)

_GDSF_SHIFT = registry.GDSF_SHIFT

#: byte-capacity on the Pallas tier covers the base-step family; the ring/
#: sketch-admission kinds under a byte budget are a JAX-scan-only combination
#: and arc rejects byte mode in every tier (see PolicySpec / ARCCache)
BYTE_CAPABLE_KINDS = tuple(
    k for k in KERNEL_KINDS if k not in ("wlfu", "tinylfu", "arc")
)

# telemetry output rows: METRICS padded up to a TPU-friendly sublane count
_TEL_ROWS = 16
assert telemetry_spec.N_METRICS <= _TEL_ROWS


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _tile_rows(n: int) -> int:
    """Rows of the dense (rows, 128) layout that hold ``n`` lanes, rounded to
    whole (8, 128) tiles."""
    return _round_up(max(n, 1), 8 * 128) // 128


def _dense_iota(rows: int):
    """(rows, 128) int32 flat index, sublane * 128 + lane."""
    shape = (rows, 128)
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0) * 128 + (
        jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    )


def _fori(lo, hi, body, init):
    """``lax.fori_loop`` that carries bool leaves as int32 (Mosaic cannot
    carry i1 vectors through a loop); ``body`` sees and returns bools."""
    leaves, tree = jax.tree.flatten(init)
    is_bool = [leaf.dtype == jnp.bool_ for leaf in leaves]

    def enc(c):
        ls = jax.tree.leaves(c)
        return [l.astype(jnp.int32) if b else l for l, b in zip(ls, is_bool)]

    def dec(ls):
        return jax.tree.unflatten(
            tree, [l != 0 if b else l for l, b in zip(ls, is_bool)]
        )

    return dec(jax.lax.fori_loop(lo, hi, lambda t, c: enc(body(t, dec(c))), enc(init)))


def _pick(c, a, b):
    """``jnp.where`` over bool operands (Mosaic has no select of i1 vectors)."""
    return (c & a) | (~c & b)


def _bucket_rows(iota_u32, salts, width: int):
    """Per-row lowbias32 bucket tables, computed in-kernel.

    ``iota_u32``: per-id uint32 iota. Returns one int32 table of the same
    shape per salt — identical bits to ``sketch.bucket_table`` /
    ``sketch.bloom_table`` because the arithmetic is uint32-only.
    """
    u = jnp.uint32
    return [
        (sketch._mix32((iota_u32 + u(1)) * u(salt), jnp) % u(width)).astype(jnp.int32)
        for salt in salts
    ]


def _lane_pick(onehot, table):
    """table[x] without indexing: sum over the one-hot lane. Scalar int32."""
    return jnp.sum(jnp.where(onehot, table, 0))


def _rows_add(rows, w_iota, idx, inc):
    """One-hot scatter-increment: rows[d][idx[d]] += inc (inc: scalar bool)."""
    return [
        r + ((w_iota == i) & inc).astype(jnp.int32) for r, i in zip(rows, idx)
    ]


def _rows_estimate(rows, w_iota, idx):
    """Count-min point estimate: min over rows of the addressed counter."""
    est = _lane_pick(w_iota == idx[0], rows[0])
    for d in range(1, len(rows)):
        est = jnp.minimum(est, _lane_pick(w_iota == idx[d], rows[d]))
    return est


def _bloom_contains(bloom, b_iota, bidx):
    """All BLOOM_DEPTH addressed bits set (scalar bool)."""
    got = jnp.any((b_iota == bidx[0]) & bloom)
    for d in range(1, len(bidx)):
        got = got & jnp.any((b_iota == bidx[d]) & bloom)
    return got


def _bloom_set(bloom, b_iota, bidx):
    marks = b_iota == bidx[0]
    for d in range(1, len(bidx)):
        marks = marks | (b_iota == bidx[d])
    return bloom | marks


def _top_k_mask(est, k: int, iota):
    """Mask of ``lax.top_k(est, k)``'s ids without a sort: estimate desc,
    ties to the lowest id. Valid estimates are >= 0 and padding lanes hold
    -1, so they are never picked while k <= the valid count.

    Two bisections of fixed length: v = the k-th largest estimate (the
    largest v with #(est >= v) >= k), then the id cut m among the ties at v
    (the least m with #(est == v, id < m) >= k - #(est > v)). The mask is
    ``est > v | (est == v & id < m)``."""
    if k == 0:
        return jnp.zeros(est.shape, jnp.bool_)
    count = lambda m: jnp.sum(m.astype(jnp.int32))

    def bisect_v(_, lh):  # invariant: #(est >= lo) >= k > #(est >= hi)
        lo, hi = lh
        mid = lo + (hi - lo) // 2
        ok = count(est >= mid) >= k
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    v, _ = jax.lax.fori_loop(0, 32, bisect_v, (jnp.int32(0), jnp.max(est) + 1))
    gt = est > v
    tie = est == v
    need = k - count(gt)

    def bisect_m(_, lh):  # invariant: #(tie, id < lo) < need <= #(tie, id < hi)
        lo, hi = lh
        mid = lo + (hi - lo) // 2
        ok = count(tie & (iota < mid)) >= need
        return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

    n_lanes = est.size
    _, m = jax.lax.fori_loop(
        0, n_lanes.bit_length() + 1, bisect_m, (jnp.int32(0), jnp.int32(n_lanes))
    )
    return gt | (tie & (iota < m))


def _refresh_hot(rows, tables, *, width: int, n_objects: int, hot_k: int, iota):
    """plfua_dyn chunk-boundary refresh: hot mask = sketch top-``hot_k``.

    Estimate-all without a gather: per sketch row, a walk over the ``width``
    counters writes each counter's value into the ids hashed to it (one
    lane-pick and one select per counter). The top-k is :func:`_top_k_mask`,
    the same set as ``jax_cache.refresh_hot``'s ``lax.top_k``. Padding lanes
    get estimate -1. Returns (hot bool mask, halved rows).
    """
    w_iota = _dense_iota(rows[0].shape[0])
    est = None
    for row, tbl in zip(rows, tables):

        def put(c, e, row=row, tbl=tbl):
            return jnp.where(tbl == c, _lane_pick(w_iota == c, row), e)

        est_d = jax.lax.fori_loop(0, width, put, jnp.zeros_like(iota))
        est = est_d if est is None else jnp.minimum(est, est_d)
    est = jnp.where(iota < n_objects, est, -1)
    return _top_k_mask(est, hot_k, iota), [r >> 1 for r in rows]


def _cache_sim_kernel(
    *refs,  # trace, [sizes iff size-aware], [groups iff grouped], outs, [tel out]
    kind: str,
    capacity: int,
    hot_size: int,
    window: int,
    refresh: int,
    sketch_width: int,
    doorkeeper: int,
    n_objects: int,
    n_pad: int,
    trace_len: int,
    telemetry_window: int = 0,
    n_w_pad: int = 0,
    capacity_bytes: int = 0,
    max_victims: int = 0,
    n_groups: int = 0,
):
    BYTES = capacity_bytes > 0
    SIZED = BYTES or kind == "gdsf"
    GROUPED = telemetry_window > 0 and n_groups > 0
    trace_ref = refs[0]  # (T_pad // 128, 128) int32 VMEM
    i = 1
    if SIZED:
        sizes_ref = refs[i]  # per-id int32 VMEM, grid-shared; padding = 1
        i += 1
    if GROUPED:
        groups_ref = refs[i]  # per-id int32 VMEM, grid-shared; padding = 0
        i += 1
    hits_ref = refs[i]  # (1, 1) int32 SMEM out
    freq_ref = refs[i + 1]  # per-id int32 VMEM out (lru: last-access stamps)
    cache_ref = refs[i + 2]  # per-id int32 VMEM out (0/1 mask)
    tel_refs = refs[i + 3 :]  # (ROWS, n_w_pad) out, iff telemetry_window

    iota = _dense_iota(n_pad // 128)
    iota_u32 = iota.astype(jnp.uint32)
    tile_iota = _dense_iota(8)

    def request(t):
        """trace[t]: the aligned (8, 128) tile holding t, one-hot picked."""
        tile = trace_ref[pl.ds(pl.multiple_of((t // 1024) * 8, 8), 8), :]
        return _lane_pick(tile_iota == t % 1024, tile)

    if SIZED:
        sizes_row = sizes_ref[...]
    if GROUPED:
        groups_row = groups_ref[...]

    TEL = telemetry_window > 0
    if TEL:
        W = telemetry_window
        n_w = -(-trace_len // W)
        # grouped layout stacks one _TEL_ROWS block per group: row = g*16 + m
        ROWS = _TEL_ROWS * (n_groups if GROUPED else 1)
        m_iota = jax.lax.broadcasted_iota(jnp.int32, (ROWS, 1), 0)
        nw_iota = jax.lax.broadcasted_iota(jnp.int32, (1, n_w_pad), 1)
        _row = lambda i: (m_iota == i).astype(jnp.int32)

        def tel_update(
            tel, t, *, hit, fill, evict, count, aging=None, active=None, sz=None,
            evict_mask=None, cache_mask=None, gx=None,
        ):
            """Scatter one step's events into the windowed accumulator via a
            one-hot window column (metric row order = telemetry_spec.METRICS;
            occupancy is a set-at-window-end, everything else an add).
            ``evict`` may be a bool (object mode) or an int32 victim count
            (byte mode); ``sz`` is the request's byte size (1 when unsized,
            matching the jax tier's unit fallback). Under GROUPED the
            request-attributed metrics land in the requester's row block at
            the dynamic row ``gx*16 + m`` while evictions / occupancy are
            membership-attributed from ``evict_mask`` / ``cache_mask`` via a
            static per-group lane-sum loop — exactly the jax tier's
            ``evict_g`` / ``count_g`` one-hot matmuls."""
            act = jnp.bool_(True) if active is None else active
            i32 = lambda b: (b & act).astype(jnp.int32)
            szv = jnp.int32(1) if sz is None else sz
            won = nw_iota == jnp.minimum(t // W, n_w - 1)
            if GROUPED:
                grow = lambda m: (m_iota == gx * _TEL_ROWS + m).astype(jnp.int32)
                inc = (
                    grow(0) * i32(jnp.bool_(True))  # requests
                    + grow(1) * i32(hit)  # hits
                    + grow(2) * i32(~hit)  # misses
                    + grow(3) * i32(fill)  # fills
                    + grow(5) * i32(~hit)  # fill_offers: flat cache, every miss
                    + grow(9) * (szv * i32(hit))  # hit_bytes
                    + grow(10) * (szv * i32(~hit))  # miss_bytes
                )
                if aging is not None:
                    inc = inc + grow(7) * i32(aging)  # refreshes (tinylfu aging)
                acti = act.astype(jnp.int32)
                for g in range(n_groups):
                    in_g = groups_row == g
                    ev_g = jnp.sum((evict_mask & in_g).astype(jnp.int32))
                    inc = inc + _row(g * _TEL_ROWS + 4) * (ev_g * acti)
                tel = tel + inc * won.astype(jnp.int32)
                is_end = act & (((t + 1) % W == 0) | (t == trace_len - 1))
                for g in range(n_groups):
                    cnt_g = jnp.sum((cache_mask & (groups_row == g)).astype(jnp.int32))
                    tel = jnp.where(
                        (m_iota == g * _TEL_ROWS + 6) & won & is_end, cnt_g, tel
                    )
                return tel
            inc = (
                _row(0) * i32(jnp.bool_(True))  # requests
                + _row(1) * i32(hit)  # hits
                + _row(2) * i32(~hit)  # misses
                + _row(3) * i32(fill)  # fills
                + _row(4) * (jnp.asarray(evict).astype(jnp.int32) * i32(jnp.bool_(True)))  # evictions
                + _row(5) * i32(~hit)  # fill_offers: flat cache, every miss
                + _row(9) * (szv * i32(hit))  # hit_bytes
                + _row(10) * (szv * i32(~hit))  # miss_bytes
            )
            if aging is not None:
                inc = inc + _row(7) * i32(aging)  # refreshes (tinylfu aging)
            tel = tel + inc * won.astype(jnp.int32)
            is_end = act & (((t + 1) % W == 0) | (t == trace_len - 1))
            tel = jnp.where((m_iota == 6) & won & is_end, count, tel)  # occupancy
            return tel

    sketchy = kind in _SKETCH_KINDS
    if sketchy:
        w_iota = _dense_iota(_tile_rows(sketch_width))
        tables = _bucket_rows(iota_u32, sketch._SALTS, sketch_width)
        rows0 = [jnp.zeros(w_iota.shape, jnp.int32) for _ in sketch._SALTS]
    if kind == "tinylfu" and doorkeeper:
        b_iota = _dense_iota(_tile_rows(doorkeeper))
        btables = _bucket_rows(iota_u32, sketch._BLOOM_SALTS, doorkeeper)
    if kind == "wlfu":
        r_iota = _dense_iota(_tile_rows(window))

    def victim_of(keyrow, member):
        """One-hot of the lowest id minimising ``keyrow`` over ``member``:
        ``min`` then the lowest iota at the min (Mosaic lowers argmin for
        float32 only). An empty ``member`` gives id 0, as argmin would."""
        scores = jnp.where(member, keyrow, _I32_MAX)
        low = jnp.min(scores)
        return iota == jnp.min(jnp.where(scores == low, iota, _I32_MAX))

    # ---------------------------------------------------------------- steps
    def base_step(t, carry, active=None):
        """lru / lfu / plfu / plfua / plfua_dyn / gdsf one-hot step. The
        carry is (freq, in_cache, count, hits) + per-kind extras in a fixed
        order: gdsf appends (score, L), plfua_dyn appends (rows, hot), byte
        mode appends (nbytes,); with telemetry the windowed accumulator
        rides last in every driver. ``active`` masks tail padding of the
        chunked plfua_dyn walk."""
        if TEL:
            *carry, tel = carry
        freq, in_cache, count, hits = carry[0], carry[1], carry[2], carry[3]
        j = 4
        if kind == "gdsf":
            score, credit = carry[j], carry[j + 1]
            j += 2
        if kind == "plfua_dyn":
            rows, hot = carry[j], carry[j + 1]
            j += 2
        if BYTES:
            nbytes = carry[j]
        x = request(jnp.minimum(t, trace_len - 1))
        onehot = iota == x
        hit = jnp.any(onehot & in_cache)
        if SIZED:
            size_x = _lane_pick(onehot, sizes_row)
        if GROUPED:
            gx = _lane_pick(onehot, groups_row)

        if kind == "plfua_dyn":
            idx = [_lane_pick(onehot, tbl) for tbl in tables]
            new_rows = _rows_add(rows, w_iota, idx, jnp.bool_(True))
            admitted = jnp.any(onehot & hot) | hit
        elif kind == "plfua":
            admitted = x < hot_size
        else:
            admitted = jnp.bool_(True)
        touch = hit | admitted
        want = (~hit) & admitted
        key = score if kind == "gdsf" else freq

        if BYTES:
            # bounded multi-victim eviction until x fits (mirrors the jitted
            # scan's _evict_bytes_loop / the reference's _room_for exactly):
            # an object larger than the whole budget evicts nothing
            fits_ever = size_x <= capacity_bytes

            def evict_body(_, c):
                ic, cnt, nb, keyrow, cr = c
                need = want & fits_ever & (nb + size_x > capacity_bytes) & (cnt > 0)
                v_oh = victim_of(keyrow, ic)
                if kind == "gdsf":
                    cr = jnp.where(need, _lane_pick(v_oh, keyrow), cr)
                ic = ic & ~(v_oh & need)
                cnt = cnt - need.astype(jnp.int32)
                nb = nb - jnp.where(need, _lane_pick(v_oh, sizes_row), 0)
                if kind == "lfu":
                    # in-memory LFU destroys metadata on eviction
                    keyrow = jnp.where(v_oh & need, 0, keyrow)
                return ic, cnt, nb, keyrow, cr

            new_in_cache, new_count, nb, key, cr = _fori(
                0,
                max_victims,
                evict_body,
                (in_cache, count, nbytes, key,
                 credit if kind == "gdsf" else jnp.int32(0)),
            )
            if kind == "gdsf":
                new_credit = cr
            insert = want & (nb + size_x <= capacity_bytes)
            new_nbytes = nb + jnp.where(insert, size_x, 0)
            new_freq = key if kind == "lfu" else freq
            need_evict_n = count - new_count  # victims this step (int32)
            new_count = new_count + insert.astype(jnp.int32)
        else:
            need_evict = want & (count >= capacity)
            victim_onehot = victim_of(key, in_cache)
            if kind == "gdsf":
                # the aging credit ratchets to the evicted victim's priority
                new_credit = jnp.where(
                    need_evict, _lane_pick(victim_onehot, score), credit
                )
            new_in_cache = in_cache & ~(victim_onehot & need_evict)
            new_freq = freq
            if kind == "lfu":
                # in-memory LFU destroys metadata on eviction -> restart at 1
                new_freq = jnp.where(victim_onehot & need_evict, 0, new_freq)
            insert = want
            new_count = count + insert.astype(jnp.int32) - need_evict.astype(jnp.int32)
            need_evict_n = need_evict

        if kind == "lru":
            # recency eviction: "freq" holds last-access stamps (t+1; 0 = never)
            new_freq = jnp.where(onehot & touch, t + 1, new_freq)
        else:
            # PLFU/PLFUA/GDSF: untouched freq of an evicted id *is* the
            # parked-list entry (since PR 7 in-memory LFU parks too; only
            # its eviction zeroes the entry — see the zeroing above)
            new_freq = jnp.where(onehot & touch, new_freq + 1, new_freq)
        if kind == "gdsf":
            # re-price under the post-eviction credit, from the bumped freq
            fx = _lane_pick(onehot, new_freq)
            new_score = jnp.where(
                onehot & touch,
                new_credit + ((fx << _GDSF_SHIFT) // size_x),
                key,
            )
        new_in_cache = new_in_cache | (onehot & insert)
        if TEL:
            gargs = (
                # victims = membership lost this step (insert only ever adds
                # the missed id's lane, so the diff is exactly the evictions)
                dict(evict_mask=in_cache & ~new_in_cache,
                     cache_mask=new_in_cache, gx=gx)
                if GROUPED
                else {}
            )
            tel = tel_update(
                tel, t, hit=hit, fill=insert, evict=need_evict_n,
                count=new_count, active=active, sz=size_x if SIZED else None,
                **gargs,
            )
        if active is not None:
            new_freq = jnp.where(active, new_freq, freq)
            new_in_cache = _pick(active, new_in_cache, in_cache)
            new_count = jnp.where(active, new_count, count)
            if kind == "gdsf":
                new_score = jnp.where(active, new_score, score)
                new_credit = jnp.where(active, new_credit, credit)
            if BYTES:
                new_nbytes = jnp.where(active, new_nbytes, nbytes)
            hit = hit & active
        hits = hits + hit.astype(jnp.int32)
        out = (new_freq, new_in_cache, new_count, hits)
        if kind == "gdsf":
            out = out + (new_score, new_credit)
        if kind == "plfua_dyn":
            if active is not None:
                new_rows = [
                    jnp.where(active, nr, r) for nr, r in zip(new_rows, rows)
                ]
            out = out + (new_rows, hot)
        if BYTES:
            out = out + (new_nbytes,)
        return out + (tel,) if TEL else out

    def wlfu_step(t, carry):
        if TEL:
            *carry, tel = carry
        freq, in_cache, count, hits, ring, ptr = carry
        x = request(t)
        onehot = iota == x
        # slide the window *before* the hit test, as the reference does
        ptr_onehot = r_iota == ptr
        old = jnp.sum(jnp.where(ptr_onehot, ring, 0))
        freq = freq - ((iota == old) & (old >= 0)).astype(jnp.int32)
        ring = jnp.where(ptr_onehot, x, ring)
        ptr = (ptr + 1) % window
        freq = freq + onehot.astype(jnp.int32)

        hit = jnp.any(onehot & in_cache)
        need_evict = (~hit) & (count >= capacity)
        victim_onehot = victim_of(freq, in_cache)
        prev_cache = in_cache
        in_cache = (in_cache & ~(victim_onehot & need_evict)) | onehot
        count = count + (~hit).astype(jnp.int32) - need_evict.astype(jnp.int32)
        hits = hits + hit.astype(jnp.int32)
        if TEL:
            gargs = (
                dict(evict_mask=prev_cache & ~in_cache, cache_mask=in_cache,
                     gx=_lane_pick(onehot, groups_row))
                if GROUPED
                else {}
            )
            tel = tel_update(
                tel, t, hit=hit, fill=~hit, evict=need_evict, count=count, **gargs
            )
            return freq, in_cache, count, hits, ring, ptr, tel
        return freq, in_cache, count, hits, ring, ptr

    def tinylfu_step(t, carry):
        if TEL:
            *carry, tel = carry
        if doorkeeper:
            freq, in_cache, count, hits, rows, seen, bloom = carry
        else:
            freq, in_cache, count, hits, rows, seen = carry
        x = request(t)
        onehot = iota == x
        idx = [_lane_pick(onehot, tbl) for tbl in tables]
        # sketch first (add, then age), exactly as TinyLFUCache.request does
        if doorkeeper:
            # doorkeeper gate: first touch per window marks the bloom only;
            # the sketch increments from the second touch on
            bidx = [_lane_pick(onehot, tbl) for tbl in btables]
            in_dk = _bloom_contains(bloom, b_iota, bidx)
            rows = _rows_add(rows, w_iota, idx, in_dk)
            bloom = _bloom_set(bloom, b_iota, bidx)
        else:
            rows = _rows_add(rows, w_iota, idx, jnp.bool_(True))
        seen = seen + 1
        age = seen >= window
        rows = [jnp.where(age, r >> 1, r) for r in rows]
        seen = jnp.where(age, 0, seen)
        if doorkeeper:
            bloom = bloom & ~age

        hit = jnp.any(onehot & in_cache)
        full = count >= capacity
        victim_onehot = victim_of(freq, in_cache)
        vidx = [_lane_pick(victim_onehot, tbl) for tbl in tables]
        # admission duel: incoming vs victim, by (post-aging) sketch estimate,
        # with the doorkeeper'd occurrence added back when the front is on
        est_x = _rows_estimate(rows, w_iota, idx)
        est_v = _rows_estimate(rows, w_iota, vidx)
        if doorkeeper:
            vbidx = [_lane_pick(victim_onehot, tbl) for tbl in btables]
            est_x = est_x + _bloom_contains(bloom, b_iota, bidx).astype(jnp.int32)
            est_v = est_v + _bloom_contains(bloom, b_iota, vbidx).astype(jnp.int32)
        admit = est_x > est_v
        insert = (~hit) & ((~full) | admit)
        need_evict = (~hit) & full & admit
        prev_cache = in_cache
        in_cache = (in_cache & ~(victim_onehot & need_evict)) | (onehot & insert)
        # LFU eviction semantics: metadata dies with the victim, entry restarts at 1
        freq = jnp.where(victim_onehot & need_evict, 0, freq)
        freq = jnp.where(
            onehot,
            jnp.where(hit, freq + 1, jnp.where(insert, 1, freq)),
            freq,
        )
        count = count + insert.astype(jnp.int32) - need_evict.astype(jnp.int32)
        hits = hits + hit.astype(jnp.int32)
        if TEL:
            gargs = (
                dict(evict_mask=prev_cache & ~in_cache, cache_mask=in_cache,
                     gx=_lane_pick(onehot, groups_row))
                if GROUPED
                else {}
            )
            tel = tel_update(
                tel, t, hit=hit, fill=insert, evict=need_evict, count=count,
                aging=age, **gargs
            )
        out = (
            (freq, in_cache, count, hits, rows, seen, bloom)
            if doorkeeper
            else (freq, in_cache, count, hits, rows, seen)
        )
        return out + (tel,) if TEL else out

    def arc_step(t, carry):
        """Branch-free ARC, mirroring ``jax_cache.step`` lane for lane. The
        carry is (stamp, in_cache, count, hits, lst, p): ``stamp`` rides in
        the freq slot of the shared epilogue and ``in_cache`` is re-derived
        from ``lst`` every step so the standard (freq, in_cache, count, hits)
        prefix holds. The kernel is the flat cache (no placement gating), so
        the jitted scan's unfilled park/refresh paths are compile-time off."""
        if TEL:
            *carry, tel = carry
        stamp, in_cache, count, hits, lst, p = carry
        x = request(t)
        onehot = iota == x
        lx = _lane_pick(onehot, lst)
        hit = (lx == 1) | (lx == 2)
        g2 = lx == 4
        ghost = (lx == 3) | g2
        cold = lx == 0
        t1n = jnp.sum((lst == 1).astype(jnp.int32))
        t2n = jnp.sum((lst == 2).astype(jnp.int32))
        b1n = jnp.sum((lst == 3).astype(jnp.int32))
        b2n = jnp.sum((lst == 4).astype(jnp.int32))
        total = t1n + t2n + b1n + b2n
        # adaptation (ghost hits only): a B1 hit grows the recency target p,
        # a B2 hit shrinks it — integer deltas, exactly the jitted scan's
        d1 = jnp.maximum(1, b2n // jnp.maximum(1, b1n))
        d2 = jnp.maximum(1, b1n // jnp.maximum(1, b2n))
        p = jnp.where(
            lx == 3,
            jnp.minimum(capacity, p + d1),
            jnp.where(g2, jnp.maximum(0, p - d2), p),
        )
        # Case IV ghost trimming (cold misses): IV(a) drops B1's LRU when the
        # recency side T1+B1 is at capacity (B1 empty -> hard-drop T1's LRU,
        # no ghost left behind), IV(b) drops B2's LRU at 2c directory entries
        caseA = cold & (t1n + b1n >= capacity)
        hard_t1 = caseA & (b1n == 0)
        gone_b1 = caseA & (b1n > 0)
        gone_b2 = cold & (~caseA) & (total >= 2 * capacity) & (b2n > 0)
        list_lru = lambda L: victim_of(stamp, lst == L)
        b1_oh = list_lru(3)
        b2_oh = list_lru(4)
        lst = jnp.where((b1_oh & gone_b1) | (b2_oh & gone_b2), 0, lst)
        # REPLACE: a miss into a full cache demotes T1's LRU (|T1| > p, or
        # == p on a B2 hit, or T2 empty) to B1's MRU, else T2's LRU to B2's
        need_evict = (~hit) & (~hard_t1) & (t1n + t2n >= capacity)
        from_t1 = (t1n >= 1) & ((g2 & (t1n == p)) | (t1n > p) | (t2n == 0))
        victim_oh = _pick(hard_t1 | from_t1, list_lru(1), list_lru(2))
        evict = need_evict | hard_t1
        vdst = jnp.where(hard_t1, 0, jnp.where(from_t1, 3, 4))
        lst = jnp.where(victim_oh & evict, vdst, lst)
        stamp = jnp.where(victim_oh & need_evict, t, stamp)
        # x lands at T2's MRU on any hit or ghost hit, T1's MRU on a cold miss
        dst = jnp.where(hit | ghost, 2, 1)
        lst = jnp.where(onehot, dst, lst)
        stamp = jnp.where(onehot, t, stamp)
        prev_cache = in_cache
        in_cache = (lst == 1) | (lst == 2)
        count = jnp.sum(in_cache.astype(jnp.int32))
        hits = hits + hit.astype(jnp.int32)
        if TEL:
            gargs = (
                dict(evict_mask=prev_cache & ~in_cache, cache_mask=in_cache,
                     gx=_lane_pick(onehot, groups_row))
                if GROUPED
                else {}
            )
            tel = tel_update(
                tel, t, hit=hit, fill=~hit, evict=evict, count=count, **gargs
            )
            return stamp, in_cache, count, hits, lst, p, tel
        return stamp, in_cache, count, hits, lst, p

    # -------------------------------------------------------------- drivers
    freq0 = jnp.zeros(iota.shape, jnp.int32)
    cache0 = jnp.zeros(iota.shape, jnp.bool_)
    zero = jnp.int32(0)
    gdsf0 = (jnp.zeros(iota.shape, jnp.int32), zero) if kind == "gdsf" else ()
    bytes0 = (zero,) if BYTES else ()
    tel0 = (jnp.zeros((ROWS, n_w_pad), jnp.int32),) if TEL else ()

    if kind == "wlfu":
        ring0 = jnp.full(r_iota.shape, -1, jnp.int32)
        carry = _fori(
            0, trace_len, wlfu_step, (freq0, cache0, zero, zero, ring0, zero) + tel0
        )
    elif kind == "tinylfu":
        carry = (freq0, cache0, zero, zero, rows0, zero)
        if doorkeeper:
            carry = carry + (jnp.zeros(b_iota.shape, jnp.bool_),)
        carry = _fori(0, trace_len, tinylfu_step, carry + tel0)
    elif kind == "arc":
        lst0 = jnp.zeros(iota.shape, jnp.int32)
        carry = _fori(
            0, trace_len, arc_step, (freq0, cache0, zero, zero, lst0, zero) + tel0
        )
    elif kind == "plfua_dyn":
        # chunked walk, hot mask frozen inside each chunk; the refresh fires
        # only when its whole period lies within the real trace (global-time
        # cadence — a padded tail chunk must NOT refresh, or the final
        # hot/sketch state would diverge whenever T % refresh != 0)
        hot0 = iota < hot_size
        n_chunks = -(-trace_len // refresh)

        def chunk(c, carry):
            base = c * refresh

            def step_in_chunk(tl, cy):
                t = base + tl
                return base_step(t, cy, active=t < trace_len)

            carry = _fori(0, refresh, step_in_chunk, carry)
            if TEL:
                *carry, tel = carry
            freq, in_cache, count, hits, rows, hot, *extra = carry
            fire = (c + 1) * refresh <= trace_len
            new_hot, new_rows = _refresh_hot(
                rows, tables, width=sketch_width, n_objects=n_objects,
                hot_k=hot_size, iota=iota,
            )
            if TEL:
                # refresh + hot-churn land in the window of the request that
                # completed the period (trace position (c+1)*refresh - 1)
                pos = jnp.minimum((c + 1) * refresh - 1, trace_len - 1)
                won = (nw_iota == pos // W).astype(jnp.int32)
                fire_i = fire.astype(jnp.int32)
                if GROUPED:
                    # the refresh is attributed to the group of the request
                    # that completed the period; churn is membership-split
                    # over the hot-mask diff (the jax tier's churn_g matmul)
                    gp = _lane_pick(iota == request(pos), groups_row)
                    inc = (m_iota == gp * _TEL_ROWS + 7).astype(jnp.int32) * fire_i
                    diff = hot != new_hot
                    for g in range(n_groups):
                        churn_g = jnp.sum((diff & (groups_row == g)).astype(jnp.int32))
                        inc = inc + _row(g * _TEL_ROWS + 8) * (churn_g * fire_i)
                    tel = tel + inc * won
                else:
                    churn = jnp.sum((hot != new_hot).astype(jnp.int32))
                    tel = tel + (_row(7) * fire_i + _row(8) * (churn * fire_i)) * won
            hot = _pick(fire, new_hot, hot)
            rows = [jnp.where(fire, nr, r) for nr, r in zip(new_rows, rows)]
            out = (freq, in_cache, count, hits, rows, hot, *extra)
            return out + (tel,) if TEL else out

        carry = _fori(
            0,
            n_chunks,
            chunk,
            (freq0, cache0, zero, zero, rows0, hot0) + bytes0 + tel0,
        )
    else:
        carry = _fori(
            0,
            trace_len,
            base_step,
            (freq0, cache0, zero, zero) + gdsf0 + bytes0 + tel0,
        )

    freq, in_cache, _, hits = carry[0], carry[1], carry[2], carry[3]
    hits_ref[0, 0] = hits
    freq_ref[...] = freq
    cache_ref[...] = in_cache.astype(jnp.int32)
    if TEL:
        tel_refs[0][...] = carry[-1]


def cache_sim_pallas(
    traces: jax.Array,
    *,
    kind: str,
    n_objects: int,
    capacity: int,
    hot_size: int = 0,
    window: int = 0,
    refresh: int = 0,
    sketch_width: int = 0,
    doorkeeper: int = 0,
    telemetry_window: int = 0,
    capacity_bytes: int = 0,
    max_victims: int = 0,
    sizes=None,
    n_groups: int = 0,
    groups=None,
    interpret: bool = False,
):
    """Simulate S same-shape traces on the Pallas grid.

    Args:
      traces: (S, T) int32 request ids in [0, n_objects).
      kind: one of KERNEL_KINDS (every kind in the registry).
      hot_size: plfua/plfua_dyn hot-set size (0 -> the paper's 2*capacity).
      window: wlfu sliding window (required >= 1) / tinylfu aging window
        (0 -> ``sketch.default_window``).
      refresh: plfua_dyn hot-set refresh period (0 -> ``sketch.default_refresh``).
      sketch_width: count-min width for the sketch kinds
        (0 -> ``sketch.default_width``).
      doorkeeper: tinylfu bloom front size in bits (0 = off).
      telemetry_window: windowed-telemetry bucket size W (0 = off). When set,
        the kernel accumulates the :data:`repro.telemetry.METRICS` counters
        per ceil(T/W) window inside the trace loop and a fourth output is
        returned; the disabled kernel program is unchanged.
      capacity_bytes: byte budget (0 = object-count mode). Byte mode is
        supported for ``BYTE_CAPABLE_KINDS`` only (the base-step family);
        ``wlfu``/``tinylfu`` under a byte budget raise — use the JAX scan.
      max_victims: byte-mode multi-victim eviction bound (0 -> the registry
        default; a byte-only option, like ``PolicySpec``).
      sizes: (n_objects,) int32 per-object byte sizes, shared by all samples
        (``workloads.object_sizes``). Consulted only by the size-aware
        programs (byte mode or gdsf); None -> unit sizes.
      n_groups: group-segmented telemetry (PR 8): number of tenant groups G
        (0 = off). Requires ``telemetry_window`` and a ``groups`` catalogue;
        the series output grows a group axis. The n_groups=0 program is
        byte-identical to before the option existed.
      groups: (n_objects,) int32 id -> group labels in [0, n_groups), shared
        by all samples (``workloads.tenant_groups``).
      interpret: run the Pallas interpreter instead of compiling with Mosaic
        (the only way to run the kernel off-TPU; ``ops.cache_sim`` picks it
        from the backend).

    The defaults mirror ``jax_cache.PolicySpec`` exactly, so identical
    arguments produce bit-identical state across the two tiers.

    Returns:
      hits:     (S,)      int32 — total hits per sample (CHR = hits / T).
      freq:     (S, N)    int32 — final frequency table (lru/arc: last-access
                stamps; arc stamps every *tracked* id, ghosts included).
      in_cache: (S, N)    bool  — final cache contents.
      series:   (S, n_windows, N_METRICS) int32 — only with telemetry_window,
                matching ``jax_cache.simulate(..., TelemetrySpec(W))`` exactly;
                (S, n_windows, n_groups, N_METRICS) when grouped, matching
                ``TelemetrySpec(W, n_groups)`` + the same ``groups`` catalogue.
    """
    if kind not in KERNEL_KINDS:
        raise ValueError(f"kind={kind!r} not in {KERNEL_KINDS}")
    if kind == "wlfu" and window < 1:
        raise ValueError("wlfu requires window >= 1")
    if doorkeeper < 0:
        raise ValueError(f"doorkeeper must be >= 0, got {doorkeeper}")
    if doorkeeper and kind != "tinylfu":
        raise ValueError("doorkeeper is a tinylfu-only option")
    if telemetry_window < 0:
        raise ValueError(f"telemetry_window must be >= 0, got {telemetry_window}")
    if n_groups < 0:
        raise ValueError(f"n_groups must be >= 0, got {n_groups}")
    if n_groups and not telemetry_window:
        raise ValueError("n_groups is a telemetry option: set telemetry_window")
    if n_groups and groups is None:
        raise ValueError("n_groups > 0 requires a groups catalogue")
    if capacity_bytes < 0:
        raise ValueError(f"capacity_bytes must be >= 0, got {capacity_bytes}")
    if capacity_bytes and kind not in BYTE_CAPABLE_KINDS:
        raise ValueError(
            f"byte-capacity mode is not supported for kind={kind!r} on the "
            f"Pallas tier (supported: {BYTE_CAPABLE_KINDS}); use jax_cache"
        )
    if max_victims < 0:
        raise ValueError(f"max_victims must be >= 0, got {max_victims}")
    if max_victims and not capacity_bytes:
        raise ValueError("max_victims is a byte-capacity (capacity_bytes) option")
    max_victims = (max_victims or registry.DEFAULT_MAX_VICTIMS) if capacity_bytes else 0
    s, t = traces.shape
    n_rows = _tile_rows(n_objects)
    n_pad = n_rows * 128
    if kind in ("plfua", "plfua_dyn"):
        hot_size = min(n_objects, hot_size or 2 * capacity)
    # normalise options the kind ignores to 0 so they can't create spurious
    # jit-cache variants (or the false impression that they applied)
    if kind == "tinylfu":
        window = window or sketch.default_window(capacity)
    elif kind != "wlfu":
        window = 0
    refresh = refresh or sketch.default_refresh(capacity) if kind == "plfua_dyn" else 0
    sketch_width = (
        sketch_width or sketch.default_width(capacity)
        if kind in _SKETCH_KINDS
        else 0
    )

    n_w = -(-t // telemetry_window) if telemetry_window else 0
    n_w_pad = _round_up(max(n_w, 128), 128) if telemetry_window else 0
    kernel = functools.partial(
        _cache_sim_kernel,
        kind=kind,
        capacity=capacity,
        hot_size=hot_size,
        window=window,
        refresh=refresh,
        sketch_width=sketch_width,
        doorkeeper=doorkeeper,
        n_objects=n_objects,
        n_pad=n_pad,
        trace_len=t,
        telemetry_window=telemetry_window,
        n_w_pad=n_w_pad,
        capacity_bytes=capacity_bytes,
        max_victims=max_victims,
        n_groups=n_groups,
    )
    # per-sample blocks squeeze the sample dim; the last two dims are whole
    # dense (rows, 128) arrays, which is what Mosaic's (8, 128) rule admits
    per_sample = lambda *tail: pl.BlockSpec(
        (None, *tail), lambda i: (i,) + (0,) * len(tail)
    )
    shared = lambda rows: pl.BlockSpec((rows, 128), lambda i: (0, 0))
    out_specs = [
        pl.BlockSpec((None, 1, 1), lambda i: (i, 0, 0), memory_space=pltpu.SMEM),
        per_sample(n_rows, 128),
        per_sample(n_rows, 128),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((s, 1, 1), jnp.int32),
        jax.ShapeDtypeStruct((s, n_rows, 128), jnp.int32),
        jax.ShapeDtypeStruct((s, n_rows, 128), jnp.int32),
    ]
    if telemetry_window:
        tel_rows = _TEL_ROWS * (n_groups or 1)
        out_specs.append(per_sample(tel_rows, n_w_pad))
        out_shape.append(jax.ShapeDtypeStruct((s, tel_rows, n_w_pad), jnp.int32))
    t_rows = _tile_rows(t)
    traces = jnp.pad(traces.astype(jnp.int32), ((0, 0), (0, t_rows * 128 - t)))
    in_specs = [per_sample(t_rows, 128)]
    inputs = [traces.reshape(s, t_rows, 128)]

    def per_id(name, values, pad):
        # grid-shared per-id row (jnp throughout: it may be a tracer under
        # the jitted ops.cache_sim)
        v = jnp.asarray(values, jnp.int32)
        if v.shape != (n_objects,):
            raise ValueError(f"{name} must have shape ({n_objects},), got {v.shape}")
        in_specs.append(shared(n_rows))
        inputs.append(jnp.pad(v, (0, n_pad - n_objects), constant_values=pad)
                      .reshape(n_rows, 128))

    if capacity_bytes or kind == "gdsf":
        # padding lanes are size 1 so the unit-size fallback and the padded
        # tail share one code path
        per_id("sizes", jnp.ones((n_objects,)) if sizes is None else sizes, 1)
    if telemetry_window and n_groups:
        # padding lanes get group 0 — harmless because padding ids are never
        # requested, cached, or hot
        per_id("groups", groups, 0)
    out = pl.pallas_call(
        kernel,
        grid=(s,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*inputs)
    hits = out[0].reshape(s)
    freq, cache = (o.reshape(s, n_pad)[:, :n_objects] for o in out[1:3])
    result = (hits, freq, cache.astype(bool))
    if telemetry_window:
        if n_groups:
            # (S, 16G, w_pad) -> (S, G, 16, n_w) -> (S, n_w, G, N_METRICS)
            raw = out[3][:, :, :n_w].reshape(s, n_groups, _TEL_ROWS, n_w)
            series = jnp.transpose(
                raw[:, :, : telemetry_spec.N_METRICS, :], (0, 3, 1, 2)
            )
        else:
            # (S, rows, w_pad) -> (S, n_windows, N_METRICS) in METRICS order
            series = jnp.transpose(
                out[3][:, : telemetry_spec.N_METRICS, :n_w], (0, 2, 1)
            )
        result = result + (series,)
    return result
