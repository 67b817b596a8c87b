"""Reduce a profiler trace of one window to what the per-layer metrics read.

The TPU profiler writes one ``/device:TPU:<n>`` plane per chip with an
``XLA Modules`` line (one event per program execution, named
``<jit name>(<fingerprint>)``) and an ``XLA Ops`` line (every operation,
loop bodies included), beside ``/host:*`` planes that hold the benchmark's
own spans (``jax.profiler.TraceAnnotation`` named ``chipbench:<what>``).
All events share one clock.

``reduce`` takes the ``chipbench:window`` span as the window and gives:

* ``busy_s``: the union of the program executions inside the window,
  averaged over the chips;
* ``module_s``: that union per program (fingerprint dropped), averaged over
  the chips;
* ``op_s``: the device seconds of each ``<module>/<operation>``, summed
  (a loop's operation and the operations of its body both count);
* ``gaps``: every interval of the window in which no program ran, named by
  the host span that overlaps it most (``host`` where none does)."""
from __future__ import annotations

import bisect
import dataclasses
import operator
import pathlib
import re

SPAN_PREFIX = "chipbench:"
WINDOW = SPAN_PREFIX + "window"

_FINGERPRINT = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Trace:
    """Events as (name, start_ns, end_ns): per chip its program executions
    and its operations, and the benchmark's host spans."""

    modules: list  # per chip: [(module, start, end)]
    ops: list  # per chip: [(op, start, end)]
    host: list  # [(span, start, end)]


@dataclasses.dataclass
class Reduced:
    n_chips: int  # device planes found; 0 off the chip
    window_s: float
    busy_s: float
    module_s: dict
    op_s: dict
    gaps: list  # [(host span, seconds)], in time order

    def idle_by_span(self) -> dict:
        out: dict = {}
        for name, s in self.gaps:
            out[name] = out.get(name, 0.0) + s
        return out


def module_name(event_name: str) -> str:
    return _FINGERPRINT.sub("", event_name).strip()


def op_name(event_name: str) -> str:
    """``%fusion.20 = (...) fusion(...)`` -> ``%fusion.20``."""
    return event_name.split(" = ", 1)[0].strip()


def load(profile_dir) -> Trace:
    """Read the newest ``*.xplane.pb`` under ``profile_dir``."""
    from chipbench import xplane

    files = sorted(pathlib.Path(profile_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no xplane.pb under {profile_dir}")
    space = xplane.parse(files[-1].read_bytes())
    origin = xplane.origin_ns(space)
    modules, ops, host = [], [], []
    for plane in space.planes:
        if plane.name.startswith("/device:TPU:"):
            mods, op_list = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods += xplane.events(plane, line, origin)
                elif line.name == "XLA Ops":
                    op_list += xplane.events(plane, line, origin)
            modules.append(mods)
            ops.append(op_list)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [ev for ev in xplane.events(plane, line, origin)
                         if ev[0].startswith(SPAN_PREFIX)]
    return Trace(modules, ops, host)


def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(events, w0, w1):
    return [(n, max(s, w0), min(e, w1)) for n, s, e in events if e > w0 and s < w1]


def _length(merged) -> float:
    return sum(e - s for s, e in merged)


def reduce(trace: Trace) -> Reduced:
    windows = [(s, e) for n, s, e in trace.host if n == WINDOW]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    w0, w1 = windows[-1]
    n_chips = max(1, len(trace.modules))
    busy, module_s, op_s, gaps = 0.0, {}, {}, []
    spans = _Spans((n[len(SPAN_PREFIX):], s, e) for n, s, e in trace.host if n != WINDOW)
    for mods, ops in zip(trace.modules, trace.ops):
        mods = [(module_name(n), s, e) for n, s, e in _clip(mods, w0, w1)]
        merged = union((s, e) for _, s, e in mods)
        busy += _length(merged)
        for name in {n for n, _, _ in mods}:
            t = _length(union((s, e) for n, s, e in mods if n == name))
            module_s[name] = module_s.get(name, 0.0) + t * 1e-9 / n_chips
        for (mod, op), t in _op_time(mods, ops, w0, w1).items():
            name = f"{mod}/{op_name(op)}"
            op_s[name] = op_s.get(name, 0.0) + t * 1e-9 / n_chips
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((spans.label(a, b), (b - a) * 1e-9))
    if not trace.modules:  # no chip plane: the whole window idles
        gaps.append((spans.label(w0, w1), (w1 - w0) * 1e-9))
    return Reduced(len(trace.modules), (w1 - w0) * 1e-9, busy * 1e-9 / n_chips, module_s,
                   op_s, gaps)


def _op_time(mods, ops, w0, w1) -> dict:
    """Device ns inside the window of every (module, operation) pair, each
    operation named by the program execution that holds its start."""
    mods = sorted(mods, key=operator.itemgetter(1))
    out, i = {}, 0
    for n, s, e in sorted(ops, key=operator.itemgetter(1)):
        if e <= w0 or s >= w1:
            continue
        while i < len(mods) and mods[i][2] <= s:
            i += 1
        key = (mods[i][0] if i < len(mods) and mods[i][1] <= s else "?", n)
        out[key] = out.get(key, 0.0) + min(e, w1) - max(s, w0)
    return out


class _Spans:
    """Host spans sorted by start, to name a gap by the span overlapping it
    most (``host`` where none does)."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=operator.itemgetter(1))
        self.starts = [s for _, s, _ in self.spans]
        self.longest = max((e - s for _, s, e in self.spans), default=0.0)

    def label(self, a, b) -> str:
        best, name = 0.0, "host"
        k = bisect.bisect_left(self.starts, b) - 1
        while k >= 0 and self.starts[k] >= a - self.longest:
            n, s, e = self.spans[k]
            overlap = min(b, e) - max(a, s)
            if overlap > best:
                best, name = overlap, n
            k -= 1
        return name


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle gaps
    by what the host was doing."""
    ops = sorted(red.op_s.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(red.gaps, key=lambda g: -g[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}
