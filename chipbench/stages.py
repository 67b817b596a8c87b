"""Stage time of a profiler trace, by the names the program gives its work.

The simulator names its device work with scopes ``repro.<stage>``
(``jax.named_scope``) and its host work in ``FleetStream`` with spans
``repro:<what>`` (``jax.profiler.TraceAnnotation``); docs/observability.md,
"Spans and scopes", lists them. On the TPU each operation of the ``XLA Ops``
line carries its framework name, the scope path included, as the ``tf_op``
stat of its event metadata (a string, or a reference to an interned one),
except a loop (``while``), which carries none (TPU v5e, JAX 0.9): a loop
takes the stages of the scope its body ops name before ``/while/body``,
common to all of them.

``reduce`` takes the ``chipbench:window`` span as the window, as
``trace.reduce`` does, and gives:

* ``scope_s[stage]``: the union of the intervals of the operations whose
  path holds ``repro.<stage>``, averaged over the chips as ``busy_s`` is. A
  loop and the operations of its body overlap and count once, and a nested
  stage is a subset of the outer one (``victim`` of ``step``);
* ``body_s[stage]``: the same union over the named operations alone, loops
  left out: the part of a stage's loops in which their bodies' operations
  ran;
* ``scope_ops[stage]``: how many such operations ran, averaged over chips;
* ``span_s[name]``: the duration of each program span inside the window;
* ``gaps``: each idle interval as ``trace.reduce`` names it, with the
  innermost program span that overlaps it (``None`` where none does).

No metric of the benchmark reads this yet. On a saved profile:

    python3 chipbench/stages.py <profile_dir> --requests <n>
"""
from __future__ import annotations

import argparse
import bisect
import dataclasses
import json
import operator
import pathlib
import re
import sys

if __name__ == "__main__":
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory  # noqa: E402

from chipbench import trace, xplane  # noqa: E402

PROGRAM_PREFIX = "repro:"
LOOP_BODY = "/while/body"
SCOPE = re.compile(r"\brepro\.([A-Za-z]\w*)")
#: the stat of an operation's event metadata that holds its framework name
OP_PATH_STAT = "tf_op"

_F = descriptor_pb2.FieldDescriptorProto
_INT64, _UINT64, _STRING, _MESSAGE = _F.TYPE_INT64, _F.TYPE_UINT64, _F.TYPE_STRING, _F.TYPE_MESSAGE
_ONE, _MANY = _F.LABEL_OPTIONAL, _F.LABEL_REPEATED

#: ``xplane.py``'s schema plus the stats of event metadata
_SCHEMA = {
    **xplane._SCHEMA,
    "XPlane": xplane._SCHEMA["XPlane"] + [
        ("stat_metadata", 5, _MESSAGE, _MANY, "StatMetadataEntry")],
    "XEventMetadata": xplane._SCHEMA["XEventMetadata"] + [
        ("stats", 5, _MESSAGE, _MANY, "XStat")],
    "StatMetadataEntry": [("key", 1, _INT64, _ONE, None),
                          ("value", 2, _MESSAGE, _ONE, "XStatMetadata")],
    "XStatMetadata": [("id", 1, _INT64, _ONE, None), ("name", 2, _STRING, _ONE, None)],
    "XStat": [("metadata_id", 1, _INT64, _ONE, None), ("str_value", 5, _STRING, _ONE, None),
              ("ref_value", 7, _UINT64, _ONE, None)],
}
_PACKAGE = "chipbench_stages"


@dataclasses.dataclass
class Profile:
    """Events as (name, start_ns, end_ns): per chip its program executions
    and its operations (named by framework path), and the host spans of the
    benchmark (``chipbench:``) and of the program (``repro:``)."""

    modules: list
    ops: list
    host: list


@dataclasses.dataclass
class Stages:
    window_s: float
    scope_s: dict
    body_s: dict
    scope_ops: dict
    span_s: dict
    gaps: list  # [(host span, program span or None, seconds)], in time order

    def table(self, requests: int | None = None) -> dict:
        """Per stage: device seconds, operations, and operations per 1,000
        requests where ``requests`` is given."""
        out = {}
        for stage in sorted(self.scope_s, key=lambda s: -self.scope_s[s]):
            row = {"s": self.scope_s[stage], "body_s": self.body_s.get(stage, 0.0),
                   "ops": self.scope_ops[stage]}
            if requests:
                row["ops_per_kreq"] = self.scope_ops[stage] * 1000 / requests
            out[stage] = row
        return out


def _classes() -> dict:
    fdp = descriptor_pb2.FileDescriptorProto(name=f"{_PACKAGE}.proto", package=_PACKAGE,
                                             syntax="proto3")
    for msg, fields in _SCHEMA.items():
        m = fdp.message_type.add(name=msg)
        for name, number, ftype, label, ref in fields:
            f = m.field.add(name=name, number=number, type=ftype, label=label)
            if ref:
                f.type_name = f".{_PACKAGE}.{ref}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return {m: message_factory.GetMessageClass(pool.FindMessageTypeByName(f"{_PACKAGE}.{m}"))
            for m in _SCHEMA}


def op_paths(plane) -> dict:
    """Event metadata id -> the operation's framework path (its ``tf_op``)."""
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}
    out = {}
    for entry in plane.event_metadata:
        for st in entry.value.stats:
            if stat_names.get(st.metadata_id) == OP_PATH_STAT:
                out[entry.key] = st.str_value or stat_names.get(st.ref_value, "")
    return out


def load(profile_dir) -> Profile:
    """Read the newest ``*.xplane.pb`` under ``profile_dir``."""
    files = sorted(pathlib.Path(profile_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no xplane.pb under {profile_dir}")
    space = _classes()["XSpace"].FromString(files[-1].read_bytes())
    origin = xplane.origin_ns(space)
    modules, ops, host = [], [], []
    for plane in space.planes:
        if plane.name.startswith("/device:TPU:"):
            mods, op_list = [], []
            paths = op_paths(plane)
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods += xplane.events(plane, line, origin)
                elif line.name == "XLA Ops":
                    ids = [e.metadata_id for e in line.events]
                    op_list += [(paths.get(i, ""), s, e) for i, (_, s, e)
                                in zip(ids, xplane.events(plane, line, origin))]
            modules.append(mods)
            ops.append(op_list)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [ev for ev in xplane.events(plane, line, origin)
                         if ev[0].startswith((trace.SPAN_PREFIX, PROGRAM_PREFIX))]
    return Profile(modules, ops, host)


def stages_of(path: str) -> set:
    """``jit(f)/repro.step/while/body/repro.victim/reduce`` -> {step, victim}."""
    return set(SCOPE.findall(path))


def _tagged(ops, memo) -> list:
    """(stages, named, start, end) of each operation: the stages of its own
    path, or, for one without a framework name (a loop), those of the scope
    that every body operation inside it names before its last
    ``/while/body`` (the loop's condition operations name none)."""
    ops = sorted(ops, key=operator.itemgetter(1))
    starts = [s for _, s, _ in ops]
    own, scope = [], []
    for path, _, _ in ops:
        if path not in memo:
            cut = path.rfind(LOOP_BODY)
            memo[path] = (stages_of(path), None if cut < 0 else stages_of(path[:cut]))
        own.append(memo[path][0])
        scope.append(memo[path][1])
    out = []
    for i, (path, s, e) in enumerate(ops):
        found = own[i]
        if not path:
            inner = [scope[j] for j in range(i + 1, bisect.bisect_left(starts, e, i + 1))
                     if scope[j] is not None and ops[j][2] <= e]
            found = set.intersection(*inner) if inner else set()
        out.append((found, bool(path), s, e))
    return out


def reduce(profile: Profile) -> Stages:
    windows = [(s, e) for n, s, e in profile.host if n == trace.WINDOW]
    if not windows:
        raise ValueError(f"the trace holds no {trace.WINDOW!r} span")
    w0, w1 = windows[-1]
    n_chips = max(1, len(profile.modules))
    bench = trace._Spans((n[len(trace.SPAN_PREFIX):], s, e) for n, s, e in profile.host
                         if n.startswith(trace.SPAN_PREFIX) and n != trace.WINDOW)
    program = [(n, s, e) for n, s, e in profile.host if n.startswith(PROGRAM_PREFIX)]
    span_s: dict = {}
    for n, s, e in sorted(program, key=lambda ev: ev[1]):
        if s >= w0 and e <= w1:
            span_s.setdefault(n, []).append((e - s) * 1e-9)
    scope_s, body_s, scope_ops, gaps, memo = {}, {}, {}, [], {}
    for mods, ops in zip(profile.modules, profile.ops):
        per_stage: dict = {}
        for found, named, s, e in _tagged(ops, memo):
            if e <= w0 or s >= w1:
                continue
            for stage in found:
                per_stage.setdefault(stage, []).append((max(s, w0), min(e, w1), named))
        for stage, iv in per_stage.items():
            length = trace._length(trace.union((s, e) for s, e, _ in iv))
            body = trace._length(trace.union((s, e) for s, e, named in iv if named))
            scope_s[stage] = scope_s.get(stage, 0.0) + length * 1e-9 / n_chips
            body_s[stage] = body_s.get(stage, 0.0) + body * 1e-9 / n_chips
            scope_ops[stage] = scope_ops.get(stage, 0.0) + len(iv) / n_chips
        merged = trace.union((s, e) for _, s, e in trace._clip(mods, w0, w1))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((bench.label(a, b), _innermost(program, a, b), (b - a) * 1e-9))
    if not profile.modules:  # no chip plane: the whole window idles
        gaps.append((bench.label(w0, w1), _innermost(program, w0, w1), (w1 - w0) * 1e-9))
    return Stages((w1 - w0) * 1e-9, scope_s, body_s, scope_ops, span_s, gaps)


def _innermost(spans, a, b):
    """The shortest program span overlapping (a, b), or None."""
    hit = [(e - s, n) for n, s, e in spans if s < b and e > a]
    return min(hit)[1] if hit else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Stage time of a saved profile, as JSON.")
    ap.add_argument("profile_dir")
    ap.add_argument("--requests", type=int, default=None,
                    help="requests simulated in the window, for operations per 1,000")
    args = ap.parse_args(argv)
    st = reduce(load(args.profile_dir))
    spans = {n: {"count": len(d), "mean_us": sum(d) / len(d) * 1e6}
             for n, d in st.span_s.items()}
    print(json.dumps({"window_s": st.window_s, "stages": st.table(args.requests),
                      "spans": spans}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
