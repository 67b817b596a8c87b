"""Read a profiler ``*.xplane.pb`` (a serialized ``XSpace``) with a schema of
just the fields the reduction needs, parsed by protobuf's C runtime.

A TPU trace of a two-second window holds millions of operation events (loop
bodies are recorded per iteration); walking them through
``jax.profiler.ProfileData`` costs tens of microseconds an event, this well
under one. Field numbers are those of tsl's ``xplane.proto``; fields left
out of the schema (stats, display names) are skipped by the parser."""
from __future__ import annotations

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_F = descriptor_pb2.FieldDescriptorProto
_INT64, _STRING, _MESSAGE = _F.TYPE_INT64, _F.TYPE_STRING, _F.TYPE_MESSAGE
_ONE, _MANY = _F.LABEL_OPTIONAL, _F.LABEL_REPEATED

_SCHEMA = {
    "XSpace": [("planes", 1, _MESSAGE, _MANY, "XPlane")],
    "XPlane": [("name", 2, _STRING, _ONE, None), ("lines", 3, _MESSAGE, _MANY, "XLine"),
               ("event_metadata", 4, _MESSAGE, _MANY, "MetadataEntry")],
    "MetadataEntry": [("key", 1, _INT64, _ONE, None),
                      ("value", 2, _MESSAGE, _ONE, "XEventMetadata")],
    "XEventMetadata": [("id", 1, _INT64, _ONE, None), ("name", 2, _STRING, _ONE, None)],
    "XLine": [("name", 2, _STRING, _ONE, None), ("timestamp_ns", 3, _INT64, _ONE, None),
              ("events", 4, _MESSAGE, _MANY, "XEvent")],
    "XEvent": [("metadata_id", 1, _INT64, _ONE, None), ("offset_ps", 2, _INT64, _ONE, None),
               ("duration_ps", 3, _INT64, _ONE, None)],
}
_PACKAGE = "chipbench_xplane"


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


def parse(data: bytes):
    """The ``XSpace`` message of ``data``."""
    return _classes()["XSpace"].FromString(data)


def events(plane, line, origin_ns: int):
    """(name, start_ns, end_ns) of every event of ``line``, in ns after
    ``origin_ns`` on the trace's clock (kept small, so float ns stay exact)."""
    names = {e.key: e.value.name for e in plane.event_metadata}
    base_ps = (line.timestamp_ns - origin_ns) * 1000
    return [(names.get(e.metadata_id, ""), (base_ps + e.offset_ps) / 1000,
             (base_ps + e.offset_ps + e.duration_ps) / 1000) for e in line.events]


def origin_ns(space) -> int:
    """The earliest line start of ``space``: a common origin for ``events``."""
    return min((ln.timestamp_ns for p in space.planes for ln in p.lines if ln.events),
               default=0)
