"""The parts of the profiler's XPlane schema that the trace reduction
reads and ``jax.profiler.ProfileData`` does not expose: the stats of an
event's METADATA, where a device operation keeps what is not in its
event's own stats.

The schema is ``tsl/profiler/protobuf/xplane.proto`` (field numbers as
there); only the fields read here are declared, and the parser skips the
rest. Built from ``google.protobuf`` alone, under a package of its own so
that it never meets another copy of the schema in the default pool.
"""

from __future__ import annotations

import functools

_PACKAGE = "tpubench.xplane"

# message -> [(field, number, type, label, message type)]
_SCHEMA = {
    "XSpace": [("planes", 1, "message", "repeated", "XPlane")],
    "XPlane": [("name", 2, "string", "optional", None),
               ("lines", 3, "message", "repeated", "XLine"),
               ("event_metadata", 4, "message", "repeated",
                "EventMetadataEntry"),
               ("stat_metadata", 5, "message", "repeated",
                "StatMetadataEntry")],
    # A proto map is, on the wire, a repeated entry of key 1 and value 2.
    "EventMetadataEntry": [("key", 1, "int64", "optional", None),
                           ("value", 2, "message", "optional",
                            "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, "int64", "optional", None),
                          ("value", 2, "message", "optional",
                           "XStatMetadata")],
    "XLine": [("name", 2, "string", "optional", None),
              ("timestamp_ns", 3, "int64", "optional", None),
              ("events", 4, "message", "repeated", "XEvent")],
    "XEvent": [("metadata_id", 1, "int64", "optional", None),
               ("offset_ps", 2, "int64", "optional", None),
               ("duration_ps", 3, "int64", "optional", None)],
    "XStat": [("metadata_id", 1, "int64", "optional", None),
              ("str_value", 5, "string", "optional", None),
              ("ref_value", 7, "uint64", "optional", None)],
    "XEventMetadata": [("id", 1, "int64", "optional", None),
                       ("stats", 5, "message", "repeated", "XStat")],
    "XStatMetadata": [("id", 1, "int64", "optional", None),
                      ("name", 2, "string", "optional", None)],
}


@functools.cache
def space_class():
    """The message class of an ``XSpace``, the root of a ``.xplane.pb``."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    fdp = descriptor_pb2.FieldDescriptorProto
    proto = descriptor_pb2.FileDescriptorProto(
        name="tpubench_xplane.proto", package=_PACKAGE, syntax="proto3")
    for message, fields in _SCHEMA.items():
        m = proto.message_type.add(name=message)
        for name, number, kind, label, type_name in fields:
            f = m.field.add(
                name=name, number=number,
                type=getattr(fdp, "TYPE_" + kind.upper()),
                label=getattr(fdp, "LABEL_" + label.upper()))
            if type_name:
                f.type_name = f".{_PACKAGE}.{type_name}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(proto)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PACKAGE}.XSpace"))


def read_space(path):
    space = space_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def metadata_stat(plane, name: str) -> dict:
    """{event metadata id: the string stat ``name`` of that metadata} for
    one plane, where it has one. A string stat is held inline or,
    interned, as a reference to a stat metadata whose name is the
    string."""
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}
    out = {}
    for entry in plane.event_metadata:
        for stat in entry.value.stats:
            if stat_names.get(stat.metadata_id) != name:
                continue
            value = stat.str_value or stat_names.get(stat.ref_value, "")
            if value:
                out[entry.key] = value
    return out
