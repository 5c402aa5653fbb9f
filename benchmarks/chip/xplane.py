"""A minimal schema of the profiler's ``XSpace`` (``.xplane.pb``), enough
to read event-metadata stats, which ``jax.profiler.ProfileData`` does not
expose: the ``tf_op`` stat holds an XLA op's ``op_name`` path, and with it
the ``jax.named_scope`` around the op.

Built here from the fields' numbers in TSL's ``xplane.proto`` with
``google.protobuf`` alone (the generated module ships inside TensorFlow,
whose import would load all of TensorFlow). Fields not listed are skipped
when parsing; maps are read as their repeated entries.
"""
from __future__ import annotations

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_F = descriptor_pb2.FieldDescriptorProto
_INT64, _UINT64, _DOUBLE = _F.TYPE_INT64, _F.TYPE_UINT64, _F.TYPE_DOUBLE
_STRING, _BYTES, _MESSAGE = _F.TYPE_STRING, _F.TYPE_BYTES, _F.TYPE_MESSAGE
_ONE, _MANY = _F.LABEL_OPTIONAL, _F.LABEL_REPEATED

#: message -> [(field, number, type, label, message type)]
_SCHEMA = {
    "XStat": [("metadata_id", 1, _INT64, _ONE, None),
              ("double_value", 2, _DOUBLE, _ONE, None),
              ("uint64_value", 3, _UINT64, _ONE, None),
              ("int64_value", 4, _INT64, _ONE, None),
              ("str_value", 5, _STRING, _ONE, None),
              ("bytes_value", 6, _BYTES, _ONE, None),
              ("ref_value", 7, _UINT64, _ONE, None)],
    "XEvent": [("metadata_id", 1, _INT64, _ONE, None),
               ("offset_ps", 2, _INT64, _ONE, None),
               ("duration_ps", 3, _INT64, _ONE, None),
               ("stats", 4, _MESSAGE, _MANY, "XStat")],
    "XLine": [("id", 1, _INT64, _ONE, None),
              ("name", 2, _STRING, _ONE, None),
              ("timestamp_ns", 3, _INT64, _ONE, None),
              ("events", 4, _MESSAGE, _MANY, "XEvent")],
    "XEventMetadata": [("id", 1, _INT64, _ONE, None),
                       ("name", 2, _STRING, _ONE, None),
                       ("display_name", 4, _STRING, _ONE, None),
                       ("stats", 5, _MESSAGE, _MANY, "XStat")],
    "XStatMetadata": [("id", 1, _INT64, _ONE, None),
                      ("name", 2, _STRING, _ONE, None)],
    "EventMetadataEntry": [("key", 1, _INT64, _ONE, None),
                           ("value", 2, _MESSAGE, _ONE, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, _INT64, _ONE, None),
                          ("value", 2, _MESSAGE, _ONE, "XStatMetadata")],
    "XPlane": [("id", 1, _INT64, _ONE, None),
               ("name", 2, _STRING, _ONE, None),
               ("lines", 3, _MESSAGE, _MANY, "XLine"),
               ("event_metadata", 4, _MESSAGE, _MANY, "EventMetadataEntry"),
               ("stat_metadata", 5, _MESSAGE, _MANY, "StatMetadataEntry")],
    "XSpace": [("planes", 1, _MESSAGE, _MANY, "XPlane")],
}
_PACKAGE = "bench_xplane"


def _classes():
    f = descriptor_pb2.FileDescriptorProto(
        name=f"{_PACKAGE}.proto", package=_PACKAGE, syntax="proto2")
    for name, fields in _SCHEMA.items():
        m = f.message_type.add(name=name)
        for field, number, kind, label, ref in fields:
            fd = m.field.add(name=field, number=number, type=kind,
                             label=label)
            if ref:
                fd.type_name = f".{_PACKAGE}.{ref}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return {name: message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PACKAGE}.{name}"))
        for name in _SCHEMA}


_CLASSES = _classes()
XSpace = _CLASSES["XSpace"]


def parse(data: bytes):
    """The ``XSpace`` serialized in ``data``."""
    space = XSpace()
    space.ParseFromString(data)
    return space
