"""The profiler's ``XSpace`` trace format, read with ``google.protobuf`` alone.

The benchmark reads the ``.xplane.pb`` file itself, against this copy of
the schema of ``tsl/profiler/protobuf/xplane.proto`` (field numbers as
published there), so that the reduction does not move with JAX's own
reader, and so that tests can build small traces in the same format.  Its
reading of a recorded trace is checked against values read with
``jax.profiler.ProfileData`` (``bench/tests/test_bench_reduce.py``).
"""

from __future__ import annotations

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_F = descriptor_pb2.FieldDescriptorProto
_PKG = "bench.xplane"

# message: [(field, number, type, repeated, message type or None)]
_SCHEMA = {
    "XSpace": [("planes", 1, _F.TYPE_MESSAGE, True, "XPlane"),
               ("errors", 2, _F.TYPE_STRING, True, None),
               ("warnings", 3, _F.TYPE_STRING, True, None),
               ("hostnames", 4, _F.TYPE_STRING, True, None)],
    "XPlane": [("id", 1, _F.TYPE_INT64, False, None),
               ("name", 2, _F.TYPE_STRING, False, None),
               ("lines", 3, _F.TYPE_MESSAGE, True, "XLine"),
               ("event_metadata", 4, _F.TYPE_MESSAGE, True,
                "XPlane.EventMetadataEntry"),
               ("stat_metadata", 5, _F.TYPE_MESSAGE, True,
                "XPlane.StatMetadataEntry"),
               ("stats", 6, _F.TYPE_MESSAGE, True, "XStat")],
    "XLine": [("id", 1, _F.TYPE_INT64, False, None),
              ("display_id", 10, _F.TYPE_INT64, False, None),
              ("name", 2, _F.TYPE_STRING, False, None),
              ("display_name", 11, _F.TYPE_STRING, False, None),
              ("timestamp_ns", 3, _F.TYPE_INT64, False, None),
              ("duration_ps", 9, _F.TYPE_INT64, False, None),
              ("events", 4, _F.TYPE_MESSAGE, True, "XEvent")],
    "XEvent": [("metadata_id", 1, _F.TYPE_INT64, False, None),
               ("offset_ps", 2, _F.TYPE_INT64, False, None),
               ("num_occurrences", 5, _F.TYPE_INT64, False, None),
               ("duration_ps", 3, _F.TYPE_INT64, False, None),
               ("stats", 4, _F.TYPE_MESSAGE, True, "XStat")],
    "XStat": [("metadata_id", 1, _F.TYPE_INT64, False, None),
              ("double_value", 2, _F.TYPE_DOUBLE, False, None),
              ("uint64_value", 3, _F.TYPE_UINT64, False, None),
              ("int64_value", 4, _F.TYPE_INT64, False, None),
              ("str_value", 5, _F.TYPE_STRING, False, None),
              ("bytes_value", 6, _F.TYPE_BYTES, False, None),
              ("ref_value", 7, _F.TYPE_UINT64, False, None)],
    "XEventMetadata": [("id", 1, _F.TYPE_INT64, False, None),
                       ("name", 2, _F.TYPE_STRING, False, None),
                       ("display_name", 4, _F.TYPE_STRING, False, None),
                       ("metadata", 3, _F.TYPE_BYTES, False, None),
                       ("stats", 5, _F.TYPE_MESSAGE, True, "XStat"),
                       ("child_id", 6, _F.TYPE_INT64, True, None)],
    "XStatMetadata": [("id", 1, _F.TYPE_INT64, False, None),
                      ("name", 2, _F.TYPE_STRING, False, None),
                      ("description", 3, _F.TYPE_STRING, False, None)],
}
_MAPS = {"EventMetadataEntry": "XEventMetadata",
         "StatMetadataEntry": "XStatMetadata"}


def _add_fields(message, fields):
    for name, number, ftype, repeated, type_name in fields:
        field = message.field.add(
            name=name, number=number, type=ftype,
            label=_F.LABEL_REPEATED if repeated else _F.LABEL_OPTIONAL)
        if type_name:
            field.type_name = f".{_PKG}.{type_name}"


def _build():
    fp = descriptor_pb2.FileDescriptorProto(
        name="bench/xplane.proto", package=_PKG, syntax="proto3")
    for name, fields in _SCHEMA.items():
        message = fp.message_type.add(name=name)
        _add_fields(message, fields)
        if name == "XPlane":
            for entry, value in _MAPS.items():
                nested = message.nested_type.add(name=entry)
                nested.options.map_entry = True
                _add_fields(nested, [("key", 1, _F.TYPE_INT64, False, None),
                                     ("value", 2, _F.TYPE_MESSAGE, False,
                                      value)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PKG}.XSpace"))


XSpace = _build()


def parse(data: bytes):
    """An ``XSpace`` message from the bytes of an ``.xplane.pb`` file."""
    space = XSpace()
    space.ParseFromString(data)
    return space
