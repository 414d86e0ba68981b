"""Read an `.xplane.pb` with its event METADATA, which `jax.profiler.
ProfileData` does not show: each `XLA Ops` event's metadata carries `tf_op`
(JAX's `op_name`: where a `jax.named_scope` lands), `hlo_category`, `flops`
and `bytes_accessed`.

The seven messages of tsl/profiler/protobuf/xplane.proto are declared here
in code and parsed by `google.protobuf` alone (no TensorFlow import, which
costs 20 s and may be missing where the trace is read). Only the fields
the reduction reads are declared; the parser skips the rest. The two maps
are declared as what they are on the wire, repeated key/value entries.
"""
from __future__ import annotations

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_T = descriptor_pb2.FieldDescriptorProto
_PACKAGE = "af2bench.xplane"

# message -> [(field, number, type, repeated, message type or None)]
_SCHEMA = {
    "XSpace": [("planes", 1, _T.TYPE_MESSAGE, True, "XPlane")],
    "XPlane": [
        ("id", 1, _T.TYPE_INT64, False, None),
        ("name", 2, _T.TYPE_STRING, False, None),
        ("lines", 3, _T.TYPE_MESSAGE, True, "XLine"),
        ("event_metadata", 4, _T.TYPE_MESSAGE, True, "EventMetadataEntry"),
        ("stat_metadata", 5, _T.TYPE_MESSAGE, True, "StatMetadataEntry"),
    ],
    "EventMetadataEntry": [
        ("key", 1, _T.TYPE_INT64, False, None),
        ("value", 2, _T.TYPE_MESSAGE, False, "XEventMetadata"),
    ],
    "StatMetadataEntry": [
        ("key", 1, _T.TYPE_INT64, False, None),
        ("value", 2, _T.TYPE_MESSAGE, False, "XStatMetadata"),
    ],
    "XLine": [
        ("id", 1, _T.TYPE_INT64, False, None),
        ("name", 2, _T.TYPE_STRING, False, None),
        ("timestamp_ns", 3, _T.TYPE_INT64, False, None),
        ("events", 4, _T.TYPE_MESSAGE, True, "XEvent"),
    ],
    "XEvent": [
        ("metadata_id", 1, _T.TYPE_INT64, False, None),
        ("offset_ps", 2, _T.TYPE_INT64, False, None),
        ("duration_ps", 3, _T.TYPE_INT64, False, None),
    ],
    "XStat": [
        ("metadata_id", 1, _T.TYPE_INT64, False, None),
        ("double_value", 2, _T.TYPE_DOUBLE, False, None),
        ("uint64_value", 3, _T.TYPE_UINT64, False, None),
        ("int64_value", 4, _T.TYPE_INT64, False, None),
        ("str_value", 5, _T.TYPE_STRING, False, None),
        ("ref_value", 7, _T.TYPE_UINT64, False, None),
    ],
    "XEventMetadata": [
        ("id", 1, _T.TYPE_INT64, False, None),
        ("name", 2, _T.TYPE_STRING, False, None),
        ("display_name", 4, _T.TYPE_STRING, False, None),
        ("stats", 5, _T.TYPE_MESSAGE, True, "XStat"),
    ],
    "XStatMetadata": [
        ("id", 1, _T.TYPE_INT64, False, None),
        ("name", 2, _T.TYPE_STRING, False, None),
    ],
}


def _xspace_class():
    file = descriptor_pb2.FileDescriptorProto(
        name="af2bench_xplane.proto", package=_PACKAGE, syntax="proto3")
    for message, fields in _SCHEMA.items():
        m = file.message_type.add(name=message)
        for name, number, kind, repeated, of in fields:
            f = m.field.add(
                name=name, number=number, type=kind,
                label=_T.LABEL_REPEATED if repeated else _T.LABEL_OPTIONAL)
            if of:
                f.type_name = f".{_PACKAGE}.{of}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(file)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PACKAGE}.XSpace"))


_XSPACE = None


def read(path: str):
    """The parsed XSpace of the file at `path`."""
    global _XSPACE
    if _XSPACE is None:
        _XSPACE = _xspace_class()
    space = _XSPACE()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def metadata_of(plane) -> dict:
    """{event metadata id: {"name", "display_name", and each of the
    metadata's own stats by its name}} of one plane. A `ref_value` stat
    (a string kept once in the stat-metadata table) is resolved."""
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}
    out = {}
    for entry in plane.event_metadata:
        md = entry.value
        row = {"name": md.name, "display_name": md.display_name}
        for st in md.stats:
            key = stat_names.get(st.metadata_id)
            if key is None:
                continue
            if st.str_value:
                row[key] = st.str_value
            elif st.ref_value:
                row[key] = stat_names.get(st.ref_value, "")
            elif st.double_value:
                row[key] = st.double_value
            else:
                row[key] = st.int64_value or st.uint64_value
        out[entry.key] = row
    return out


def events_of(line):
    """(start_s, end_s, metadata id) of each event of one line."""
    base = line.timestamp_ns * 1e-9
    for e in line.events:
        start = base + e.offset_ps * 1e-12
        yield start, start + e.duration_ps * 1e-12, e.metadata_id
