# The profiler's .xplane.pb read whole. `jax.profiler.ProfileData` shows
# an event's own stats only; the HLO `op_name` (the jax.named_scope path)
# of a device op is a stat of the event's *metadata*, so the readers
# that attribute device time by scope parse the XSpace message
# themselves. The schema (tsl/profiler/protobuf/xplane.proto) is
# declared here field by field: nothing but `google.protobuf` is
# imported, which JAX already needs.
"""Read an .xplane.pb into plain Python, metadata stats included."""
import functools
import glob
import os

_INT64, _UINT64, _DOUBLE, _STRING, _BYTES, _MESSAGE = 3, 4, 1, 9, 12, 11
_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("id", 1, _INT64), ("name", 2, _STRING),
               ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventMetadataEntry", True),
               ("stat_metadata", 5, "StatMetadataEntry", True),
               ("stats", 6, "XStat", True)],
    "EventMetadataEntry": [("key", 1, _INT64), ("value", 2, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, _INT64), ("value", 2, "XStatMetadata")],
    "XLine": [("id", 1, _INT64), ("name", 2, _STRING),
              ("timestamp_ns", 3, _INT64), ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, _INT64), ("offset_ps", 2, _INT64),
               ("duration_ps", 3, _INT64), ("stats", 4, "XStat", True)],
    "XStat": [("metadata_id", 1, _INT64), ("double_value", 2, _DOUBLE),
              ("uint64_value", 3, _UINT64), ("int64_value", 4, _INT64),
              ("str_value", 5, _STRING), ("bytes_value", 6, _BYTES),
              ("ref_value", 7, _UINT64)],
    "XEventMetadata": [("id", 1, _INT64), ("name", 2, _STRING),
                       ("display_name", 4, _STRING),
                       ("stats", 5, "XStat", True)],
    "XStatMetadata": [("id", 1, _INT64), ("name", 2, _STRING)],
}
_VALUES = ("double_value", "uint64_value", "int64_value", "str_value",
           "bytes_value")


@functools.lru_cache(maxsize=None)
def _xspace_class():
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    file = descriptor_pb2.FileDescriptorProto(
        name="flashy_bench_xplane.proto", package="flashy_bench",
        syntax="proto3")
    for message, fields in _SCHEMA.items():
        entry = file.message_type.add(name=message)
        for name, number, kind, *repeated in fields:
            field = entry.field.add(name=name, number=number,
                                    label=3 if repeated else 1)
            if isinstance(kind, str):
                field.type = _MESSAGE
                field.type_name = f".flashy_bench.{kind}"
            else:
                field.type = kind
    pool = descriptor_pool.DescriptorPool()
    pool.Add(file)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("flashy_bench.XSpace"))


class Event(tuple):
    """(name, start_ns, end_ns, stats): stats are the event's own over
    its metadata's, names resolved, `ref_value`s followed."""
    __slots__ = ()
    name = property(lambda self: self[0])
    start = property(lambda self: self[1])
    end = property(lambda self: self[2])
    stats = property(lambda self: self[3])


def _stats(stats, names) -> dict:
    """{stat name: value}; a stat whose every value field is at its
    default reads 0 (proto3 does not send defaults)."""
    out = {}
    for stat in stats:
        key = names.get(stat.metadata_id, str(stat.metadata_id))
        if stat.ref_value:  # a string interned as a stat metadata's name
            out[key] = names.get(stat.ref_value, "")
            continue
        out[key] = next((value for value in
                         (getattr(stat, field) for field in _VALUES)
                         if value), 0)
    return out


def parse(data: bytes) -> dict:
    """{plane name: {line name: [Event]}} of a serialized XSpace."""
    space = _xspace_class().FromString(data)
    planes = {}
    for plane in space.planes:
        names = {e.key: e.value.name for e in plane.stat_metadata}
        metadata = {e.key: (e.value.name, _stats(e.value.stats, names))
                    for e in plane.event_metadata}
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            base = line.timestamp_ns
            for event in line.events:
                name, shared = metadata.get(event.metadata_id, ("", {}))
                own = _stats(event.stats, names) if event.stats else None
                start = base + event.offset_ps / 1e3
                events.append(Event((
                    name, start, start + event.duration_ps / 1e3,
                    {**shared, **own} if own else shared)))
    return planes


def newest_trace_file(root: str):
    """The newest .xplane.pb under <root>/.bench_out/*/trace/ (run.py
    wipes the cell's directory when a run starts), or None."""
    files = glob.glob(os.path.join(root, ".bench_out", "*", "trace", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def load(path: str) -> dict:
    with open(path, "rb") as f:
        return parse(f.read())
