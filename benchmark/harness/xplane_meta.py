"""What ``jax.profiler.ProfileData`` leaves out of a capture: the stats
of an event's *metadata*.

On a TPU capture (seen by hand on the v5e, PR 23) an ``XLA Ops`` event
carries only its offset and duration; what the compiler knew of the
operation sits on the event's metadata entry, shared by every execution
of it: ``tf_op`` (the JAX name stack of the lowered operation, e.g.
``jit(decode_step)/sampling/jit(sort)/sort:``: this is where a
``jax.named_scope`` arrives), ``hlo_category``, ``program_id``,
``flops``, ``bytes_accessed``, ``source``. ``ProfileData`` exposes the
event's own stats and not these, so this module reads the ``.xplane.pb``
itself, with ``google.protobuf`` and the few fields of the XSpace schema
it needs (tsl/profiler/protobuf/xplane.proto), declared here so that
nothing heavier has to be imported.
"""

from __future__ import annotations

from typing import Dict, List

from .trace import DEVICE_PLANE, MODULES_LINE, OPS_LINE, Event, short_name, _set_own_time

_TYPES = {"int64": 3, "uint64": 4, "double": 1, "string": 9, "bytes": 12,
          "message": 11}
_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("id", 1, "int64"), ("name", 2, "string"),
               ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventMetadataEntry", True),
               ("stat_metadata", 5, "StatMetadataEntry", True)],
    "EventMetadataEntry": [("key", 1, "int64"), ("value", 2, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, "int64"), ("value", 2, "XStatMetadata")],
    "XLine": [("id", 1, "int64"), ("name", 2, "string"),
              ("timestamp_ns", 3, "int64"), ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
               ("duration_ps", 3, "int64")],
    "XEventMetadata": [("id", 1, "int64"), ("name", 2, "string"),
                       ("stats", 5, "XStat", True)],
    "XStatMetadata": [("id", 1, "int64"), ("name", 2, "string")],
    "XStat": [("metadata_id", 1, "int64"), ("double_value", 2, "double"),
              ("uint64_value", 3, "uint64"), ("int64_value", 4, "int64"),
              ("str_value", 5, "string"), ("bytes_value", 6, "bytes"),
              ("ref_value", 7, "uint64")],
}
_classes: Dict[str, type] = {}


def _xspace_class():
    """The message classes for the fields above, built once. Unknown
    fields of the real schema are skipped by the parser."""
    if _classes:
        return _classes["XSpace"]
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    fd = descriptor_pb2.FileDescriptorProto(
        name="dynamo_bench_xplane.proto", package="dynamo_bench_xplane",
        syntax="proto3")
    for msg, fields in _SCHEMA.items():
        m = fd.message_type.add(name=msg)
        for name, number, kind, *repeated in fields:
            f = m.field.add(name=name, number=number,
                            label=3 if repeated else 1)
            if kind in _TYPES:
                f.type = _TYPES[kind]
            else:
                f.type = _TYPES["message"]
                f.type_name = f".dynamo_bench_xplane.{kind}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    for msg in _SCHEMA:
        _classes[msg] = message_factory.GetMessageClass(
            pool.FindMessageTypeByName(f"dynamo_bench_xplane.{msg}"))
    return _classes["XSpace"]


def load_op_events(path: str, stat: str = "tf_op") -> Dict[int, Dict[str, List[Event]]]:
    """Per device: its ``XLA Ops`` events (own time set, ``detail`` =
    the metadata's ``stat``, '' where it has none) and its ``XLA
    Modules`` events, as ``{"ops": [...], "modules": [...]}``."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out: Dict[int, Dict[str, List[Event]]] = {}
    for plane in space.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        stat_ids = {e.value.id or e.key for e in plane.stat_metadata
                    if e.value.name == stat}
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {}
        for e in plane.event_metadata:
            value = ""
            for s in e.value.stats:
                if s.metadata_id in stat_ids:
                    # a string stat is stored inline or as a reference
                    # to a stat-metadata name
                    value = s.str_value or stat_names.get(s.ref_value, "")
            meta[e.key] = (e.value.name, value)
        got: Dict[str, List[Event]] = {"ops": [], "modules": []}
        for line in plane.lines:
            key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
            if key is None:
                continue
            base = line.timestamp_ns * 1e-9
            evs = [Event(short_name(meta.get(ev.metadata_id, ("", ""))[0]),
                         base + ev.offset_ps * 1e-12, ev.duration_ps * 1e-12,
                         detail=meta.get(ev.metadata_id, ("", ""))[1])
                   for ev in line.events]
            evs.sort(key=lambda ev: (ev.start, -ev.dur))
            if key == "ops":
                _set_own_time(evs)
            got[key] = evs
        out[int(m.group(1))] = got
    return out
