"""The part of a profiler trace that ``jax.profiler.ProfileData`` does not
show: the stats of each event's metadata, where a TPU trace keeps an
operation's scope path (``tf_op``: ``jit(_step_impl)/kv_gather/gather:``).

``ProfileData`` gives each event's own stats only (on the TPU: offset,
duration).  This module reads the ``.xplane.pb`` itself with a message schema
of the few ``XSpace`` fields it needs (``tsl/profiler/protobuf/xplane.proto``
field numbers), built at import time with ``google.protobuf``; every other
field is skipped as unknown.
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

#: the event-metadata stat that holds an operation's scope path
SCOPE_STAT = "tf_op"

_F = descriptor_pb2.FieldDescriptorProto


def _schema() -> descriptor_pb2.FileDescriptorProto:
    fd = descriptor_pb2.FileDescriptorProto(name="bench_xspace.proto",
                                            package="bench_xspace",
                                            syntax="proto3")

    def message(name, fields, oneof=None):
        m = fd.message_type.add(name=name)
        if oneof:
            m.oneof_decl.add(name=oneof)
        for fname, number, kind, *rest in fields:
            f = m.field.add(name=fname, number=number, type=kind,
                            label=_F.LABEL_REPEATED if "repeated" in rest
                            else _F.LABEL_OPTIONAL)
            if kind == _F.TYPE_MESSAGE:
                f.type_name = ".bench_xspace." + rest[-1]
            if "oneof" in rest:
                f.oneof_index = 0

    message("XStat", [("metadata_id", 1, _F.TYPE_INT64),
                      ("double_value", 2, _F.TYPE_DOUBLE, "oneof"),
                      ("uint64_value", 3, _F.TYPE_UINT64, "oneof"),
                      ("int64_value", 4, _F.TYPE_INT64, "oneof"),
                      ("str_value", 5, _F.TYPE_STRING, "oneof"),
                      ("bytes_value", 6, _F.TYPE_BYTES, "oneof"),
                      ("ref_value", 7, _F.TYPE_UINT64, "oneof")],
            oneof="value")
    message("XEventMetadata", [("id", 1, _F.TYPE_INT64),
                               ("name", 2, _F.TYPE_STRING),
                               ("display_name", 4, _F.TYPE_STRING),
                               ("stats", 5, _F.TYPE_MESSAGE, "repeated",
                                "XStat")])
    message("XStatMetadata", [("id", 1, _F.TYPE_INT64),
                              ("name", 2, _F.TYPE_STRING)])
    # map<int64, ...> fields, read as the repeated entries they are on the wire
    message("EventMetadataEntry", [("key", 1, _F.TYPE_INT64),
                                   ("value", 2, _F.TYPE_MESSAGE,
                                    "XEventMetadata")])
    message("StatMetadataEntry", [("key", 1, _F.TYPE_INT64),
                                  ("value", 2, _F.TYPE_MESSAGE,
                                   "XStatMetadata")])
    message("XPlane", [("name", 2, _F.TYPE_STRING),
                       ("event_metadata", 4, _F.TYPE_MESSAGE, "repeated",
                        "EventMetadataEntry"),
                       ("stat_metadata", 5, _F.TYPE_MESSAGE, "repeated",
                        "StatMetadataEntry")])
    message("XSpace", [("planes", 1, _F.TYPE_MESSAGE, "repeated", "XPlane")])
    return fd


_pool = descriptor_pool.DescriptorPool()
_pool.Add(_schema())
XSpace = message_factory.GetMessageClass(
    _pool.FindMessageTypeByName("bench_xspace.XSpace"))


def op_scopes(path: Path) -> Dict[str, Dict[str, str]]:
    """``{plane name: {event name: scope path}}`` for every event metadata
    that carries ``SCOPE_STAT``, on the device planes (``/device:``).  An
    event is found under its metadata's name and under its display name."""
    space = XSpace()
    space.ParseFromString(Path(path).read_bytes())
    out: Dict[str, Dict[str, str]] = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:"):
            continue
        names = {e.key: e.value.name for e in plane.stat_metadata}
        scopes: Dict[str, str] = {}
        for entry in plane.event_metadata:
            md = entry.value
            for st in md.stats:
                if names.get(st.metadata_id) != SCOPE_STAT:
                    continue
                kind = st.WhichOneof("value")
                scope = names.get(st.ref_value, "") if kind == "ref_value" \
                    else str(getattr(st, kind)) if kind else ""
                for key in (md.name, md.display_name):
                    if key:
                        scopes[key] = scope
        out[plane.name] = scopes
    return out
