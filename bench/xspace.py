"""A wire reader of the profiler's ``.xplane.pb``, for what
``jax.profiler.ProfileData`` leaves out: the EVENT METADATA's own stats (on a
device plane they carry each HLO operation's ``op_name`` path, the
``jax.named_scope`` names) and the stats of host events (the attributes of a
``TraceAnnotation``: ``rid``, ``group``).

Five messages of ``tsl/profiler/protobuf/xplane.proto``, by field number:

  XSpace          1 planes
  XPlane          2 name, 3 lines, 4 event_metadata (map), 5 stat_metadata (map)
  XLine           2 name, 3 timestamp_ns, 4 events
  XEvent          1 metadata_id, 2 offset_ps, 3 duration_ps, 4 stats
  XEventMetadata  1 id, 2 name, 4 display_name, 5 stats
  XStat           1 metadata_id, 2 double, 3 uint64, 4 int64, 5 str, 6 bytes,
                  7 ref (the id of a stat metadata whose NAME is the value)
  XStatMetadata   1 id, 2 name

No dependency but the standard library: the ``protobuf`` package's generated
module for this file lives in TensorFlow, whose import costs more than the
reading.
"""

from __future__ import annotations

import struct


def _varint(buf, i: int):
    value, shift = 0, 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message; a length-delimited
    value is a memoryview slice, not a copy."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i : i + size]
            i += size
        elif wire == 1:
            value = buf[i : i + 8]
            i += 8
        elif wire == 5:
            value = buf[i : i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield number, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _stat(buf, stat_names: dict):
    name, value = None, None
    for number, _, raw in _fields(buf):
        if number == 1:
            name = stat_names.get(raw, str(raw))
        elif number == 2:
            value = struct.unpack("<d", raw)[0]
        elif number == 3:
            value = raw
        elif number == 4:
            value = _signed(raw)
        elif number == 5:
            value = _text(raw)
        elif number == 6:
            value = bytes(raw)
        elif number == 7:
            value = stat_names.get(raw, str(raw))
    return name, value


def _map_entry(buf):
    key, value = None, None
    for number, _, raw in _fields(buf):
        if number == 1:
            key = raw
        elif number == 2:
            value = raw
    return key, value


def _plane(buf, host_names) -> dict:
    name, lines, event_meta, stat_meta = "", [], [], []
    for number, _, raw in _fields(buf):
        if number == 2:
            name = _text(raw)
        elif number == 3:
            lines.append(raw)
        elif number == 4:
            event_meta.append(raw)
        elif number == 5:
            stat_meta.append(raw)
    stat_names = {}
    for entry in stat_meta:
        key, value = _map_entry(entry)
        for number, _, raw in _fields(value):
            if number == 2:
                stat_names[key] = _text(raw)
    metadata = {}
    for entry in event_meta:
        key, value = _map_entry(entry)
        meta = {"name": "", "stats": {}}
        for number, _, raw in _fields(value):
            if number == 2:
                meta["name"] = _text(raw)
            elif number == 4:
                meta["display_name"] = _text(raw)
            elif number == 5:
                stat_name, stat_value = _stat(raw, stat_names)
                meta["stats"][stat_name] = stat_value
        metadata[key] = meta
    wanted = None
    if host_names is not None and not name.startswith("/device:"):
        wanted = {k for k, m in metadata.items() if m["name"] in host_names}
    out_lines = []
    for line in lines:
        line_name, timestamp_ns, events = "", 0, []
        for number, _, raw in _fields(line):
            if number == 2:
                line_name = _text(raw)
            elif number == 3:
                timestamp_ns = _signed(raw)
            elif number == 4:
                events.append(raw)
        parsed = []
        for event in events:
            if wanted is not None and _first_varint(event) not in wanted:
                continue
            meta_id, offset_ps, duration_ps, stats = 0, 0, 0, {}
            for number, _, raw in _fields(event):
                if number == 1:
                    meta_id = raw
                elif number == 2:
                    offset_ps = raw
                elif number == 3:
                    duration_ps = raw
                elif number == 4:
                    stat_name, stat_value = _stat(raw, stat_names)
                    stats[stat_name] = stat_value
            parsed.append(
                (meta_id, timestamp_ns + offset_ps / 1e3, duration_ps / 1e3, stats)
            )
        out_lines.append({"name": line_name, "events": parsed})
    return {"name": name, "lines": out_lines, "event_metadata": metadata}


def _first_varint(event) -> int:
    """An event's metadata id without reading the rest of it (field 1 comes
    first on the wire); 0 where it does not."""
    if len(event) and event[0] == 0x08:
        return _varint(event, 1)[0]
    return 0


def read(path: str, host_names=None) -> list:
    """[{"name", "lines": [{"name", "events": [(metadata id, start_ns,
    dur_ns, {stat: value})]}], "event_metadata": {id: {"name", "stats"}}}]

    With ``host_names``, a plane that is not a device's keeps only the events
    so named: a trace taken with the Python tracer on holds millions of
    frames nobody here reads."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return [
        _plane(raw, host_names)
        for number, _, raw in _fields(buf)
        if number == 1
    ]
