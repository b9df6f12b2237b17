"""The profiler's ``.xplane.pb`` read field by field, without a schema
module: ``jax.profiler.ProfileData`` shows an event's own stats but not
those of its metadata, and an operation's ``op_name`` (the scope path) is
one of the latter. Only what the readers need is decoded: planes, lines,
events with their times, and the stats of events and of event metadata.

The wire format (protobuf): a message is a run of ``(field << 3 | type)``
keys, each followed by a varint (type 0), 8 bytes (1), a length and that
many bytes (2) or 4 bytes (5). Field numbers are those of
``tsl/profiler/protobuf/xplane.proto``."""
import struct


def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """``(field, wire_type, value)`` of one message; a length-delimited
    value is a ``memoryview`` of its bytes."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, kind = key >> 3, key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif kind == 1:
            value = buf[i:i + 8]
            i += 8
        elif kind == 5:
            value = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {kind} at byte {i}")
        yield field, kind, value


def _signed(value):
    return value - (1 << 64) if value >= 1 << 63 else value


def _stat(buf, stat_names):
    """One ``XStat`` as ``(name, value)``; a reference to another stat's
    name (``ref_value``) resolves to that string."""
    name, value = None, None
    for field, kind, v in fields(buf):
        if field == 1:
            name = stat_names.get(v, str(v))
        elif field == 2:
            value = struct.unpack("<d", v)[0]
        elif field == 3:
            value = v
        elif field == 4:
            value = _signed(v)
        elif field == 5:
            value = bytes(v).decode("utf-8", "replace")
        elif field == 6:
            value = bytes(v)
        elif field == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(buf):
    key, value = 0, b""
    for field, _, v in fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


def _event_metadata(buf, stat_names):
    meta = {"name": "", "display_name": "", "stats": {}}
    for field, _, v in fields(buf):
        if field == 2:
            meta["name"] = bytes(v).decode("utf-8", "replace")
        elif field == 4:
            meta["display_name"] = bytes(v).decode("utf-8", "replace")
        elif field == 5:
            name, value = _stat(v, stat_names)
            meta["stats"][name] = value
    return meta


def _line(buf, metadata, stat_names, with_stats):
    name, timestamp_ns, raw_events = "", 0, []
    for field, _, v in fields(buf):
        if field == 2:
            name = bytes(v).decode("utf-8", "replace")
        elif field == 3:
            timestamp_ns = _signed(v)
        elif field == 4:
            raw_events.append(v)
    events = []
    for raw in raw_events:
        meta_id, offset_ps, duration_ps, stats = 0, 0, 0, {}
        for field, _, v in fields(raw):
            if field == 1:
                meta_id = v
            elif field == 2:
                offset_ps = _signed(v)
            elif field == 3:
                duration_ps = _signed(v)
            elif field == 4 and with_stats:
                key, value = _stat(v, stat_names)
                stats[key] = value
        events.append({"metadata": metadata.get(meta_id, {"name": str(meta_id),
                                                          "stats": {}}),
                       "start_s": timestamp_ns * 1e-9 + offset_ps * 1e-12,
                       "seconds": duration_ps * 1e-12, "stats": stats})
    return {"name": name, "events": events}


def read(path, planes=lambda name: True, lines=lambda name: True,
         with_stats=False):
    """``[{"name", "lines": [{"name", "events": [...]}]}]`` of the planes
    and lines whose names the two predicates accept. An event is
    ``{"metadata": {"name", "display_name", "stats"}, "start_s", "seconds",
    "stats"}``: ``start_s`` on the clock ``ProfileData`` reports as
    ``start_ns``; the event's own stats only ``with_stats``."""
    with open(path, "rb") as f:
        space = f.read()
    out = []
    for field, _, plane in fields(space):
        if field != 1:
            continue
        name, raw_lines, raw_meta, stat_names = "", [], [], {}
        for field, _, v in fields(plane):
            if field == 2:
                name = bytes(v).decode("utf-8", "replace")
            elif field == 3:
                raw_lines.append(v)
            elif field == 4:
                raw_meta.append(v)
            elif field == 5:
                key, value = _map_entry(v)
                for f2, _, v2 in fields(value):
                    if f2 == 2:
                        stat_names[key] = bytes(v2).decode("utf-8", "replace")
        if not planes(name):
            continue
        metadata = {}
        for raw in raw_meta:
            key, value = _map_entry(raw)
            metadata[key] = _event_metadata(value, stat_names)
        found = []
        for raw in raw_lines:
            line_name = ""
            for field, _, v in fields(raw):
                if field == 2:
                    line_name = bytes(v).decode("utf-8", "replace")
                    break
            if lines(line_name):
                found.append(_line(raw, metadata, stat_names, with_stats))
        out.append({"name": name, "lines": found})
    return out
