"""The profiler's ``.xplane.pb`` (an XSpace protobuf) read from its wire
format, with nothing but Python.

``jax.profiler.ProfileData`` gives an event's own stats but not those of its
event METADATA, and on the TPU that is where an op's HLO ``op_name`` lives
(stat ``tf_op``: ``jit(_fused_step)/jit(main)/kv_write/scatter:``), beside
``hlo_category``, ``flops`` and ``bytes_accessed`` (seen on one v5e, PR 26).
The schema read here (tsl/profiler/protobuf/xplane.proto):

    XSpace   1 planes*
    XPlane   2 name  3 lines*  4 event_metadata{id: XEventMetadata}
             5 stat_metadata{id: XStatMetadata}
    XLine    2 name  3 timestamp_ns  4 events*
    XEvent   1 metadata_id  2 offset_ps  3 duration_ps  4 stats*
    XStat    1 metadata_id  2 double  3 uint64  4 int64  5 str  6 bytes  7 ref
    XEventMetadata  1 id  2 name  4 display_name  5 stats*
    XStatMetadata   1 id  2 name

An event's time is its line's ``timestamp_ns`` plus its ``offset_ps``, as
ProfileData gives it.
"""

from __future__ import annotations

import struct


def _varint(b, i: int) -> tuple[int, int]:
    x = s = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << s
        if c < 0x80:
            return x, i
        s += 7


def _fields(b):
    """(field number, value) of one message; a length-delimited value is a
    memoryview, a varint an unsigned int."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        f, w = key >> 3, key & 7
        if w == 0:
            v, i = _varint(b, i)
        elif w == 2:
            ln, i = _varint(b, i)
            v = b[i:i + ln]
            i += ln
        elif w in (1, 5):
            size = 8 if w == 1 else 4
            v = bytes(b[i:i + size])
            i += size
        else:
            raise ValueError(f"xplane: wire type {w} at byte {i}")
        yield f, v


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat(b, stat_names: dict):
    mid, val = 0, None
    for f, v in _fields(b):
        if f == 1:
            mid = v
        elif f == 2:
            val = struct.unpack("<d", v)[0]
        elif f == 3:
            val = v
        elif f == 4:
            val = _signed(v)
        elif f in (5, 6):
            val = _text(v)
        elif f == 7:  # a reference into the stat names: a string held once
            val = stat_names.get(v, "")
    return stat_names.get(mid, str(mid)), val


def _map_value(entry):
    """The value (field 2) of one map entry."""
    return next((v for f, v in _fields(entry) if f == 2), b"")


def read_planes(path: str, want=lambda name: True) -> list[dict]:
    """[{"name", "lines": [{"name", "events": [(metadata id, start_s,
    dur_s, {stat: value})]}], "metadata": {id: {"name", "display_name",
    "stats"}}}] for the planes whose name ``want`` accepts."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = []
    for f, raw in _fields(space):
        if f != 1:
            continue
        name, lines, metas, stat_names = "", [], [], {}
        for f2, v in _fields(raw):
            if f2 == 2:
                name = _text(v)
            elif f2 == 3:
                lines.append(v)
            elif f2 == 4:
                metas.append(_map_value(v))
            elif f2 == 5:
                sid, sname = 0, ""
                for f3, v3 in _fields(_map_value(v)):
                    if f3 == 1:
                        sid = v3
                    elif f3 == 2:
                        sname = _text(v3)
                stat_names[sid] = sname
        if not want(name):
            continue
        plane = {"name": name, "lines": [], "metadata": {}}
        for m in metas:
            meta = {"name": "", "display_name": "", "stats": {}}
            mid = 0
            for f3, v3 in _fields(m):
                if f3 == 1:
                    mid = v3
                elif f3 == 2:
                    meta["name"] = _text(v3)
                elif f3 == 4:
                    meta["display_name"] = _text(v3)
                elif f3 == 5:
                    k, val = _stat(v3, stat_names)
                    meta["stats"][k] = val
            plane["metadata"][mid] = meta
        for ln in lines:
            lname, t_line, events = "", 0, []
            for f3, v3 in _fields(ln):
                if f3 == 2:
                    lname = _text(v3)
                elif f3 == 3:
                    t_line = _signed(v3)
                elif f3 == 4:
                    mid = off = dur = 0
                    stats = {}
                    for f4, v4 in _fields(v3):
                        if f4 == 1:
                            mid = v4
                        elif f4 == 2:
                            off = _signed(v4)
                        elif f4 == 3:
                            dur = _signed(v4)
                        elif f4 == 4:
                            k, val = _stat(v4, stat_names)
                            stats[k] = val
                    events.append((mid, off, dur, stats))
            plane["lines"].append({
                "name": lname,
                "events": [(mid, t_line / 1e9 + off / 1e12, dur / 1e12, st) for mid, off, dur, st in events],
            })
        out.append(plane)
    return out
