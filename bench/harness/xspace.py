"""What ``jax.profiler.ProfileData`` does not expose of a profile.

A ``.xplane.pb`` file is an ``XSpace`` protobuf.  ``ProfileData`` gives
each event's name, times and own stats, but not the stats of the event's
metadata, and on a TPU that is where an operation's ``op_name`` is: the
``tf_op`` stat of each ``XLA Ops`` event's metadata holds the path of
``jax.named_scope`` names the operation was traced under.  This module
reads just the event metadata from the file's bytes with a small
protobuf wire-format reader, skipping every event line without decoding
it, so a large trace costs little more than a pass over its planes.

Field numbers are those of ``tsl/profiler/protobuf/xplane.proto``.
"""
from __future__ import annotations

import mmap

# XSpace.planes; XPlane.{name, event_metadata, stat_metadata}
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 4, 5
# XEventMetadata.{name, stats}; XStatMetadata.{id, name}
_EMD_NAME, _EMD_STATS = 2, 5
_SMD_ID, _SMD_NAME = 1, 2
# XStat.{metadata_id, str_value, ref_value}
_STAT_ID, _STAT_STR, _STAT_REF = 1, 5, 7


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf, lo: int = 0, hi: int | None = None):
    """``(field, value)`` of one message: an int for a varint, a
    ``(start, end)`` byte range for anything length-delimited or fixed."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = (i, i + 8), i + 8
        elif wire == 5:
            v, i = (i, i + 4), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _text(buf, r) -> str:
    return bytes(buf[r[0]:r[1]]).decode("utf-8", "replace")


def _map_values(buf, r):
    """The value message of one protobuf map entry."""
    for f, v in fields(buf, *r):
        if f == 2:
            yield v


def _plane(buf, r, want) -> tuple[str, dict]:
    """A plane's name and, if ``want(name)``, the string stats of its
    event metadata: ``{event name: {stat name: text}}``."""
    name, stat_names, emds = "", {}, []
    for f, v in fields(buf, *r):
        if f == _PLANE_NAME:
            name = _text(buf, v)
        elif f == _PLANE_STAT_MD:
            for val in _map_values(buf, v):
                sid, sname = None, ""
                for g, w in fields(buf, *val):
                    if g == _SMD_ID:
                        sid = w
                    elif g == _SMD_NAME:
                        sname = _text(buf, w)
                stat_names[sid] = sname
        elif f == _PLANE_EVENT_MD:
            emds.extend(_map_values(buf, v))
    if not want(name):
        return name, {}
    out = {}
    for r in emds:
        ename, stats = "", {}
        for f, v in fields(buf, *r):
            if f == _EMD_NAME:
                ename = _text(buf, v)
            elif f == _EMD_STATS:
                sid = text = None
                for g, w in fields(buf, *v):
                    if g == _STAT_ID:
                        sid = w
                    elif g == _STAT_STR:
                        text = _text(buf, w)
                    elif g == _STAT_REF:
                        # a reference names the stat metadata holding it
                        text = stat_names.get(w)
                if text is not None and sid in stat_names:
                    stats[stat_names[sid]] = text
        if stats:
            out[ename] = stats
    return name, out


def event_metadata_strings(path: str, want) -> dict:
    """``{plane name: {event name: {stat name: text}}}``: the string stats
    of the event metadata of each plane whose name ``want`` accepts."""
    with open(path, "rb") as f:
        if f.seek(0, 2) == 0:
            return {}
        buf = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
    try:
        out = {}
        for f, v in fields(buf):
            if f == _SPACE_PLANES:
                name, strings = _plane(buf, v, want)
                if strings:
                    out[name] = strings
        return out
    finally:
        buf.close()
