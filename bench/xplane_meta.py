"""Read the per-op metadata of a profiler trace (``.xplane.pb``).

``jax.profiler.ProfileData`` gives each event its name, times and its own
stats, but not the stats kept on the event's metadata, where the TPU's
trace keeps what it knows of an operation (its name-scope path among
them). This reads that part of the file straight from the protobuf wire
format (``tsl/profiler/protobuf/xplane.proto``), skipping the lines of
events, so it needs nothing but the standard library.
"""
from __future__ import annotations

# XSpace.planes; XPlane.name, .lines, .event_metadata, .stat_metadata;
# XEventMetadata.name, .display_name, .stats; XStat value fields
_PLANES = 1
_P_NAME, _P_EVENT_META, _P_STAT_META = 2, 4, 5
_E_ID, _E_NAME, _E_DISPLAY, _E_STATS = 1, 2, 4, 5
_S_ID, _S_STR, _S_BYTES, _S_REF = 1, 5, 6, 7


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: ints for varint and fixed
    fields, a memoryview for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, val


def _map_entry(buf):
    """(key, value message) of a map<int64, message> entry."""
    key, val = 0, b""
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _text(v):
    return bytes(v).decode("utf-8", "replace")


def op_metadata(path: str, plane_prefix: str = "/device:"):
    """{plane name: {event metadata name: {stat name: str value}}} for the
    planes whose name starts with ``plane_prefix``: each event metadata's
    ``display_name`` (as ``"display_name"``) and its string-valued stats
    (a ``ref_value`` resolves to the referenced stat metadata's name)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != _PLANES:
            continue
        name, emeta, smeta = "", [], {}
        for f, v in _fields(plane):
            if f == _P_NAME:
                name = _text(v)
                if not name.startswith(plane_prefix):
                    break
            elif f == _P_EVENT_META:
                emeta.append(_map_entry(v)[1])
            elif f == _P_STAT_META:
                k, m = _map_entry(v)
                smeta[k] = next((_text(x) for g, x in _fields(m) if g == 2),
                                "")
        if not name.startswith(plane_prefix):
            continue
        ops = out.setdefault(name, {})
        for m in emeta:
            ename, stats = "", {}
            for f, v in _fields(m):
                if f == _E_NAME:
                    ename = _text(v)
                elif f == _E_DISPLAY:
                    stats["display_name"] = _text(v)
                elif f == _E_STATS:
                    sid, val = 0, None
                    for g, x in _fields(v):
                        if g == _S_ID:
                            sid = x
                        elif g in (_S_STR, _S_BYTES):
                            val = _text(x)
                        elif g == _S_REF:
                            val = smeta.get(x, "")
                    if val is not None:
                        stats[smeta.get(sid, str(sid))] = val
            ops[ename] = stats
    return out
