"""``xplane_writer`` with stats: the same hand-written ``XSpace``, whose
events may also carry what a ``TraceAnnotation``'s keyword arguments
become in the profiler's file (stats of the event) and what a device
operation's scope path becomes (a stat of the event's METADATA entry:
give its key a leading ``@``).  Further field numbers (tensorflow/tsl
``xplane.proto``): XPlane.stat_metadata=5 (map: key=1 value=2);
XEvent.stats=4; XEventMetadata.stats=5; XStat.metadata_id=1
double_value=2 int64_value=4 str_value=5; XStatMetadata.id=1 name=2."""

import struct

from xplane_writer import _bytes, _int, _varint


def _stat(stat_id: int, value) -> bytes:
    body = _int(1, stat_id)
    if isinstance(value, bool) or not isinstance(value, (int, float)):
        body += _bytes(5, str(value).encode())
    elif isinstance(value, int):
        body += _int(4, value)
    else:
        body += _varint(2 << 3 | 1) + struct.pack("<d", value)
    return body


def xspace(planes) -> bytes:
    """``planes``: [(plane_name, [(line_name, timestamp_ns, [(event_name,
    offset_ns, duration_ns[, {stat: value}]), ...]), ...]), ...] →
    serialized XSpace.  A stat's value is written as int64, double or
    string by its Python type; ``{"@tf_op": ...}`` goes onto the event's
    metadata entry (one entry per event name: the first event's wins)."""
    out = b""
    for pid, (pname, lines) in enumerate(planes, 1):
        events = [e for _l, _t, evs in lines for e in evs]
        ids = {n: i for i, n in enumerate(sorted({e[0] for e in events}), 1)}
        stat_ids = {n: i for i, n in enumerate(sorted(
            {k.lstrip("@") for e in events if len(e) > 3 for k in e[3]}), 1)}
        on_metadata = {}
        for e in events:
            on_metadata.setdefault(e[0], {
                k[1:]: v for k, v in (e[3] if len(e) > 3 else {}).items()
                if k.startswith("@")})
        body = _int(1, pid) + _bytes(2, pname.encode())
        for lid, (lname, ts, evs) in enumerate(lines, 1):
            line = _int(1, lid) + _bytes(2, lname.encode()) + _int(3, ts)
            for ename, off_ns, dur_ns, *stats in evs:
                ev = (_int(1, ids[ename]) + _int(2, off_ns * 1000)
                      + _int(3, dur_ns * 1000))
                for k, v in (stats[0] if stats else {}).items():
                    if not k.startswith("@"):
                        ev += _bytes(4, _stat(stat_ids[k], v))
                line += _bytes(4, ev)
            body += _bytes(3, line)
        for n, i in ids.items():
            meta = _int(1, i) + _bytes(2, n.encode())
            for k, v in on_metadata[n].items():
                meta += _bytes(5, _stat(stat_ids[k], v))
            body += _bytes(4, _int(1, i) + _bytes(2, meta))
        for n, i in stat_ids.items():
            meta = _int(1, i) + _bytes(2, n.encode())
            body += _bytes(5, _int(1, i) + _bytes(2, meta))
        out += _bytes(1, body)
    return out


def write(path: str, planes) -> str:
    with open(path, "wb") as f:
        f.write(xspace(planes))
    return path
