"""A dozen lines of protobuf wire format: enough to write an ``XSpace``
(tensorflow/tsl profiler ``xplane.proto``) with planes, lines and timed
events, so the trace reducer is tested on a trace whose every interval is
known by hand.  Field numbers: XSpace.planes=1; XPlane.id=1 name=2 lines=3
event_metadata=4 (map: key=1 value=2); XLine.id=1 name=2 timestamp_ns=3
events=4; XEvent.metadata_id=1 offset_ps=2 duration_ps=3;
XEventMetadata.id=1 name=2."""


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(field: int, value: int) -> bytes:
    return _varint(field << 3) + _varint(value)


def _bytes(field: int, payload: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def xspace(planes) -> bytes:
    """``planes``: [(plane_name, [(line_name, timestamp_ns, [(event_name,
    offset_ns, duration_ns), ...]), ...]), ...] → serialized XSpace."""
    out = b""
    for pid, (pname, lines) in enumerate(planes, 1):
        names = sorted({e[0] for _l, _t, evs in lines for e in evs})
        ids = {n: i for i, n in enumerate(names, 1)}
        body = _int(1, pid) + _bytes(2, pname.encode())
        for lid, (lname, ts, evs) in enumerate(lines, 1):
            line = _int(1, lid) + _bytes(2, lname.encode()) + _int(3, ts)
            for ename, off_ns, dur_ns in evs:
                line += _bytes(4, _int(1, ids[ename]) + _int(2, off_ns * 1000)
                               + _int(3, dur_ns * 1000))
            body += _bytes(3, line)
        for n, i in ids.items():
            meta = _int(1, i) + _bytes(2, n.encode())
            body += _bytes(4, _int(1, i) + _bytes(2, meta))
        out += _bytes(1, body)
    return out


def write(path: str, planes) -> str:
    with open(path, "wb") as f:
        f.write(xspace(planes))
    return path
