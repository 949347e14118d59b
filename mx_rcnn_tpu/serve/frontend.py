"""Wire-facing front door: length-prefixed socket protocol over submit.

The engine's in-process ``submit`` trusts its caller; real multi-tenant
traffic arrives over a wire and must be authenticated, typed, and
bounded BEFORE it can cost anything.  :class:`Frontend` is that intake:
a minimal length-prefixed protocol (no external deps) where every
request carries ``tenant``, ``lane``, and ``deadline``, and every
rejection is a TYPED error frame from a closed taxonomy — the client
can tell "back off" (``over_budget``, ``queue_full``) from "fix your
request" (``invalid_frame``, ``invalid_request``) from "you are not
provisioned" (``unknown_tenant``).

Wire format (all integers big-endian):

* request frame: ``u32 length`` + payload, where payload is one JSON
  header line (UTF-8, ``\\n``-terminated) followed by raw image bytes::

      {"v": 1, "id": 7, "tenant": "acme", "lane": "interactive",
       "deadline_ms": 250, "model": null, "dtype": "uint8",
       "shape": [480, 640, 3]}\\n
      <H*W*3 raw bytes>

* response frame: ``u32 length`` + one JSON object::

      {"ok": true, "id": 7, "detections": [null, [[x1,...,score]], ...],
       "det_meta": [null, ["float32", [1, 5]], ...]}
      {"ok": false, "id": 7, "error": "<code>", "message": "..."}

``v`` is the wire protocol version (:data:`WIRE_VERSION`).  A header
carrying any other value is rejected with the typed ``bad_version``
code — a version skew must fail loudly, not as a silently ignored
unknown field.  Headers without ``v`` are accepted (the pre-versioned
ISSUE 16 client).

``id`` opts a request into PIPELINING: the server submits it without
blocking the connection and writes the response frame — tagged with the
same ``id`` — whenever the engine resolves it, possibly out of order
relative to other ids on the same socket.  Requests without ``id`` keep
the original serial request/response cadence.  ``det_meta`` carries the
per-class dtype+shape so :func:`decode_detections` reconstructs arrays
byte-identical to what an in-process ``submit`` returned.

Optional ``stream`` (non-empty string) + ``frame`` (non-negative int,
strictly increasing per stream) header fields put the request under the
engine's per-stream in-order delivery guarantee (ISSUE 20): frames of
one stream RESOLVE in frame order even when pipelined ids would let
them complete out of order; naturally they should be paired with
``id``-pipelining so the stream's frames are in flight together.
Either field without the other, a wrong type, or a non-monotone frame
index is an ``invalid_frame`` / ``invalid_request`` reject.  Absent
both, the legacy independent-image path serves byte-identically.

A header with an ``"op"`` key instead of image fields is an admin
frame: ``{"op": "ping"}`` (liveness probe) and ``{"op": "snapshot"}``
(returns the engine + frontend snapshots) — how a fleet gateway
(``serve/fleet.py``) health-checks and aggregates per-backend counters
over the same wire the traffic uses.

Error codes: ``invalid_frame`` (length/JSON/shape/byte-count violations
— rejected before an array is even built), ``bad_version``,
``conn_limit`` (accept-time connection cap), ``unknown_tenant``,
``over_budget``, ``invalid_request`` (failed the quarantine admission
gate), ``poison`` (quarantined digest), ``queue_full``, ``deadline``,
``unknown_model``, ``unknown_version`` (a rollout arm that rolled back
mid-flight with no incumbent fallback), ``rollout_aborted`` (a blocking
rollout command's verdict), ``exhausted``, ``engine_stopped``,
``error``.

The frame parser enforces byte-level bounds (``max_frame`` caps payload
size so a hostile length prefix cannot balloon memory), then the decoded
array flows through the SAME admission matrix as in-process callers:
``engine.submit`` runs ``quarantine.validate_image``, the tenant token
bucket, and the shed logic — nothing reaches the batcher that an
in-process caller could not have submitted.  (The structural
``quarantine.validate_request`` gate fires once more inside
``batcher.submit``, unchanged.)

One handler thread per connection (serial requests on one connection
are served in order; pipelined ones resolve independently); the accept
loop and all handlers join on ``stop()``.  Two half-open-client guards
bound what a stalled peer can pin: ``conn_read_timeout`` reaps a
connection idle past the deadline with no pipelined work outstanding
(``conn_timeouts`` counter), and ``max_conns`` caps live connections at
accept time with a typed ``conn_limit`` reject (``conn_rejected``).
"""

from __future__ import annotations

import json
import socket
import struct
import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

from mx_rcnn_tpu.analysis.lockcheck import make_lock

__all__ = ["Frontend", "FrontendClient", "WIRE_DTYPES", "WIRE_VERSION",
           "decode_detections"]

#: dtypes a frame may declare; anything else is an invalid_frame (the
#: admission gate would reject non-numeric dtypes anyway — rejecting at
#: parse time just refuses to build the array at all)
WIRE_DTYPES = {"uint8": np.uint8, "float32": np.float32}

#: wire protocol version; a header ``v`` naming any other value is a
#: typed ``bad_version`` reject on both the frontend and the gateway
WIRE_VERSION = 1

_LEN = struct.Struct(">I")


def _classify(e: BaseException) -> str:
    """Exception → wire error code (same name-based convention as
    ``loadgen.classify`` so the two taxonomies cannot drift apart)."""
    name = type(e).__name__
    if "UnknownTenant" in name:
        return "unknown_tenant"
    if "OverBudget" in name:
        return "over_budget"
    if "UnknownModel" in name:
        return "unknown_model"
    if "UnknownVersion" in name:
        return "unknown_version"
    if "RolloutAborted" in name:
        return "rollout_aborted"
    if "InvalidRequest" in name:
        return "invalid_request"
    if "Poison" in name:
        return "poison"
    if "QueueFull" in name:
        return "queue_full"
    if "BucketOverflow" in name:
        return "invalid_request"
    if "Exhausted" in name:
        return "exhausted"
    if "Deadline" in name:
        return "deadline"
    if "EngineStopped" in name:
        return "engine_stopped"
    return "error"


class _FrameError(ValueError):
    """Malformed frame — rejected at the parser, before any array is
    built or any admission code runs."""


class _ReadTimeout(OSError):
    """recv deadline expired.  ``mid_frame`` records whether partial
    bytes were already consumed — if so the stream offset can no longer
    be trusted and the connection must close regardless of in-flight
    work."""

    def __init__(self, mid_frame: bool):
        super().__init__("read timed out")
        self.mid_frame = mid_frame


def _read_exact(sock: socket.socket, n: int) -> Optional[bytes]:
    """Read exactly ``n`` bytes or None on clean EOF; raises on a
    connection torn mid-frame, :class:`_ReadTimeout` when the socket's
    recv deadline expires."""
    buf = bytearray()
    while len(buf) < n:
        try:
            chunk = sock.recv(min(65536, n - len(buf)))
        except socket.timeout:
            raise _ReadTimeout(len(buf) > 0)
        if not chunk:
            if not buf:
                return None
            raise ConnectionError(
                f"connection closed mid-frame ({len(buf)}/{n} bytes)"
            )
        buf.extend(chunk)
    return bytes(buf)


def _split_payload(payload: bytes) -> Tuple[Dict, bytes]:
    """Payload → (header dict, raw body bytes); raises
    :class:`_FrameError` on a missing terminator, bad JSON, or a
    non-object header."""
    nl = payload.find(b"\n")
    if nl < 0:
        raise _FrameError("no header line in frame")
    try:
        header = json.loads(payload[:nl].decode("utf-8"))
    except (UnicodeDecodeError, json.JSONDecodeError) as e:
        raise _FrameError(f"header is not valid JSON: {e}")
    if not isinstance(header, dict):
        raise _FrameError(f"header must be a JSON object, got "
                          f"{type(header).__name__}")
    return header, payload[nl + 1:]


def _parse_image(header: Dict, body: bytes) -> np.ndarray:
    """Header + body → image array; raises :class:`_FrameError` on
    every malformation (missing or non-string tenant, undeclared dtype,
    bad shape, byte-count mismatch)."""
    tenant = header.get("tenant")
    if not isinstance(tenant, str) or not tenant:
        raise _FrameError("frame must carry a non-empty string 'tenant'")
    dtype_s = header.get("dtype", "uint8")
    if dtype_s not in WIRE_DTYPES:
        raise _FrameError(
            f"dtype {dtype_s!r} not in {sorted(WIRE_DTYPES)}"
        )
    shape = header.get("shape")
    if (
        not isinstance(shape, (list, tuple)) or len(shape) != 3
        or not all(isinstance(d, int) and d > 0 for d in shape)
        or shape[2] != 3
    ):
        raise _FrameError(f"shape must be [H, W, 3] positive ints, "
                          f"got {shape!r}")
    dtype = WIRE_DTYPES[dtype_s]
    expected = int(np.prod(shape)) * np.dtype(dtype).itemsize
    if len(body) != expected:
        raise _FrameError(
            f"image bytes {len(body)} != shape/dtype implied {expected}"
        )
    return np.frombuffer(body, dtype=dtype).reshape(shape)


def _parse_frame(payload: bytes) -> Tuple[Dict, np.ndarray]:
    """Payload → (header dict, image array); raises :class:`_FrameError`
    on every malformation."""
    header, body = _split_payload(payload)
    return header, _parse_image(header, body)


def _encode_detections(dets) -> List:
    """Per-class detections → JSON-safe nested lists (None stays null,
    float32 rounds through Python floats)."""
    out = []
    for cls in dets:
        if cls is None:
            out.append(None)
        else:
            out.append(np.asarray(cls).tolist())
    return out


def _det_meta(dets) -> List:
    """Per-class ``[dtype_name, shape]`` (null for null classes) so the
    receiving side can rebuild arrays byte-identical to the in-process
    result — floats survive the JSON round trip exactly (repr round-
    trips), so dtype+shape is the only information the wire loses."""
    meta = []
    for cls in dets:
        if cls is None:
            meta.append(None)
        else:
            a = np.asarray(cls)
            meta.append([a.dtype.name, list(a.shape)])
    return meta


def _ok_response(dets) -> Dict:
    return {
        "ok": True,
        "detections": _encode_detections(dets),
        "det_meta": _det_meta(dets),
    }


def decode_detections(detections: List, det_meta: Optional[List] = None
                      ) -> List:
    """Inverse of the response encoding: nested lists (+ optional
    ``det_meta``) → per-class arrays.  With meta present the arrays are
    byte-identical to what the serving engine returned in-process;
    without it (a pre-meta server) classes decode as float32."""
    if det_meta is None:
        det_meta = [None] * len(detections)
    out = []
    for cls, meta in zip(detections, det_meta):
        if cls is None:
            out.append(None)
        elif meta is None:
            out.append(np.asarray(cls, dtype=np.float32))
        else:
            dtype_s, shape = meta
            out.append(
                np.asarray(cls, dtype=np.dtype(dtype_s)).reshape(
                    [int(d) for d in shape]
                )
            )
    return out


class _ConnState:
    """Per-connection send serialization + pipelined in-flight count.

    The send lock orders response frames from concurrent engine
    completion callbacks (pipelined responses race each other and the
    handler thread); ``inflight`` distinguishes a quiet-but-working
    pipelined client from a half-open one at read-timeout time."""

    def __init__(self, conn: socket.socket):
        self.conn = conn
        self._lock = make_lock("Frontend._conn")
        self._inflight = 0

    def begin(self) -> None:
        with self._lock:
            self._inflight += 1

    def done(self) -> None:
        with self._lock:
            self._inflight -= 1

    def busy(self) -> bool:
        with self._lock:
            return self._inflight > 0

    def send(self, obj: Dict) -> None:
        data = json.dumps(obj).encode("utf-8")
        with self._lock:
            self.conn.sendall(_LEN.pack(len(data)) + data)


class Frontend:
    """Socket intake bound to one :class:`ServingEngine`.

    ``port=0`` binds an ephemeral port (tests); read ``.port`` after
    ``start()``.  Counters: ``accepted`` connections, ``frames`` parsed,
    ``rejected_frames`` (malformed at the wire), ``pipelined`` frames
    served out-of-band, ``conn_timeouts`` (idle half-open connections
    reaped), ``conn_rejected`` (over the ``max_conns`` cap at accept),
    ``errors`` by code.

    ``conn_read_timeout`` reaps a connection that sends nothing for
    that long while no pipelined request of its is in flight (a client
    waiting on pipelined responses is quiet but not dead); ``None``
    disables the reaper.
    """

    def __init__(self, engine, host: str = "127.0.0.1", port: int = 0,
                 max_frame: int = 64 * 1024 * 1024, backlog: int = 16,
                 conn_read_timeout: Optional[float] = 300.0,
                 max_conns: int = 64):
        self.engine = engine
        self.host = host
        self.port = int(port)
        self.max_frame = int(max_frame)
        self.backlog = int(backlog)
        self.conn_read_timeout = (
            float(conn_read_timeout) if conn_read_timeout is not None
            else None
        )
        self.max_conns = int(max_conns)
        self._sock: Optional[socket.socket] = None
        self._accept_thread: Optional[threading.Thread] = None
        self._stopping = False
        self._lock = make_lock("Frontend._lock")
        self._conns: Dict[int, socket.socket] = {}
        self._handlers: List[threading.Thread] = []
        self._next_conn = 0
        self.accepted = 0
        self.frames = 0
        self.rejected_frames = 0
        self.pipelined = 0
        self.conn_timeouts = 0
        self.conn_rejected = 0
        self.errors: Dict[str, int] = {}

    # ---------------------------------------------------------- lifecycle
    def start(self) -> "Frontend":
        if self._sock is not None:
            return self
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind((self.host, self.port))
        s.listen(self.backlog)
        self.port = s.getsockname()[1]
        self._sock = s
        self._stopping = False
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="frontend-accept", daemon=True
        )
        self._accept_thread.start()
        return self

    def stop(self) -> None:
        """Close the listener and every live connection; join the accept
        loop and all handler threads (in-flight requests resolve first —
        the engine owns their futures, not the sockets)."""
        self._stopping = True
        sock = self._sock
        self._sock = None
        if sock is not None:
            # shutdown BEFORE close: closing a listener does not wake a
            # thread blocked in accept() on Linux — shutdown does
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                sock.close()
            except OSError:
                pass
        with self._lock:
            conns = list(self._conns.values())
            handlers = list(self._handlers)
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass
        if self._accept_thread is not None:
            self._accept_thread.join(timeout=5.0)
            self._accept_thread = None
        for h in handlers:
            h.join(timeout=5.0)

    def __enter__(self) -> "Frontend":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ------------------------------------------------------------- server
    def _accept_loop(self) -> None:
        while not self._stopping:
            sock = self._sock
            if sock is None:
                return
            try:
                conn, _addr = sock.accept()
            except OSError:
                return  # listener closed by stop()
            with self._lock:
                if len(self._conns) >= self.max_conns:
                    self.conn_rejected += 1
                    h = None
                else:
                    cid = self._next_conn
                    self._next_conn += 1
                    self._conns[cid] = conn
                    self.accepted += 1
                    # prune finished handlers so a long-lived server's
                    # bookkeeping stays bounded by live connections
                    self._handlers = [
                        t for t in self._handlers if t.is_alive()
                    ]
                    h = threading.Thread(
                        target=self._handle, args=(cid, conn),
                        name=f"frontend-conn-{cid}", daemon=True,
                    )
                    self._handlers.append(h)
            if h is None:
                # over the cap: typed reject so the peer can tell "back
                # off and retry" from a network failure, then close
                try:
                    self._send(conn, {
                        "ok": False, "error": "conn_limit",
                        "message": f"connection limit {self.max_conns} "
                                   f"reached",
                    })
                except OSError:
                    pass
                try:
                    conn.close()
                except OSError:
                    pass
                continue
            h.start()

    def _note_error(self, code: str) -> None:
        with self._lock:
            self.errors[code] = self.errors.get(code, 0) + 1

    def _send(self, conn: socket.socket, obj: Dict) -> None:
        data = json.dumps(obj).encode("utf-8")
        conn.sendall(_LEN.pack(len(data)) + data)

    def _reject(self, state: _ConnState, rid: Optional[int], code: str,
                message: str) -> None:
        with self._lock:
            self.rejected_frames += 1
        self._note_error(code)
        obj = {"ok": False, "error": code, "message": message}
        if rid is not None:
            obj["id"] = rid
        state.send(obj)

    def _handle(self, cid: int, conn: socket.socket) -> None:
        state = _ConnState(conn)
        if self.conn_read_timeout is not None:
            conn.settimeout(self.conn_read_timeout)
        try:
            while not self._stopping:
                try:
                    hdr = _read_exact(conn, _LEN.size)
                except _ReadTimeout as t:
                    # half-open reaper: a connection idle past the read
                    # deadline at a frame boundary is reaped UNLESS its
                    # pipelined responses are still in flight (a client
                    # waiting on results is quiet, not dead); a timeout
                    # mid-header means a broken peer either way
                    if not t.mid_frame and state.busy():
                        continue
                    with self._lock:
                        self.conn_timeouts += 1
                    return
                if hdr is None:
                    return  # clean EOF
                (length,) = _LEN.unpack(hdr)
                if length == 0 or length > self.max_frame:
                    # hostile/broken length prefix: typed reject, then
                    # close — the stream offset can no longer be trusted
                    self._reject(state, None, "invalid_frame",
                                 f"frame length {length} outside "
                                 f"(0, {self.max_frame}]")
                    return
                try:
                    payload = _read_exact(conn, length)
                except _ReadTimeout:
                    # stalled mid-frame: the offset is untrustworthy
                    with self._lock:
                        self.conn_timeouts += 1
                    return
                if payload is None:
                    return
                with self._lock:
                    self.frames += 1
                try:
                    header, body = _split_payload(payload)
                except _FrameError as e:
                    self._reject(state, None, "invalid_frame", str(e))
                    continue
                rid = header.get("id")
                if rid is not None and not isinstance(rid, int):
                    self._reject(state, None, "invalid_frame",
                                 f"'id' must be an int, got {rid!r}")
                    continue
                v = header.get("v")
                if v is not None and v != WIRE_VERSION:
                    self._note_error("bad_version")
                    obj = {
                        "ok": False, "error": "bad_version",
                        "message": f"wire version {v!r} != speaker's "
                                   f"{WIRE_VERSION}",
                    }
                    if rid is not None:
                        obj["id"] = rid
                    state.send(obj)
                    continue
                op = header.get("op")
                if op is not None:
                    self._serve_op(state, rid, op)
                    continue
                try:
                    im = _parse_image(header, body)
                except _FrameError as e:
                    self._reject(state, rid, "invalid_frame", str(e))
                    continue
                self._serve_one(state, header, rid, im)
        except (ConnectionError, OSError):
            pass  # peer went away; per-request state lives in the engine
        finally:
            with self._lock:
                self._conns.pop(cid, None)
            try:
                conn.close()
            except OSError:
                pass

    def _serve_op(self, state: _ConnState, rid: Optional[int],
                  op) -> None:
        base: Dict = {"id": rid} if rid is not None else {}
        if op == "ping":
            state.send({"ok": True, "op": "ping", **base})
        elif op == "snapshot":
            state.send({
                "ok": True, "op": "snapshot",
                "engine": self.engine.snapshot(),
                "frontend": self.snapshot(),
                **base,
            })
        else:
            self._reject(state, rid, "invalid_frame",
                         f"unknown op {op!r}")

    def _serve_one(self, state: _ConnState, header: Dict,
                   rid: Optional[int], im: np.ndarray) -> None:
        deadline_ms = header.get("deadline_ms")
        deadline_s = (
            float(deadline_ms) / 1000.0 if deadline_ms is not None else None
        )
        kwargs = dict(
            deadline_s=deadline_s,
            model=header.get("model"),
            lane=header.get("lane"),
            tenant=header["tenant"],
        )
        # streaming mode (ISSUE 20): optional stream/frame header fields
        # put the request under per-stream in-order delivery; absent =
        # the legacy independent-image path, byte-identical behavior
        stream = header.get("stream")
        frame = header.get("frame")
        if stream is not None or frame is not None:
            if not isinstance(stream, str) or not stream:
                self._reject(state, rid, "invalid_frame",
                             f"'stream' must be a non-empty string, "
                             f"got {stream!r}")
                return
            if not isinstance(frame, int) or isinstance(frame, bool) \
                    or frame < 0:
                self._reject(state, rid, "invalid_frame",
                             f"'frame' must be a non-negative int, "
                             f"got {frame!r}")
                return
            kwargs["stream"] = stream
            kwargs["frame"] = frame
        if rid is None:
            # serial path: block the connection, respond in order
            try:
                dets = self.engine.submit(im, **kwargs).result()
            except Exception as e:  # noqa: BLE001 — typed taxonomy on wire
                code = _classify(e)
                self._note_error(code)
                state.send({
                    "ok": False, "error": code, "message": repr(e),
                })
                return
            state.send(_ok_response(dets))
            return
        # pipelined path: submit without blocking; the response frame —
        # tagged with the request id — goes out whenever the engine
        # resolves, possibly after later ids on this connection
        with self._lock:
            self.pipelined += 1
        state.begin()
        try:
            fut = self.engine.submit(im, **kwargs)
        except Exception as e:  # noqa: BLE001 — typed taxonomy on wire
            state.done()
            code = _classify(e)
            self._note_error(code)
            state.send({
                "ok": False, "error": code, "message": repr(e), "id": rid,
            })
            return
        fut.add_done_callback(
            lambda f: self._finish_pipelined(state, rid, f)
        )

    def _finish_pipelined(self, state: _ConnState, rid: int, fut) -> None:
        try:
            dets = fut.result()
        except Exception as e:  # noqa: BLE001 — typed taxonomy on wire
            code = _classify(e)
            self._note_error(code)
            obj = {"ok": False, "error": code, "message": repr(e),
                   "id": rid}
        else:
            obj = _ok_response(dets)
            obj["id"] = rid
        try:
            state.send(obj)
        except OSError:
            pass  # peer went away; the engine already settled the result
        finally:
            state.done()

    # ------------------------------------------------------ observability
    def snapshot(self) -> Dict:
        with self._lock:
            return {
                "port": self.port,
                "accepted": self.accepted,
                "frames": self.frames,
                "rejected_frames": self.rejected_frames,
                "pipelined": self.pipelined,
                "conn_timeouts": self.conn_timeouts,
                "conn_rejected": self.conn_rejected,
                "live_conns": len(self._conns),
                "errors": dict(self.errors),
            }


class FrontendClient:
    """Minimal blocking client for tests: one socket, one request
    at a time.  ``request`` returns the parsed response dict;
    ``send_raw`` ships arbitrary bytes (the malformed-frame matrix)."""

    def __init__(self, host: str, port: int, timeout: float = 30.0):
        self._sock = socket.create_connection((host, port), timeout=timeout)

    def request(self, im: np.ndarray, tenant: str,
                lane: Optional[str] = None,
                deadline_s: Optional[float] = None,
                model: Optional[str] = None,
                stream: Optional[str] = None,
                frame: Optional[int] = None) -> Dict:
        im = np.ascontiguousarray(im)
        dtype_s = {np.dtype(np.uint8): "uint8",
                   np.dtype(np.float32): "float32"}.get(im.dtype)
        if dtype_s is None:
            im = im.astype(np.float32)
            dtype_s = "float32"
        header = {
            "v": WIRE_VERSION,
            "tenant": tenant, "lane": lane, "model": model,
            "deadline_ms": (
                deadline_s * 1000.0 if deadline_s is not None else None
            ),
            "dtype": dtype_s, "shape": list(im.shape),
        }
        if stream is not None:
            header["stream"] = stream
        if frame is not None:
            header["frame"] = frame
        payload = json.dumps(header).encode("utf-8") + b"\n" + im.tobytes()
        self._sock.sendall(_LEN.pack(len(payload)) + payload)
        return self._recv()

    def op(self, op_name: str) -> Dict:
        """Send an admin frame (``ping``/``snapshot``) and return the
        response dict."""
        payload = json.dumps(
            {"v": WIRE_VERSION, "op": op_name}
        ).encode("utf-8") + b"\n"
        return self.send_raw(payload)

    def send_raw(self, payload: bytes, prefix: bool = True) -> Dict:
        """Ship ``payload`` (length-prefixed unless ``prefix=False``) and
        read one response — the malformed-frame test surface."""
        data = _LEN.pack(len(payload)) + payload if prefix else payload
        self._sock.sendall(data)
        return self._recv()

    def _recv(self) -> Dict:
        hdr = _read_exact(self._sock, _LEN.size)
        if hdr is None:
            raise ConnectionError("server closed connection")
        (length,) = _LEN.unpack(hdr)
        body = _read_exact(self._sock, length)
        if body is None:
            raise ConnectionError("server closed connection mid-response")
        return json.loads(body.decode("utf-8"))

    def close(self) -> None:
        try:
            self._sock.close()
        except OSError:
            pass

    def __enter__(self) -> "FrontendClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
