"""Multi-host serving fleet: a wire-protocol gateway over N engine
processes (ISSUE 19).

Once a single host's serve path is device-bound (the benchmark's cell
``c4_serve_closed32`` is: PERF.md §5), the remaining throughput headroom
is ABOVE the host: run N complete engines — each its own process with its
own device, batcher, and :class:`~mx_rcnn_tpu.serve.frontend.Frontend`
— and fan live traffic over them through one :class:`FleetGateway`.
The ISSUE 16 length-prefixed wire protocol is the seam: the gateway is
just another wire client, so backends need zero new code to join a
fleet.

Three layers, mirroring the replica pool one level up:

* :class:`_BackendConn` — one persistent socket with request
  PIPELINING: every outbound frame carries a connection-unique ``id``;
  a reader thread correlates responses (which may return out of order)
  back to their futures.  This is where the wire throughput comes from:
  the ISSUE 16 ``FrontendClient`` is strictly one request per
  round-trip, so its ceiling is ``1/RTT`` regardless of backend depth.
* :class:`_BackendLink` — the per-host health gate: a small pool of
  pipelined connections, a latency EWMA + consecutive-failure breaker
  (``HealthPolicy`` semantics at host granularity), and reconnect
  probes over the same wire (``op: ping``).
* :class:`FleetGateway` — ``submit``/``snapshot`` compatible with
  :class:`~mx_rcnn_tpu.serve.engine.ServingEngine`, so ``run_load`` and
  every client drives a fleet exactly like one engine.  Routing is
  least-loaded with ``(tenant, lane, model, shape)`` affinity so
  bucket- and cache-affinity survive the hop; slow hosts hedge on a
  deadline-derived clock (``ReplicaPool._hedge_s`` one level up); a
  dead backend's in-flight requests REQUEUE to survivors
  (requeue-never-drop: a SIGKILL'd process loses zero requests,
  asserted by ``tests/test_fleet.py::TestChaosProcessKill``).  Wire error codes are
  rebuilt into the SAME typed exceptions the engine raises in-process
  (``UnknownTenant``, ``TenantOverBudget``, ``PoisonRequest``, …), so
  the taxonomy propagates verbatim through the gateway.

Exactly-once resolution: a request's future settles once — primary
response, hedge response, requeue error, or shutdown — guarded by the
``done`` flag under the gateway lock; late duplicates (a hedge loser,
a response racing a requeue) are counted ``abandoned`` and dropped.
Re-execution after a requeue or hedge is safe because inference is
pure: the same image bytes produce the same detections on any backend.

Observability merges the way the replica pool merges: ``snapshot()``
is the gateway's own routing/health counters plus per-backend link
counters; ``fleet_snapshot()`` additionally pulls every backend's
engine snapshot over the wire (``op: snapshot``) and sums them with
:func:`~mx_rcnn_tpu.serve.metrics.merge_snapshots`.

Lock order (one-way, leaf-ward): gateway → link → conn.  Cross-layer
upcalls (reader → link → gateway) always run with NO lock held.

``python -m mx_rcnn_tpu.serve.fleet --port 0 --service_ms 25`` runs a
stub backend process (digest runner that sleeps ``service_ms`` a
batch: a test fake, not a device model) used by the chaos tests;
``tools/serve.py --fleet N`` spawns real-model backends the same way.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import threading
import time
from concurrent.futures import Future
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from mx_rcnn_tpu.analysis.lockcheck import make_lock
from mx_rcnn_tpu.serve.frontend import (
    _LEN,
    _read_exact,
    WIRE_VERSION,
    decode_detections,
)
from mx_rcnn_tpu.serve.metrics import merge_snapshots

__all__ = [
    "BackendProc",
    "BadWireVersion",
    "FleetGateway",
    "InvalidWireFrame",
    "NoHealthyBackend",
    "error_for_code",
    "launch_backends",
    "spawn_stub_backends",
]


# ------------------------------------------------------------ taxonomy

class GatewayError(RuntimeError):
    """Gateway-local failure (not a backend engine verdict)."""


class BadWireVersion(GatewayError):
    """Backend rejected our wire version (``bad_version`` code)."""


class InvalidWireFrame(GatewayError):
    """Backend rejected a frame the gateway built (``invalid_frame``)."""


class NoHealthyBackend(GatewayError):
    """Every backend was down/unreachable for the whole failover
    budget — the host-level ``NoHealthyReplica``."""


def _code_errors() -> Dict[str, type]:
    """Wire code → the SAME exception class the engine raises
    in-process, so a gateway client catches exactly what an in-process
    caller would.  Imported lazily to keep module import light and
    cycle-free."""
    from mx_rcnn_tpu.serve.batcher import QueueFull
    from mx_rcnn_tpu.serve.buckets import BucketOverflow
    from mx_rcnn_tpu.serve.engine import DeadlineExceeded, EngineStopped
    from mx_rcnn_tpu.serve.quarantine import (
        InvalidRequest,
        PoisonRequest,
        RetriesExhausted,
    )
    from mx_rcnn_tpu.serve.registry import UnknownModel, UnknownVersion
    from mx_rcnn_tpu.serve.rollout import RolloutAborted
    from mx_rcnn_tpu.serve.tenancy import TenantOverBudget, UnknownTenant

    return {
        "unknown_tenant": UnknownTenant,
        "over_budget": TenantOverBudget,
        "unknown_model": UnknownModel,
        "unknown_version": UnknownVersion,
        "rollout_aborted": RolloutAborted,
        "invalid_request": InvalidRequest,
        "poison": PoisonRequest,
        "queue_full": QueueFull,
        "bucket_overflow": BucketOverflow,
        "exhausted": RetriesExhausted,
        "deadline": DeadlineExceeded,
        "engine_stopped": EngineStopped,
        "bad_version": BadWireVersion,
        "invalid_frame": InvalidWireFrame,
    }


def error_for_code(code: str, message: str = "") -> BaseException:
    """Rebuild a wire error frame into the typed exception the backend
    engine raised — the taxonomy crosses the gateway verbatim."""
    cls = _code_errors().get(code)
    if cls is None:
        return GatewayError(f"{code}: {message}")
    return cls(message or code)


# ------------------------------------------------------------- request

class _FleetRequest:
    """One gateway request: serialized image bytes plus routing state.
    ``done`` (guarded by the gateway lock) makes resolution
    exactly-once across primary/hedge/requeue racers."""

    __slots__ = (
        "future", "body", "dtype_s", "shape", "tenant", "lane", "model",
        "deadline_t", "t_submit", "t_dispatch", "hedge_at", "link",
        "attempts", "hedged", "done",
    )

    def __init__(self, body: bytes, dtype_s: str, shape: Tuple[int, ...],
                 tenant: str, lane: Optional[str], model: Optional[str],
                 deadline_t: Optional[float]):
        self.future: Future = Future()
        self.body = body
        self.dtype_s = dtype_s
        self.shape = shape
        self.tenant = tenant
        self.lane = lane
        self.model = model
        self.deadline_t = deadline_t
        self.t_submit = time.monotonic()
        self.t_dispatch = self.t_submit
        self.hedge_at: Optional[float] = None
        self.link = None          # primary _BackendLink of the live dispatch
        self.attempts = 0
        self.hedged = False
        self.done = False

    def header(self, deadline_ms: Optional[float]) -> Dict:
        return {
            "v": WIRE_VERSION,
            "tenant": self.tenant,
            "lane": self.lane,
            "model": self.model,
            "deadline_ms": deadline_ms,
            "dtype": self.dtype_s,
            "shape": list(self.shape),
        }


class _Sent:
    """One in-flight wire dispatch: the request plus its send
    timestamp (hedged requests have one entry per racing backend, each
    with its own clock)."""

    __slots__ = ("req", "t0")

    def __init__(self, req: _FleetRequest, t0: float):
        self.req = req
        self.t0 = t0


# ---------------------------------------------------------- connection

class _BackendConn:
    """One pipelined socket to a backend: a writer serialized by the
    conn lock, a reader thread correlating responses by ``id``.  On any
    tear (EOF, reset, bad frame) the connection dies ONCE, handing every
    still-in-flight entry to the owning link for requeue."""

    def __init__(self, owner: "_BackendLink", sock: socket.socket):
        self._owner = owner
        self._sock = sock
        self._lock = make_lock("_BackendConn._lock")
        self._next_id = 0
        self._inflight: Dict[int, _Sent] = {}
        self._dead = False
        self._reader = threading.Thread(
            target=self._read_loop, name="fleet-conn-reader", daemon=True
        )

    def start(self) -> "_BackendConn":
        self._reader.start()
        return self

    @property
    def alive(self) -> bool:
        return not self._dead

    def load(self) -> int:
        with self._lock:
            return len(self._inflight)

    def send(self, req: _FleetRequest, header: Dict) -> None:
        """Register the request under a fresh wire id and ship the
        frame; raises (after unregistering) if the socket is gone so
        the caller can fail over."""
        with self._lock:
            if self._dead:
                raise ConnectionError("backend connection is closed")
            rid = self._next_id
            self._next_id += 1
            wire_header = dict(header)
            wire_header["id"] = rid
            payload = (
                json.dumps(wire_header).encode("utf-8") + b"\n" + req.body
            )
            self._inflight[rid] = _Sent(req, time.monotonic())
            try:
                self._sock.sendall(_LEN.pack(len(payload)) + payload)
            except OSError:
                self._inflight.pop(rid, None)
                raise

    def _read_loop(self) -> None:
        try:
            while True:
                hdr = _read_exact(self._sock, _LEN.size)
                if hdr is None:
                    break
                (length,) = _LEN.unpack(hdr)
                body = _read_exact(self._sock, length)
                if body is None:
                    break
                resp = json.loads(body.decode("utf-8"))
                rid = resp.get("id")
                with self._lock:
                    entry = self._inflight.pop(rid, None)
                if entry is not None:
                    self._owner.on_response(entry, resp)
                # a response without a known id (e.g. the accept-time
                # conn_limit reject) carries no request to settle; the
                # close that follows it tears the conn below
        except (OSError, ValueError, ConnectionError):
            pass
        self.kill()

    def kill(self) -> None:
        """Tear the connection exactly once; orphaned in-flight entries
        go back to the link for requeue (never drop)."""
        with self._lock:
            if self._dead:
                return
            self._dead = True
            orphans = list(self._inflight.values())
            self._inflight.clear()
        try:
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._sock.close()
        except OSError:
            pass
        self._owner.on_conn_down(self, orphans)


# ---------------------------------------------------------------- link

class _BackendLink:
    """Health-gated handle on one backend host: a pool of pipelined
    connections plus the EWMA/consecutive-failure breaker the replica
    pool runs per replica, applied per host."""

    def __init__(self, gw: "FleetGateway", index: int, host: str,
                 port: int):
        self._gw = gw
        self.index = index
        self.host = host
        self.port = int(port)
        self._lock = make_lock("_BackendLink._lock")
        self._conns: List[_BackendConn] = []
        self._dialing = 0
        self.state = "up"        # optimistic: first dispatch probes it
        self.inflight = 0
        self.fails = 0
        self.trips = 0
        self.dispatched = 0
        self.completed = 0
        self.conn_drops = 0
        self.dials = 0
        self._ewma_ms: Optional[float] = None
        self._ewma_n = 0

    # ---- routing inputs (racy reads by design, like Replica.load) ----
    def load(self) -> int:
        return self.inflight

    def ewma(self) -> Optional[float]:
        return self._ewma_ms

    def ewma_armed(self) -> bool:
        return self._ewma_n >= self._gw.ewma_warmup

    # ---- connection pool --------------------------------------------
    def _conn_for(self) -> _BackendConn:
        with self._lock:
            alive = [c for c in self._conns if c.alive]
            if alive and len(alive) + self._dialing >= self._gw.conns_per_backend:
                return min(alive, key=lambda c: c.load())
            self._dialing += 1
        try:
            sock = socket.create_connection(
                (self.host, self.port), timeout=self._gw.connect_timeout
            )
        except OSError:
            with self._lock:
                self._dialing -= 1
            self._note_failure()
            raise
        # connect timeout must NOT become a read timeout: a pipelined
        # conn legitimately sits quiet for a whole model-forward
        sock.settimeout(None)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        conn = _BackendConn(self, sock).start()
        with self._lock:
            self._dialing -= 1
            self.dials += 1
            self._conns = [c for c in self._conns if c.alive] + [conn]
        return conn

    def dispatch(self, req: _FleetRequest,
                 deadline_ms: Optional[float]) -> None:
        """Ship one request on the least-loaded live connection; raises
        on dial/send failure (after noting it against the breaker) so
        the gateway fails over."""
        conn = self._conn_for()
        with self._lock:
            self.inflight += 1
            self.dispatched += 1
        try:
            conn.send(req, req.header(deadline_ms))
        except OSError:
            with self._lock:
                self.inflight -= 1
            self._note_failure()
            conn.kill()
            raise

    # ---- reader upcalls (no link lock held by the caller) -----------
    def on_response(self, entry: _Sent, resp: Dict) -> None:
        lat_ms = (time.monotonic() - entry.t0) * 1000.0
        with self._lock:
            self.inflight -= 1
            self.completed += 1
            self.fails = 0
            self.state = "up"
            if self._ewma_ms is None:
                self._ewma_ms = lat_ms
            else:
                d = self._gw.ewma_decay
                self._ewma_ms = d * self._ewma_ms + (1.0 - d) * lat_ms
            self._ewma_n += 1
        self._gw._finish_wire(entry.req, resp, self)

    def on_conn_down(self, conn: _BackendConn,
                     orphans: List[_Sent]) -> None:
        with self._lock:
            self.inflight -= len(orphans)
            self.conn_drops += 1
            self._conns = [
                c for c in self._conns if c is not conn and c.alive
            ]
        self._note_failure()
        if orphans:
            self._gw._requeue_from(self, [s.req for s in orphans])

    # ---- breaker -----------------------------------------------------
    def _note_failure(self) -> None:
        with self._lock:
            self.fails += 1
            if self.fails >= self._gw.fail_threshold and self.state == "up":
                self.state = "down"
                self.trips += 1

    def probe(self) -> bool:
        """Dial + ``op: ping`` round trip; a success revives the
        breaker.  Called from the gateway monitor with no lock held."""
        try:
            doc = wire_op(self.host, self.port, "ping",
                          timeout=self._gw.connect_timeout)
        except (OSError, ValueError):
            return False
        if not doc.get("ok"):
            return False
        with self._lock:
            self.state = "up"
            self.fails = 0
        return True

    def wire_snapshot(self, timeout: float) -> Optional[Dict]:
        try:
            return wire_op(self.host, self.port, "snapshot",
                           timeout=timeout)
        except (OSError, ValueError):
            return None

    def close(self) -> None:
        with self._lock:
            conns = list(self._conns)
            self._conns = []
        for c in conns:
            c.kill()

    def snapshot(self) -> Dict:
        with self._lock:
            return {
                "index": self.index,
                "addr": f"{self.host}:{self.port}",
                "state": self.state,
                "inflight": self.inflight,
                "dispatched": self.dispatched,
                "completed": self.completed,
                "fails": self.fails,
                "trips": self.trips,
                "conn_drops": self.conn_drops,
                "dials": self.dials,
                "ewma_ms": (
                    round(self._ewma_ms, 3)
                    if self._ewma_ms is not None else None
                ),
            }


def wire_op(host: str, port: int, op: str, timeout: float = 5.0) -> Dict:
    """One-shot admin frame (``ping``/``snapshot``) over a fresh
    socket; raises ``OSError``/``ValueError`` on any wire failure."""
    payload = json.dumps({"v": WIRE_VERSION, "op": op}).encode("utf-8") \
        + b"\n"
    with socket.create_connection((host, port), timeout=timeout) as s:
        s.settimeout(timeout)
        s.sendall(_LEN.pack(len(payload)) + payload)
        hdr = _read_exact(s, _LEN.size)
        if hdr is None:
            raise ConnectionError("backend closed before responding")
        (length,) = _LEN.unpack(hdr)
        body = _read_exact(s, length)
        if body is None:
            raise ConnectionError("backend closed mid-response")
        return json.loads(body.decode("utf-8"))


# ------------------------------------------------------------- gateway

class FleetGateway:
    """Wire-protocol front door over N backend engine processes.

    ``submit(im, deadline_s=, model=, lane=, tenant=)`` → ``Future`` and
    ``snapshot()`` match :class:`ServingEngine`, so every existing
    client — ``run_load`` included — drives a fleet unchanged.

    Knobs (host-level mirrors of the replica-pool policy):

    ``conns_per_backend``
        pipelined sockets per backend (wire parallelism per host).
    ``hedge_timeout`` / ``min_hedge_timeout`` / ``interactive_hedge_factor``
        cross-host hedge clock: half the remaining deadline clamped into
        ``[min, max]``, interactive requests hedge sooner.
    ``slow_factor`` / ``ewma_warmup`` / ``ewma_decay``
        latency-EWMA gate: once armed, a backend slower than
        ``slow_factor ×`` the fleet's fastest EWMA is routed around
        while a faster host is up.
    ``fail_threshold`` / ``revive_interval``
        consecutive failures tripping a host to ``down``, and how often
        the monitor re-probes a down host (``op: ping``).
    ``max_inflight``
        gateway admission cap; over it ``submit`` raises the same
        ``QueueFull`` the engine raises (clients back off identically).
    ``no_healthy_timeout``
        bounded wait for ANY host to come back before a requeued
        request fails with :class:`NoHealthyBackend`.
    """

    def __init__(
        self,
        backends: Sequence[Tuple[str, int]],
        conns_per_backend: int = 2,
        default_tenant: str = "fleet",
        hedge_timeout: float = 2.0,
        min_hedge_timeout: float = 0.05,
        interactive_hedge_factor: float = 0.5,
        slow_factor: float = 8.0,
        ewma_warmup: int = 3,
        ewma_decay: float = 0.8,
        fail_threshold: int = 3,
        revive_interval: float = 0.25,
        connect_timeout: float = 5.0,
        max_inflight: int = 1024,
        no_healthy_timeout: float = 2.0,
        max_attempts: Optional[int] = None,
    ):
        if not backends:
            raise ValueError("FleetGateway needs at least one backend")
        self.conns_per_backend = max(1, int(conns_per_backend))
        self.default_tenant = default_tenant
        self.hedge_timeout = float(hedge_timeout)
        self.min_hedge_timeout = float(min_hedge_timeout)
        self.interactive_hedge_factor = float(interactive_hedge_factor)
        self.slow_factor = float(slow_factor)
        self.ewma_warmup = int(ewma_warmup)
        self.ewma_decay = float(ewma_decay)
        self.fail_threshold = int(fail_threshold)
        self.revive_interval = float(revive_interval)
        self.connect_timeout = float(connect_timeout)
        self.max_inflight = int(max_inflight)
        self.no_healthy_timeout = float(no_healthy_timeout)
        # bounded failover, pool semantics: one attempt per backend + 1
        self.max_attempts = (
            int(max_attempts) if max_attempts is not None
            else len(backends) + 1
        )
        self._links = [
            _BackendLink(self, i, host, port)
            for i, (host, port) in enumerate(backends)
        ]
        self._lock = make_lock("FleetGateway._lock")
        self._live: set = set()
        self._stopping = False
        self._stop_event = threading.Event()
        self._monitor: Optional[threading.Thread] = None
        # routing counters (gateway level; links carry per-host ones)
        self.submitted = 0
        self.completed = 0
        self.failed = 0
        self.requeued = 0
        self.hedged = 0
        self.hedge_wins = 0
        self.abandoned = 0
        self.shed = 0
        self.no_healthy = 0

    # ---------------------------------------------------------- lifecycle
    def start(self) -> "FleetGateway":
        if self._monitor is not None:
            return self
        self._stop_event.clear()
        self._monitor = threading.Thread(
            target=self._monitor_loop, name="fleet-monitor", daemon=True
        )
        self._monitor.start()
        return self

    def stop(self) -> None:
        with self._lock:
            if self._stopping:
                return
            self._stopping = True
        self._stop_event.set()
        if self._monitor is not None:
            self._monitor.join(timeout=5.0)
            self._monitor = None
        for link in self._links:
            link.close()
        with self._lock:
            leftovers = list(self._live)
        from mx_rcnn_tpu.serve.engine import EngineStopped

        for req in leftovers:
            self._settle_err(req, EngineStopped("fleet gateway stopped"),
                             None)

    def __enter__(self) -> "FleetGateway":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ------------------------------------------------------------- intake
    def submit(self, im: np.ndarray, deadline_s: Optional[float] = None,
               model: Optional[str] = None, lane: Optional[str] = None,
               tenant: Optional[str] = None) -> Future:
        from mx_rcnn_tpu.serve.batcher import QueueFull
        from mx_rcnn_tpu.serve.engine import EngineStopped

        im = np.ascontiguousarray(im)
        dtype_s = {np.dtype(np.uint8): "uint8",
                   np.dtype(np.float32): "float32"}.get(im.dtype)
        if dtype_s is None:
            im = im.astype(np.float32)
            dtype_s = "float32"
        deadline_t = (
            time.monotonic() + float(deadline_s)
            if deadline_s is not None else None
        )
        req = _FleetRequest(
            body=im.tobytes(), dtype_s=dtype_s, shape=tuple(im.shape),
            tenant=tenant if tenant is not None else self.default_tenant,
            lane=lane, model=model, deadline_t=deadline_t,
        )
        with self._lock:
            if self._stopping:
                raise EngineStopped("fleet gateway stopped")
            if len(self._live) >= self.max_inflight:
                self.shed += 1
                raise QueueFull(
                    f"gateway at max_inflight {self.max_inflight}"
                )
            self.submitted += 1
            self._live.add(req)
        self._route(req, exclude=())
        return req.future

    # ------------------------------------------------------------ routing
    def _affinity(self, tenant: Optional[str], lane: Optional[str],
                  model: Optional[str], shape: Tuple[int, ...]) -> int:
        """Stable backend preference for a traffic key: under even load
        the same (tenant, lane, model, shape) keeps hitting the same
        host, so its compile cache and batch shapes stay warm there."""
        return hash((tenant, lane, model, tuple(shape))) % len(self._links)

    def _pick(self, req: _FleetRequest,
              exclude: Tuple = ()) -> Optional[_BackendLink]:
        links = [
            l for l in self._links
            if l.state == "up" and l not in exclude
        ]
        if not links:
            return None
        # latency-EWMA gate: with >=2 armed hosts, one slower than
        # slow_factor × the fastest is routed around while anyone
        # faster is up (the host-level HealthPolicy.latency_factor)
        armed = [l for l in links if l.ewma_armed()]
        if len(armed) >= 2:
            floor = min(l.ewma() for l in armed)
            fast = [
                l for l in links
                if not l.ewma_armed()
                or l.ewma() <= self.slow_factor * floor
            ]
            if fast:
                links = fast
        n = len(self._links)
        aff = self._affinity(req.tenant, req.lane, req.model, req.shape)
        return min(links, key=lambda l: (l.load(), (l.index - aff) % n))

    def _hedge_s(self, req: _FleetRequest, now: float) -> float:
        """Half the remaining deadline budget clamped into
        [min_hedge_timeout, hedge_timeout] (no deadline → the
        configured default); interactive requests hedge sooner —
        ``ReplicaPool._hedge_s`` applied across hosts."""
        if req.deadline_t is not None:
            t = max(self.min_hedge_timeout,
                    min(self.hedge_timeout,
                        (req.deadline_t - now) / 2.0))
        else:
            t = self.hedge_timeout
        if req.lane == "interactive":
            t *= self.interactive_hedge_factor
        return t

    def _send_to(self, link: _BackendLink, req: _FleetRequest,
                 primary: bool) -> None:
        """One wire dispatch; raises on dial/send failure."""
        now = time.monotonic()
        deadline_ms = None
        if req.deadline_t is not None:
            deadline_ms = max(0.0, (req.deadline_t - now) * 1000.0)
        if primary:
            with self._lock:
                req.link = link
                req.t_dispatch = now
                req.hedge_at = now + self._hedge_s(req, now)
                req.hedged = False
        link.dispatch(req, deadline_ms)

    def _route(self, req: _FleetRequest, exclude: Tuple) -> None:
        """Dispatch with bounded failover: each attempt charges the
        per-request budget (one per backend + 1); exhaustion or an
        expired deadline settles the future — never a silent drop."""
        from mx_rcnn_tpu.serve.engine import DeadlineExceeded

        while True:
            with self._lock:
                if req.done or self._stopping:
                    if not req.done:
                        stopping = True
                    else:
                        return
                else:
                    stopping = False
                    req.attempts += 1
                attempts = req.attempts
            if stopping:
                from mx_rcnn_tpu.serve.engine import EngineStopped

                self._settle_err(
                    req, EngineStopped("fleet gateway stopped"), None
                )
                return
            if attempts > self.max_attempts:
                with self._lock:
                    self.no_healthy += 1
                self._settle_err(req, NoHealthyBackend(
                    f"failover budget spent ({self.max_attempts} attempts)"
                ), None)
                return
            if (req.deadline_t is not None
                    and time.monotonic() >= req.deadline_t):
                self._settle_err(req, DeadlineExceeded(
                    "deadline expired before a backend accepted the "
                    "request"
                ), None)
                return
            link = self._pick(req, exclude=exclude)
            if link is None:
                if not self._wait_for_up(req):
                    with self._lock:
                        self.no_healthy += 1
                    self._settle_err(req, NoHealthyBackend(
                        f"no backend healthy within "
                        f"{self.no_healthy_timeout}s"
                    ), None)
                    return
                exclude = ()
                continue
            try:
                self._send_to(link, req, primary=True)
                return
            except (OSError, ConnectionError):
                exclude = (link,)
                continue

    def _wait_for_up(self, req: _FleetRequest) -> bool:
        """Bounded poll for any host to revive (the monitor probes in
        parallel) — mirrors ``ReplicaPool._wait_for_healthy``."""
        t_end = time.monotonic() + self.no_healthy_timeout
        if req.deadline_t is not None:
            t_end = min(t_end, req.deadline_t)
        while time.monotonic() < t_end:
            if any(l.state == "up" for l in self._links):
                return True
            if req.done:
                return False
            time.sleep(0.01)
        return any(l.state == "up" for l in self._links)

    # ----------------------------------------------------- link upcalls
    def _finish_wire(self, req: _FleetRequest, resp: Dict,
                     link: _BackendLink) -> None:
        if resp.get("ok"):
            dets = decode_detections(
                resp.get("detections", []), resp.get("det_meta")
            )
            self._settle_ok(req, dets, link)
        else:
            err = error_for_code(
                resp.get("error", "error"), resp.get("message", "")
            )
            self._settle_err(req, err, link)

    def _requeue_from(self, link: _BackendLink,
                      reqs: List[_FleetRequest]) -> None:
        """A dead connection's in-flight requests go to survivors —
        requeue-never-drop at host scope.  Re-execution is safe
        (inference is pure); a duplicate response after a requeue loses
        the done-flag race and is counted ``abandoned``."""
        from mx_rcnn_tpu.serve.engine import EngineStopped

        for req in reqs:
            with self._lock:
                if req.done:
                    continue
                stopping = self._stopping
                if not stopping:
                    self.requeued += 1
            if stopping:
                self._settle_err(
                    req, EngineStopped("fleet gateway stopped"), None
                )
            else:
                self._route(req, exclude=(link,))

    # -------------------------------------------------------- resolution
    def _settle_ok(self, req: _FleetRequest, dets: List,
                   link: Optional[_BackendLink]) -> bool:
        with self._lock:
            if req.done:
                self.abandoned += 1
                return False
            req.done = True
            self._live.discard(req)
            self.completed += 1
            if (req.hedged and link is not None
                    and link is not req.link):
                self.hedge_wins += 1
        req.future.set_result(dets)
        return True

    def _settle_err(self, req: _FleetRequest, err: BaseException,
                    link: Optional[_BackendLink]) -> bool:
        with self._lock:
            if req.done:
                self.abandoned += 1
                return False
            req.done = True
            self._live.discard(req)
            self.failed += 1
            if (req.hedged and link is not None
                    and link is not req.link):
                self.hedge_wins += 1
        req.future.set_exception(err)
        return True

    # ----------------------------------------------------------- monitor
    def _monitor_loop(self) -> None:
        last_probe = 0.0
        while not self._stop_event.wait(0.005):
            now = time.monotonic()
            with self._lock:
                due = [
                    r for r in self._live
                    if not r.done and not r.hedged
                    and r.hedge_at is not None and now >= r.hedge_at
                ]
            for req in due:
                target = self._pick(
                    req,
                    exclude=(req.link,) if req.link is not None else (),
                )
                if target is None:
                    continue
                with self._lock:
                    if req.done or req.hedged:
                        continue
                    req.hedged = True
                    self.hedged += 1
                try:
                    self._send_to(target, req, primary=False)
                except (OSError, ConnectionError):
                    pass  # primary still in flight; breaker noted it
            if now - last_probe >= self.revive_interval:
                last_probe = now
                for link in self._links:
                    if link.state == "down":
                        link.probe()

    # ------------------------------------------------------ observability
    def snapshot(self) -> Dict:
        with self._lock:
            g = {
                "backends": len(self._links),
                "submitted": self.submitted,
                "completed": self.completed,
                "failed": self.failed,
                "requeued": self.requeued,
                "hedged": self.hedged,
                "hedge_wins": self.hedge_wins,
                "abandoned": self.abandoned,
                "shed": self.shed,
                "no_healthy": self.no_healthy,
                "live": len(self._live),
            }
        return {
            "gateway": g,
            "links": [link.snapshot() for link in self._links],
        }

    def fleet_snapshot(self, timeout: float = 5.0) -> Dict:
        """Pull every reachable backend's engine+frontend snapshot over
        the wire and merge them the way the replica pool merges its
        replicas: counters sum, the per-backend list stays alongside."""
        engines, frontends, per_backend = [], [], []
        for link in self._links:
            doc = link.wire_snapshot(timeout)
            if doc and doc.get("ok"):
                engines.append(doc.get("engine") or {})
                frontends.append(doc.get("frontend") or {})
                per_backend.append({
                    "index": link.index, "addr": f"{link.host}:{link.port}",
                })
        return {
            "reachable": len(engines),
            "engines": merge_snapshots(engines),
            "frontends": merge_snapshots(frontends),
            "backends": per_backend,
            "gateway": self.snapshot(),
        }


# ----------------------------------------------------- backend process

class _FleetStubRunner:
    """Digest runner, the fake that ``tests/test_fleet.py`` puts behind
    a backend process: ``run`` sleeps ``service_ms`` per batch, serial
    per process, and returns a pure-function-of-pixels digest, so every
    byte-identity comparison through the gateway is exact (float64
    survives JSON).  The sleep keeps requests in flight long enough to
    kill a backend under them; it models no device."""

    LADDER = ((32, 32), (48, 64))

    def __init__(self, service_ms: float = 25.0, max_batch: int = 4):
        from mx_rcnn_tpu.serve.buckets import BucketLadder, CompileCache

        self.service_s = service_ms / 1000.0
        self.ladder = BucketLadder(self.LADDER)
        self.max_batch = max_batch
        self.cfg = None
        self.compile_cache = CompileCache()

    def warmup(self) -> int:
        for bh, bw in self.ladder:
            self.compile_cache.record(((self.max_batch, bh, bw, 3), "f32"))
        return self.compile_cache.misses

    def make_request(self, im, deadline=None):
        from mx_rcnn_tpu.serve.batcher import Request

        h, w = im.shape[:2]
        bh, bw = self.ladder.select(h, w)
        canvas = np.zeros((bh, bw, 3), np.float32)
        canvas[:h, :w] = im
        return Request(
            image=canvas,
            im_info=np.array([h, w, 1.0], np.float32),
            orig_hw=(h, w),
            bucket=(bh, bw),
            deadline=deadline,
        )

    def assemble(self, requests):
        images = [r.image for r in requests]
        while len(images) < self.max_batch:
            images.append(images[0])
        return {"images": np.stack(images)}

    def run(self, batch):
        if self.service_s:
            time.sleep(self.service_s)
        self.compile_cache.record((batch["images"].shape, "f32"))
        im = batch["images"].astype(np.float64)
        return {
            "digest": np.stack(
                [im.sum(axis=(1, 2, 3)), (im * im).sum(axis=(1, 2, 3))],
                axis=1,
            )
        }

    def detections_for(self, out, batch, index, orig_hw=None, thresh=None):
        return [out["digest"][index].copy()]


def run_stub_backend(port: int = 0, service_ms: float = 25.0,
                     max_batch: int = 4, linger_ms: float = 4.0,
                     max_queue: int = 512,
                     port_file: Optional[str] = None) -> None:
    """One stub backend process: engine + frontend, announce the bound
    port (stdout + optional file), serve until stdin closes (how the
    parent asks for a graceful exit — SIGKILL needs no cooperation)."""
    from mx_rcnn_tpu.serve.engine import ServingEngine
    from mx_rcnn_tpu.serve.frontend import Frontend

    runner = _FleetStubRunner(service_ms=service_ms, max_batch=max_batch)
    engine = ServingEngine(
        runner,
        max_linger=linger_ms / 1000.0,
        max_queue=max_queue,
    )
    with engine:
        fe = Frontend(engine, port=port)
        fe.start()
        try:
            announce = f"FLEET_BACKEND port={fe.port}"
            print(announce, flush=True)
            if port_file:
                tmp = port_file + ".tmp"
                with open(tmp, "w") as f:
                    f.write(f"{fe.port}\n")
                os.replace(tmp, port_file)
            sys.stdin.read()  # EOF = parent wants us gone
        except KeyboardInterrupt:
            pass
        finally:
            fe.stop()


class BackendProc:
    """A spawned backend process the gateway targets.  ``kill()`` is
    the chaos hammer (SIGKILL, no goodbye on the wire); ``stop()`` the
    graceful path (stdin EOF, then wait)."""

    def __init__(self, proc: subprocess.Popen, port: int):
        self.proc = proc
        self.port = port

    @property
    def addr(self) -> Tuple[str, int]:
        return ("127.0.0.1", self.port)

    def kill(self) -> None:
        self.proc.kill()
        self.proc.wait(timeout=10.0)

    def stop(self, timeout: float = 10.0) -> None:
        if self.proc.poll() is not None:
            return
        try:
            if self.proc.stdin is not None:
                self.proc.stdin.close()
            self.proc.wait(timeout=timeout)
        except (OSError, subprocess.TimeoutExpired):
            self.proc.kill()
            self.proc.wait(timeout=timeout)


def launch_backends(argv_base: List[str], n: int,
                    startup_timeout: float = 120.0,
                    env: Optional[Dict[str, str]] = None
                    ) -> List[BackendProc]:
    """Spawn ``n`` backend processes from ``argv_base`` (which must
    accept ``--port_file PATH``), wait for each to announce its port,
    and return the live handles.  On any startup failure everything
    already launched is torn down.

    Children inherit this process's environment plus ``env`` — the
    platform they run on is the CALLER's choice, never defaulted here.
    A chip belongs to one process: on a one-chip machine at most one
    backend can own it, so CPU fleets (stubs, tests) pass
    ``env={"JAX_PLATFORMS": "cpu"}`` explicitly."""
    import tempfile

    procs: List[Tuple[subprocess.Popen, str]] = []
    out: List[BackendProc] = []
    tmpdir = tempfile.mkdtemp(prefix="fleet_backends_")
    full_env = dict(os.environ)
    if env:
        full_env.update(env)
    try:
        for i in range(n):
            port_file = os.path.join(tmpdir, f"backend_{i}.port")
            # children announce on stdout; route it to OUR stderr so a
            # parent writing a JSON report to stdout stays parseable
            proc = subprocess.Popen(
                argv_base + ["--port_file", port_file],
                stdin=subprocess.PIPE,
                stdout=sys.stderr.fileno() if sys.stderr else None,
                env=full_env,
            )
            procs.append((proc, port_file))
        t_end = time.monotonic() + startup_timeout
        for proc, port_file in procs:
            port = None
            while time.monotonic() < t_end:
                if os.path.exists(port_file):
                    with open(port_file) as f:
                        port = int(f.read().strip())
                    break
                if proc.poll() is not None:
                    raise RuntimeError(
                        f"backend exited with {proc.returncode} before "
                        f"announcing its port"
                    )
                time.sleep(0.02)
            if port is None:
                raise RuntimeError(
                    f"backend did not announce a port within "
                    f"{startup_timeout}s"
                )
            out.append(BackendProc(proc, port))
        return out
    except Exception:
        for proc, _ in procs:
            try:
                proc.kill()
            except OSError:
                pass
        raise


def spawn_stub_backends(n: int, service_ms: float = 25.0,
                        max_batch: int = 4, linger_ms: float = 4.0,
                        max_queue: int = 512,
                        startup_timeout: float = 120.0
                        ) -> List[BackendProc]:
    """N stub backend processes (``python -m mx_rcnn_tpu.serve.fleet``)
    — the chaos tests' harness."""
    # -c (not -m): serve/__init__ imports this module, so runpy's -m
    # would execute it twice and warn about the sys.modules shadow
    argv = [
        sys.executable, "-c",
        "import sys; from mx_rcnn_tpu.serve.fleet import _backend_main; "
        "sys.exit(_backend_main(sys.argv[1:]))",
        "--port", "0",
        "--service_ms", str(service_ms),
        "--max_batch", str(max_batch),
        "--linger_ms", str(linger_ms),
        "--max_queue", str(max_queue),
    ]
    # stubs sleep instead of running a model: CPU by construction, so a
    # parent that holds the chip can still spawn them
    return launch_backends(argv, n, startup_timeout=startup_timeout,
                           env={"JAX_PLATFORMS": "cpu"})


def _backend_main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Fleet stub backend (digest runner + frontend)"
    )
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--service_ms", type=float, default=25.0)
    ap.add_argument("--max_batch", type=int, default=4)
    ap.add_argument("--linger_ms", type=float, default=4.0)
    ap.add_argument("--max_queue", type=int, default=512)
    ap.add_argument("--port_file", default=None)
    args = ap.parse_args(argv)
    run_stub_backend(
        port=args.port, service_ms=args.service_ms,
        max_batch=args.max_batch, linger_ms=args.linger_ms,
        max_queue=args.max_queue, port_file=args.port_file,
    )
    return 0


if __name__ == "__main__":
    sys.exit(_backend_main())
