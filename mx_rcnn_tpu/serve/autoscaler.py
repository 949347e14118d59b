"""Elastic replica autoscaling: grow/shrink the pool against observed load.

:class:`AutoScaler` is a background controller over a
:class:`~mx_rcnn_tpu.serve.router.ReplicaPool`.  Every ``interval`` it
reads three cheap signals — batcher queue depth, routable replica count,
and (when available) the interactive p99 — and moves the pool toward a
target size through the replica lifecycle that already exists:

* **grow** — ``pool.add_replica()`` constructs a fresh
  :class:`~mx_rcnn_tpu.serve.replica.Replica`, which warms its ladder on
  its own worker thread (WARMING → HEALTHY) and only then becomes
  routable.  Growth costs warmup compiles exactly once per replica;
  steady-state traffic still never compiles (each replica's CompileCache
  proves it).
* **shrink** — ``pool.remove_replica()`` removes the youngest replica
  from the routing set and stops it.  ``Replica.stop`` trips the
  replica, which fails its queued and in-flight dispatches with
  ``ReplicaDrained`` — and the router's requeue-never-drop loop
  re-dispatches them on a sibling, so a scale-down under load loses
  zero requests by construction (tests/test_autoscale.py::
  TestPoolElasticity asserts it byte-for-byte).

Oscillation control is :class:`ScaleBreaker`, a wall-clock port of
``parallel/elastic.py``'s :class:`RegrowPolicy`: every scale event
starts a ``cooldown``; a direction REVERSAL within ``flap_window``
seconds of the previous event is a flap and doubles the cooldown (capped
at ``max_backoff``), and the backoff ages back down after a clean
``flap_window``.  On top of the breaker, a decision must hold for
``samples`` consecutive ticks before it acts — a one-tick spike buys no
replica.

The controller thread holds no serve-stack locks while scaling: signals
are read through lock-free counters/snapshots, and ``add_replica`` /
``remove_replica`` take only the pool lock for the list swap (replica
construction and stop happen outside it).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

from mx_rcnn_tpu.analysis.lockcheck import make_lock

__all__ = ["ScalePolicy", "ScaleBreaker", "AutoScaler"]


@dataclass(frozen=True)
class ScalePolicy:
    """Autoscaler knobs (documented in SERVING.md's knob table).

    Thresholds are per-HEALTHY-replica queue pressure: grow when the
    backlog exceeds ``up_queue`` requests per routable replica, shrink
    when it falls below ``down_queue`` — the hysteresis gap between them
    is the first line of flap defense, the breaker the second."""

    min_replicas: int = 1
    max_replicas: int = 4
    interval: float = 0.05        # controller tick, seconds
    samples: int = 3              # consecutive agreeing ticks before acting
    up_queue: float = 4.0         # queued reqs per healthy replica → grow
    down_queue: float = 0.5       # queued reqs per healthy replica → shrink
    p99_slo_ms: Optional[float] = None  # interactive p99 above this → grow
    cooldown: float = 0.25        # seconds after any event before the next
    flap_window: float = 2.0      # reversal within this of an event = flap
    max_backoff: float = 4.0      # cooldown cap under repeated flapping

    def __post_init__(self):
        if self.min_replicas < 1:
            raise ValueError("min_replicas must be >= 1")
        if self.max_replicas < self.min_replicas:
            raise ValueError("max_replicas must be >= min_replicas")


class ScaleBreaker:
    """Wall-clock flap breaker — ``RegrowPolicy``'s logic with seconds in
    place of checkpoint boundaries.  ``allow(now)`` gates the next scale
    event; ``note(now, direction)`` records one and detects flaps
    (direction reversal inside the flap window doubles the cooldown,
    capped; a clean window closes the breaker back down)."""

    def __init__(self, cooldown: float = 0.25, flap_window: float = 2.0,
                 max_backoff: float = 4.0):
        self.cooldown = float(cooldown)
        self.flap_window = float(flap_window)
        self.max_backoff = float(max_backoff)
        self._backoff = self.cooldown
        self._last_t: Optional[float] = None
        self._last_dir: Optional[str] = None
        self._last_flap_t: Optional[float] = None
        self.flaps = 0
        self.suppressed = 0

    def allow(self, now: float) -> bool:
        if self._last_t is None:
            return True
        if self._last_flap_t is not None \
                and now - self._last_flap_t > self.flap_window:
            # flap history aged out: the breaker closes back down
            self._last_flap_t = None
            self._backoff = self.cooldown
        if now - self._last_t < self._backoff:
            self.suppressed += 1
            return False
        return True

    def note(self, now: float, direction: str) -> None:
        if (
            self._last_dir is not None
            and direction != self._last_dir
            and self._last_t is not None
            and now - self._last_t <= self.flap_window
        ):
            # the pool flapped: grew, then shrank (or vice versa) inside
            # the window — double the cooldown before the next attempt
            self.flaps += 1
            self._last_flap_t = now
            self._backoff = min(self._backoff * 2, self.max_backoff)
        self._last_t = now
        self._last_dir = direction

    def snapshot(self) -> Dict:
        return {
            "backoff_s": self._backoff,
            "flaps": self.flaps,
            "suppressed": self.suppressed,
        }


class AutoScaler:
    """Background replica-count controller for a ReplicaPool.

    ``signal_fn`` (injectable for tests) returns the decision
    inputs: ``{"queue_depth": int, "healthy": int, "p99_ms": float|None}``.
    The default reads the engine's batcher and the pool's routable set —
    both O(replicas) counter reads, no heavy snapshots on the tick path.
    """

    def __init__(
        self,
        pool,
        policy: Optional[ScalePolicy] = None,
        engine=None,
        signal_fn: Optional[Callable[[], Dict[str, Any]]] = None,
    ):
        self.pool = pool
        self.policy = policy or ScalePolicy()
        self.engine = engine
        self._signal_fn = signal_fn
        self.breaker = ScaleBreaker(
            cooldown=self.policy.cooldown,
            flap_window=self.policy.flap_window,
            max_backoff=self.policy.max_backoff,
        )
        self._lock = make_lock("AutoScaler._lock")
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._streak_dir: Optional[str] = None
        self._streak = 0
        # observability: bounded decision log + counters
        self.events: List[Dict[str, Any]] = []
        self.scale_ups = 0
        self.scale_downs = 0
        self.ticks = 0
        self._t0 = time.monotonic()

    # ---------------------------------------------------------- lifecycle
    def start(self) -> "AutoScaler":
        if self._thread is not None:
            return self
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, name="autoscaler", daemon=True
        )
        self._thread.start()
        return self

    def stop(self, timeout: float = 10.0) -> None:
        """Signal and JOIN the controller thread.  Any replica warmup the
        controller started runs on that replica's own worker; stopping
        the scaler only guarantees no FURTHER scale events — the engine
        closes the pool (stopping every replica, warming or not) right
        after this returns, which is why stop-before-pool-teardown
        ordering matters (ISSUE 16 satellite)."""
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=timeout)
            self._thread = None

    @property
    def running(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()

    # ----------------------------------------------------------- signals
    def signals(self) -> Dict[str, Any]:
        if self._signal_fn is not None:
            return self._signal_fn()
        queue_depth = 0
        if self.engine is not None:
            queue_depth = self.engine.batcher.pending()
        healthy = sum(1 for r in self.pool.replicas if r.routable)
        p99 = None
        if self.engine is not None and self.policy.p99_slo_ms is not None:
            lane = self.engine.metrics.by_lane.get("interactive")
            if lane is not None and lane["e2e"].count:
                p99 = lane["e2e"].percentile(99)
        return {"queue_depth": queue_depth, "healthy": healthy, "p99_ms": p99}

    def _desired_direction(self, sig: Dict[str, Any]) -> Optional[str]:
        n = len(self.pool.replicas)
        healthy = max(1, int(sig.get("healthy") or 0))
        depth = float(sig.get("queue_depth") or 0)
        p99 = sig.get("p99_ms")
        if n < self.policy.max_replicas:
            if depth >= self.policy.up_queue * healthy:
                return "up"
            if (
                self.policy.p99_slo_ms is not None
                and p99 is not None
                and p99 > self.policy.p99_slo_ms
            ):
                return "up"
        if n > self.policy.min_replicas \
                and depth <= self.policy.down_queue * healthy:
            return "down"
        return None

    # -------------------------------------------------------- controller
    def _loop(self) -> None:
        while not self._stop.wait(self.policy.interval):
            self.tick()

    def tick(self, now: Optional[float] = None) -> Optional[str]:
        """One decision cycle (public so tests can drive
        the controller synchronously with an injected clock).  Returns
        the action taken ("up"/"down") or None."""
        now = time.monotonic() if now is None else now
        self.ticks += 1
        sig = self.signals()
        want = self._desired_direction(sig)
        with self._lock:
            if want is None or want != self._streak_dir:
                self._streak_dir = want
                self._streak = 1 if want is not None else 0
                return None
            self._streak += 1
            if self._streak < self.policy.samples:
                return None
            if not self.breaker.allow(now):
                return None
            # act: reset the streak so the next event needs fresh evidence
            self._streak = 0
            self._streak_dir = None
        n_before = len(self.pool.replicas)
        if want == "up":
            self.pool.add_replica()
            self.scale_ups += 1
        else:
            if self.pool.remove_replica() is None:
                return None
            self.scale_downs += 1
        with self._lock:
            self.breaker.note(now, want)
            self.events.append({
                "t_s": round(now - self._t0, 4),
                "action": want,
                "replicas_before": n_before,
                "replicas_after": len(self.pool.replicas),
                "queue_depth": sig.get("queue_depth"),
                "healthy": sig.get("healthy"),
            })
            if len(self.events) > 256:
                del self.events[: len(self.events) - 256]
        return want

    # ------------------------------------------------------ observability
    def snapshot(self) -> Dict:
        with self._lock:
            events = list(self.events)
        return {
            "replicas": len(self.pool.replicas),
            "policy": {
                "min_replicas": self.policy.min_replicas,
                "max_replicas": self.policy.max_replicas,
                "up_queue": self.policy.up_queue,
                "down_queue": self.policy.down_queue,
                "samples": self.policy.samples,
            },
            "ticks": self.ticks,
            "scale_ups": self.scale_ups,
            "scale_downs": self.scale_downs,
            "breaker": self.breaker.snapshot(),
            "events": events,
        }
