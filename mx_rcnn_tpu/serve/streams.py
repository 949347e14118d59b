"""Per-stream in-order completion + temporal proposal priming (ISSUE 20).

Streaming requests carry ``(stream_id, frame_idx)``.  The engine keeps
its whole pipeline — lane scheduling, replica trips and requeues,
hedging, containment resubmits, cascade escalation — completely unaware
of streams; ordering is enforced at the single exactly-once choke point
every one of those paths already funnels through:
``ServingEngine._resolve``.  The :class:`StreamTable` gates each
resolution there:

* a frame that is the stream's **next undelivered frame** fires
  immediately, then drains any buffered successors in frame order;
* a frame completing **early** (its predecessor still in flight — e.g.
  requeued off a tripped replica, or parked behind a hedge) is buffered
  and fires when the gap closes;
* cross-stream completions are never ordered against each other, and
  requests without a stream tag bypass the table entirely (zero cost on
  the legacy path).

Because the gate sits at settlement, the guarantee automatically
survives every redispatch mechanism: a requeue/hedge/escalation may
EXECUTE frames out of order, but results are DELIVERED in order.  A
frame settles exactly once (the table refuses a second settlement of the
same frame — graftlint R5 surface), and failures are ordered too: an
expired or poisoned frame fires its exception through the same gate, so
a client never observes frame N+1 before learning frame N's fate.

Drainer discipline: callbacks run OUTSIDE the table lock (they resolve
client futures, which run arbitrary done-callbacks), and a per-stream
single-drainer flag guarantees that even when several threads settle
frames of one stream concurrently, exactly one of them fires the ready
run — in order — while the others just deposit and leave.

Temporal proposal priming (train-free): frame N−1's detections are
likely frame N's objects moved a little, so seeding frame N's proposal
pool with the previous detections buys recall at small budgets without
touching any weights.  :func:`prime_proposals` implements the merge.
No caller outside its tests yet, and no measured recall (ROADMAP D8).
"""

from __future__ import annotations

from collections import deque
from typing import Callable, Dict, List, Optional

import numpy as np

from mx_rcnn_tpu.analysis.lockcheck import make_lock


class _StreamState:
    __slots__ = ("expected", "buffered", "draining", "last_registered",
                 "delivered")

    def __init__(self):
        # frame indices registered (submitted) but not yet delivered, in
        # frame order — strictly increasing by the monotone register rule
        self.expected: deque = deque()
        # early completions parked until their predecessors deliver:
        # frame -> zero-arg settle callback
        self.buffered: Dict[int, Callable[[], bool]] = {}
        self.draining = False
        self.last_registered = -1
        self.delivered = 0


class StreamTable:
    """In-order settlement gate, keyed by stream id (see module doc)."""

    def __init__(self):
        self._lock = make_lock("StreamTable._lock")
        self._streams: Dict[str, _StreamState] = {}
        # counters (engine snapshot)
        self.registered = 0
        self.delivered = 0
        self.buffered_now = 0
        self.buffered_peak = 0
        self.reordered = 0      # frames that had to wait for a predecessor
        self.cancelled = 0
        self.flushed = 0

    # ------------------------------------------------------------ intake
    def register(self, stream: str, frame: int) -> None:
        """Declare ``frame`` of ``stream`` in flight.  Must be called
        BEFORE the request can possibly settle (the engine registers
        before ``batcher.submit``).  Frames of one stream must arrive
        strictly increasing — a repeat or reorder at submit is a client
        protocol error (``ValueError``; the engine surfaces it as
        :class:`~mx_rcnn_tpu.serve.quarantine.InvalidRequest`)."""
        if not isinstance(stream, str) or not stream:
            raise ValueError("stream id must be a non-empty string")
        frame = int(frame)
        if frame < 0:
            raise ValueError(f"frame index must be >= 0, got {frame}")
        with self._lock:
            st = self._streams.get(stream)
            if st is None:
                st = self._streams[stream] = _StreamState()
            if frame <= st.last_registered:
                raise ValueError(
                    f"stream {stream!r}: frame {frame} not after "
                    f"{st.last_registered} — frames must be submitted "
                    f"strictly in order"
                )
            st.last_registered = frame
            st.expected.append(frame)
            self.registered += 1

    def cancel(self, stream: str, frame: int) -> None:
        """Withdraw a registration whose submit failed synchronously
        (rejected by the batcher, prep error...).  Without this the
        stream would deadlock: the permanent gap would buffer every
        later frame forever."""
        fire_run: List[Callable[[], bool]] = []
        with self._lock:
            st = self._streams.get(stream)
            if st is None:
                return
            try:
                st.expected.remove(frame)
            except ValueError:
                return
            self.cancelled += 1
            # removing the head gap may make buffered successors
            # deliverable — same drain discipline as settle
            if not st.draining and st.buffered:
                st.draining = True
                fire_run = self._collect(st)
                if not fire_run:
                    st.draining = False
        self._drain(stream, fire_run)

    # -------------------------------------------------------- settlement
    def settle(self, stream: str, frame: int,
               fire: Callable[[], bool]) -> bool:
        """Deliver ``frame``'s settlement callback in stream order:
        immediately if every earlier registered frame has delivered,
        else buffered until the gap closes.  Returns False (and does
        nothing) for a frame that is not outstanding — already
        delivered, or never registered: the exactly-once refusal."""
        frame = int(frame)
        with self._lock:
            st = self._streams.get(stream)
            if st is None:
                # stream not registered (the engine always registers at
                # submit; a flushed table at teardown also lands here):
                # deliver unordered rather than strand the future
                unordered = True
            elif frame not in st.expected or frame in st.buffered:
                # delivered or cancelled already — the exactly-once
                # refusal (graftlint R5 surface)
                return False
            elif st.expected[0] == frame and not st.draining:
                # the stream's next undelivered frame, no drainer
                # active: delivered straight through, never parked (the
                # buffered counters track only frames that WAIT)
                unordered = False
                st.expected.popleft()
                st.delivered += 1
                self.delivered += 1
                st.draining = True
                fire_run = [fire] + self._collect(st)
            else:
                unordered = False
                st.buffered[frame] = fire
                if st.expected[0] != frame:
                    self.reordered += 1
                self.buffered_now += 1
                if self.buffered_now > self.buffered_peak:
                    self.buffered_peak = self.buffered_now
                if st.draining:
                    # the active drainer picks this up before it exits
                    return True
                st.draining = True
                fire_run = self._collect(st)
                if not fire_run:
                    st.draining = False
                    return True
        if unordered:
            fire()
            return True
        self._drain(stream, fire_run)
        return True

    def _collect(self, st: _StreamState) -> List[Callable[[], bool]]:
        # caller holds self._lock: pop the maximal deliverable prefix
        run: List[Callable[[], bool]] = []
        while st.expected and st.expected[0] in st.buffered:
            f = st.expected.popleft()
            run.append(st.buffered.pop(f))
            st.delivered += 1
            self.delivered += 1
            self.buffered_now -= 1
        return run

    def _drain(self, stream: str, fire_run: List[Callable[[], bool]]) -> None:
        # single drainer per stream: fire OUTSIDE the lock (callbacks
        # resolve futures → arbitrary client code), then re-check for
        # frames that became deliverable while firing
        while fire_run:
            for fire in fire_run:
                try:
                    fire()
                except Exception:  # noqa: BLE001 — a client callback
                    pass           # must not wedge the stream's drainer
            with self._lock:
                st = self._streams.get(stream)
                if st is None:
                    return
                fire_run = self._collect(st)
                if not fire_run:
                    st.draining = False
                    return

    def flush(self) -> int:
        """Engine teardown: fire every buffered settlement (in frame
        order per stream, gaps skipped — the gap frames' futures are
        resolved by the engine's own leftover sweep).  No result that
        reached settlement is ever lost to a stop."""
        run: List[Callable[[], bool]] = []
        with self._lock:
            for st in self._streams.values():
                for f in sorted(st.buffered):
                    run.append(st.buffered.pop(f))
                    self.flushed += 1
                    self.buffered_now -= 1
                st.expected.clear()
                st.draining = False
        for fire in run:
            try:
                fire()
            except Exception:  # noqa: BLE001
                pass
        return len(run)

    # --------------------------------------------------------- reporting
    def snapshot(self) -> Dict:
        with self._lock:
            inflight = {
                s: len(st.expected) for s, st in self._streams.items()
                if st.expected
            }
            return {
                "streams": len(self._streams),
                "registered": self.registered,
                "delivered": self.delivered,
                "buffered_now": self.buffered_now,
                "buffered_peak": self.buffered_peak,
                "reordered": self.reordered,
                "cancelled": self.cancelled,
                "flushed": self.flushed,
                "inflight_frames": sum(inflight.values()),
            }


# ----------------------------------------------------- temporal priming
def prime_proposals(
    proposals: np.ndarray,
    prev_dets: Optional[np.ndarray],
    budget: int,
    prime_score: float = 1.0,
) -> np.ndarray:
    """Seed frame N's proposal pool with frame N−1's detections.

    ``proposals`` — (P, 5) [x1, y1, x2, y2, score] frame-N RPN output,
    score-descending; ``prev_dets`` — (D, ≥4) frame-(N−1) final
    detection boxes in the same coordinate frame (None/empty on the
    first frame of a stream); ``budget`` — the frame's total proposal
    budget.  Returns (≤budget, 5): the previous detections ranked FIRST
    (at ``prime_score``, above any RPN score — a tracked object is
    stronger evidence than one frame's objectness), then the top RPN
    proposals filling the remainder.  Train-free: nothing about the
    model changes, only which boxes the second stage gets to look at.
    """
    budget = int(budget)
    props = np.asarray(proposals, np.float32).reshape(-1, 5)
    if prev_dets is None or len(prev_dets) == 0:
        return props[:budget]
    seeds = np.asarray(prev_dets, np.float32)[:, :4]
    seeds = np.concatenate(
        [seeds, np.full((len(seeds), 1), prime_score, np.float32)], axis=1
    )[:budget]
    return np.concatenate([seeds, props[: max(budget - len(seeds), 0)]])
