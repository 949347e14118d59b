"""Device-resident step pipeline: double-buffered host→device feed,
K-late aux fetch, and a guarded loop that keeps training device-bound.

A step that hands its losses to the host every step leaves un-hidden
host work between steps — aux fetch, loader hand-off, dispatch residue
(ROOFLINE.md, "The host gap of training"; what it costs on the chip is
PERF.md §5).  The reference paper hid that slice behind MXNet's
async dependency engine (``rcnn/core/loader.py``'s prefetching
``AnchorLoader`` + KVStore); our loader stopped at host-side numpy
prefetch and every step blocked on a device→host ``aux`` fetch.  This
module closes the gap with three cooperating pieces:

- :class:`DeviceFeed` — extends the host prefetcher with a second,
  device-facing stage: a worker thread runs ``place_fn`` (sharding- and
  layout-aware ``jax.device_put``) on batch N+1 while the consumer's
  step N executes, keeping ``depth`` batches staged on device.  JAX
  transfers are async, so the H2D copy itself overlaps device compute;
  the staged queue keeps the *dispatch* path free of host assembly too.
  Occupancy counters (staged hits, feed-starved gets) turn "is the feed
  keeping up" into a counted number (``train_net(report=)["feed"]``).
- :class:`AsyncAuxSink` — the non-blocking metrics half: train steps
  return ``aux`` as device arrays and the sink fetches them in one
  batched ``device_get`` per flush instead of one blocking fetch per
  step, counting fetches and fetch *stalls* (a flush that had to wait
  on device results).
- :class:`PipelinedLoop` — :class:`~mx_rcnn_tpu.core.resilience
  .GuardedLoop` semantics with the aux check deferred ``aux_interval``
  steps: the NaN/spike guard still fires, merely K steps late, against
  the retained window snapshot.  On a flagged step the loop rolls back,
  *replays* the verified prefix (deterministic: the sampling rng folds
  ``state.step``, which the rollback restores), retries the poison step
  synchronously through the guard (LR backoff → skip, budgets intact),
  and re-runs the suffix that had executed on the poisoned lineage.
  ``aux_interval=1`` delegates to the guard directly and is
  byte-identical to the synchronous path (pinned by
  ``tests/test_pipeline.py``).  Each flush cycle is timed on the host
  (``stats()["cycles"]``): how long the chip has no work between the
  window's last step and the next window's first, and where that time
  goes.

Placement is unified across entry points through :func:`make_place_fn`:
single chip → ``jax.device_put`` (optionally into the compiled step's
input layouts, killing the input relayout copy), DP mesh →
``parallel/mesh.py :: shard_batch``, multi-host →
``parallel/distributed.py :: globalize_batch``.  ``core/fit.py``,
``tools/train_end2end.py``, ``core/tester.py :: pipelined`` and
``serve/runner.py`` all draw device-feed from here.
"""

from __future__ import annotations

import collections
import logging
import queue
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from mx_rcnn_tpu.core.resilience import (
    DivergencePolicy,
    GuardedLoop,
    StepWatchdog,
    _supports_lr_scale,
    host_copy,
)
from mx_rcnn_tpu.utils import faults, tracing

logger = logging.getLogger(__name__)

#: flush cycles :meth:`PipelinedLoop.stats` hands out: the newest this many
CYCLE_RECORDS = 64


# ----------------------------------------------------------------- placement
def make_place_fn(mesh=None, layouts=None) -> Callable[[Any], Any]:
    """One placement path for every feed consumer.

    ``mesh`` None → plain ``jax.device_put`` (into ``layouts`` — a pytree
    of ``jax.experimental.layout.Format`` matching the batch, as
    :func:`input_layouts_for` returns — when given, so the transfer
    lands in the layout the compiled step expects and XLA inserts no
    input relayout copy).  With a mesh: single
    process shards the leading axis (``shard_batch``); multi-process
    assembles the global array view (``globalize_batch``).
    """
    import jax

    if mesh is not None:
        from mx_rcnn_tpu.parallel import distributed
        from mx_rcnn_tpu.parallel.mesh import shard_batch

        if jax.process_count() > 1:
            return lambda batch: distributed.globalize_batch(batch, mesh)
        return lambda batch: shard_batch(batch, mesh)
    if layouts is not None:
        return lambda batch: jax.device_put(batch, layouts)
    return jax.device_put


def input_layouts_for(jitted, args, argnum: int = 1):
    """The compiled input formats (layout + sharding) of ``jitted``'s
    ``argnum``-th argument, as a pytree of
    ``jax.experimental.layout.Format``.

    ``args`` may be real arrays or ``jax.ShapeDtypeStruct`` trees (no
    data needed — lowering is abstract).  Feeding ``device_put`` these
    formats makes the host→device transfer deliver device-native tiling
    directly, so XLA need not insert an input relayout copy (on this
    round's chip the compiled formats ARE the default layouts, so it
    changes nothing there: ROADMAP D3).  A failure to
    lower or compile raises: it would fail the real dispatch too.
    """
    in_args, _kwargs = jitted.lower(*args).compile().input_formats
    return in_args[argnum]


def shape_structs(tree):
    """Pytree of arrays → matching ``jax.ShapeDtypeStruct`` tree (for
    abstract lowering in :func:`input_layouts_for`).  Device arrays keep
    their sharding, so params committed to one device (a pinned replica)
    lower for THAT device and the formats place the batch beside them."""
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            np.shape(x), x.dtype, sharding=getattr(x, "sharding", None)
        ),
        tree,
    )


# ---------------------------------------------------------------- DeviceFeed
class DeviceFeed:
    """Double-buffered host→device staging iterator.

    A daemon worker drains ``source`` and runs ``place_fn`` on each item
    ``depth`` items ahead of the consumer, so batch N+1's H2D transfer
    (async under JAX) overlaps batch N's step.  Composes with the
    loader's own host prefetch thread: decode/assembly → host queue →
    this worker (placement) → staged queue → consumer.

    Lifecycle: sentinel-based shutdown — :meth:`close` (or the context
    manager / GC) wakes the worker, drains staged references, joins the
    thread, and closes the source; worker exceptions re-raise in the
    consumer (a swallowed placement error would silently truncate an
    epoch).  Counters make feed health measurable:

    - ``fed`` — items handed to the consumer;
    - ``staged_hits`` — gets served from an already-staged item (the
      next batch was on device before the previous step retired);
    - ``feed_starved`` / ``feed_starved_after_first`` — gets that had to
      wait on the worker (the first get always waits: nothing has been
      staged yet when the consumer arrives instantly).
    """

    def __init__(
        self,
        source,
        place_fn: Optional[Callable[[Any], Any]] = None,
        depth: int = 2,
        name: str = "device-feed",
    ):
        import jax

        self._source = source
        self._place = place_fn if place_fn is not None else jax.device_put
        self.depth = max(1, int(depth))
        self._q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        self._closed = threading.Event()
        self._done = False
        self.fed = 0
        self.staged_hits = 0
        self.feed_starved = 0
        self.feed_starved_after_first = 0
        self.wait_s = 0.0  # consumer time blocked on the worker
        self._thread = threading.Thread(
            target=self._worker, name=name, daemon=True
        )
        self._thread.start()

    # -- worker side
    def _put(self, msg) -> bool:
        """Bounded put that gives up once the consumer is gone (same
        discipline as the loader's prefetch thread — a plain ``put``
        would park the worker forever on abandonment, leaking the thread
        plus ``depth`` staged batches)."""
        while not self._closed.is_set():
            try:
                self._q.put(msg, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    def _worker(self):
        try:
            for n, item in enumerate(self._source):
                with tracing.span(tracing.FEED_PLACE, batch=n):
                    staged = self._place(item)
                if not self._put(("item", staged)):
                    return
            self._put(("stop", None))
        except BaseException as e:  # noqa: BLE001 — handed to the consumer
            self._put(("err", e))

    # -- consumer side
    def __iter__(self):
        return self

    def __next__(self):
        if self._closed.is_set() or self._done:
            raise StopIteration
        try:
            kind, payload = self._q.get_nowait()
            staged = True
        except queue.Empty:
            staged = False
            t0 = time.perf_counter()
            with tracing.span(tracing.FEED_WAIT):
                while True:
                    try:
                        kind, payload = self._q.get(timeout=0.2)
                        break
                    except queue.Empty:
                        if self._closed.is_set():
                            raise StopIteration from None
            self.wait_s += time.perf_counter() - t0
        if kind == "stop":
            self._done = True
            raise StopIteration
        if kind == "err":
            self._done = True
            raise payload
        if staged:
            self.staged_hits += 1
        else:
            self.feed_starved += 1
            if self.fed > 0:
                self.feed_starved_after_first += 1
        self.fed += 1
        return payload

    def wait_staged(self, n: int = 1, timeout: float = 10.0) -> bool:
        """Block until ≥ ``n`` items are staged (or the stream ended /
        timed out).  Lets a consumer give the feed a deterministic head
        start; tests use it to make overlap assertions timing-free."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self._q.qsize() >= n or self._done or not self._thread.is_alive():
                return self._q.qsize() >= n
            time.sleep(0.005)
        return False

    def stats(self) -> Dict[str, Any]:
        fed = max(self.fed, 1)
        return {
            "fed": self.fed,
            "depth": self.depth,
            "staged_hits": self.staged_hits,
            "feed_starved": self.feed_starved,
            "feed_starved_after_first": self.feed_starved_after_first,
            "wait_s": round(self.wait_s, 4),
            "occupancy": round(self.staged_hits / fed, 4),
        }

    def close(self, timeout: float = 5.0) -> None:
        """Idempotent shutdown: signal the worker, drain staged
        references (frees pinned device buffers), join, close source."""
        self._closed.set()
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        if self._thread.is_alive():
            self._thread.join(timeout)
        close = getattr(self._source, "close", None)
        if close is not None:
            try:
                close()
            except Exception:  # noqa: BLE001 — best-effort source close
                pass

    def __enter__(self) -> "DeviceFeed":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):  # abandoned without close(): still reclaim
        try:
            self.close(timeout=0.2)
        except Exception:  # noqa: BLE001 — interpreter may be tearing down
            pass


# -------------------------------------------------------------- AsyncAuxSink
class AsyncAuxSink:
    """Batched, non-blocking aux fetcher.

    The synchronous loop pays one device→host fetch per step; the sink
    fetches a whole window of device aux trees in ONE ``device_get`` at
    flush points.  ``fetch_stalls`` counts flushes that had to wait on
    results still materializing (detected via ``Array.is_ready`` where
    the runtime exposes it) and ``fetch_stall_s`` accumulates the wait —
    the per-step host gap becomes a measured, regression-checked number.
    ``last_fetch`` holds the newest fetch's ``(stalled, ready_at,
    got_at)``: whether a result was still being computed, and the
    ``time.perf_counter()`` at which every result was ready and at which
    the copy to the host returned.
    """

    def __init__(self):
        self.pushes = 0  # aux trees deferred instead of fetched
        self.fetches = 0  # batched device_get calls
        self.fetched_trees = 0
        self.fetch_stalls = 0
        self.fetch_stall_s = 0.0
        self.last_fetch: Optional[Tuple[bool, float, float]] = None

    def defer(self, n: int = 1) -> None:
        self.pushes += n

    @staticmethod
    def _ready(trees) -> bool:
        import jax

        try:
            leaves = jax.tree_util.tree_leaves(trees)
            return all(
                x.is_ready() for x in leaves if hasattr(x, "is_ready")
            )
        except Exception:  # noqa: BLE001 — readiness probe is advisory
            return True

    def fetch(self, trees: List[Any]) -> List[Any]:
        """One batched device→host fetch of ``trees``; returns host
        copies in order."""
        import jax

        if not trees:
            return []
        self.fetches += 1
        self.fetched_trees += len(trees)
        stalled = not self._ready(trees)
        t0 = time.perf_counter()
        with tracing.span(tracing.GUARD_FETCH, n=len(trees)):
            jax.block_until_ready(trees)
            ready_at = time.perf_counter()
            out = jax.device_get(list(trees))
        got_at = time.perf_counter()
        if stalled:
            self.fetch_stalls += 1
            self.fetch_stall_s += got_at - t0
        self.last_fetch = (stalled, ready_at, got_at)
        return out

    def stats(self) -> Dict[str, Any]:
        return {
            "pushes": self.pushes,
            "fetches": self.fetches,
            "fetched_trees": self.fetched_trees,
            "fetch_stalls": self.fetch_stalls,
            "fetch_stall_ms": round(self.fetch_stall_s * 1e3, 3),
        }


# ------------------------------------------------------------- PipelinedLoop
@dataclass
class _Entry:
    idx: int
    batch: Any
    rng: Any
    aux: Any  # device aux tree, unfetched


class PipelinedLoop:
    """Guarded training loop with the aux fetch deferred K steps.

    ``aux_interval=1`` delegates every step to the wrapped
    :class:`GuardedLoop` — byte-identical to the synchronous path.
    ``aux_interval=K>1`` dispatches K steps back-to-back (the device
    never waits on a host fetch between them), then flushes: one batched
    aux fetch, losses checked **in stream order** against the guard's
    EMA/NaN policy.  A flagged step triggers rollback to the window
    snapshot, deterministic replay of the verified prefix, a synchronous
    guarded retry of the poison step (LR backoff → rollback → skip, the
    usual budgets), and a fresh re-run of the suffix that had executed
    on the poisoned lineage — so divergence recovery is merely K steps
    delayed, never weakened.

    ``step_fn`` may donate its input state (the flagship step does):
    every rollback re-places from the host-side window snapshot and no
    state object is ever passed to the device twice
    (``tests/test_pipeline.py`` pins this with real CPU donation).

    Callers must :meth:`flush` at epoch ends and before checkpoints /
    divergence decisions; ``step``/``flush`` return
    ``(state, ready, ok)`` where ``ready`` is a list of
    ``(step_index, host_aux)`` for newly verified steps (empty between
    flush points) and ``ok`` is False when a poison batch was skipped.

    Each flush cycle, from a window's flush to the return of the next
    window's first dispatch, is timed on the host and kept in ``cycles``
    (the newest :data:`CYCLE_RECORDS`): ``step`` (the window's first
    index, the ``rcnn.guard.flush`` span's id), ``n`` (its entries),
    ``stalled`` (a result was still being computed when the flush began)
    and the parts in ms, each starting where the one before ends:
    ``drain_ms`` (waiting for the window's results, while the device is
    busy), ``aux_get_ms``, ``check_ms``, ``snapshot_get_ms`` and
    ``snapshot_copy_ms`` (the state's device→host transfer, then
    ``host_copy``'s host-side copy of it), ``resume_ms`` (the caller's work, the next batch and the
    dispatch call).  ``idle_ms`` is the time the chip has no work: from
    the drain's end, or from the flush's start where nothing was left to
    drain (a lower bound).  A flush that rolled back, one that no
    dispatch follows and the head-of-stream snapshot get no record.
    """

    def __init__(
        self,
        step_fn: Callable,
        policy: Optional[DivergencePolicy] = None,
        watchdog: Optional[StepWatchdog] = None,
        snapshot_every: int = 1,
        place_fn: Optional[Callable[[Any], Any]] = None,
        aux_interval: int = 1,
    ):
        self._step_fn = step_fn
        self.aux_interval = max(1, int(aux_interval))
        self.guard = GuardedLoop(
            step_fn,
            policy=policy,
            watchdog=watchdog,
            snapshot_every=snapshot_every,
            place_fn=place_fn,
        )
        self._place = place_fn or (lambda tree: tree)
        self.sink = AsyncAuxSink()
        self._entries: List[_Entry] = []
        self._win_snapshot = None
        self._idx = 0
        # pipeline-specific counters (guard counters stay on self.guard)
        self.window_rollbacks = 0
        self.replayed_steps = 0
        self.flushes = 0
        self.snapshots = 0  # host copies of the state (window snapshots)
        self.snapshot_s = 0.0
        self.cycles: "collections.deque[Dict[str, Any]]" = collections.deque(
            maxlen=CYCLE_RECORDS)
        # the newest flush's times, until the next dispatch returns
        self._cycle: Optional[Tuple[Any, ...]] = None

    # -- delegated counters / snapshot surface (watchdog dumps, summaries)
    @property
    def watchdog(self):
        return self.guard.watchdog

    @watchdog.setter
    def watchdog(self, wd):
        self.guard.watchdog = wd

    @property
    def retried_steps(self) -> int:
        return self.guard.retried_steps

    @property
    def rollbacks(self) -> int:
        return self.guard.rollbacks + self.window_rollbacks

    @property
    def skipped_batches(self) -> int:
        return self.guard.skipped_batches

    @property
    def last_loss(self) -> float:
        return self.guard.last_loss

    @property
    def last_snapshot(self):
        if self.aux_interval > 1:
            return self._win_snapshot or self.guard.last_snapshot
        return self.guard.last_snapshot

    @property
    def steps_since_snapshot(self) -> int:
        if self.aux_interval > 1:
            return len(self._entries)
        return self.guard.steps_since_snapshot

    @property
    def pending(self) -> int:
        """Dispatched-but-unverified steps in the current window."""
        return len(self._entries)

    @property
    def next_index(self) -> int:
        """Stream index the next ``step`` call will dispatch at."""
        return self._idx if self.aux_interval > 1 else self.guard.step_index

    # -- elastic mesh-swap surface (parallel/elastic.py)
    def rebind(self, step_fn: Callable,
               place_fn: Optional[Callable[[Any], Any]] = None) -> None:
        """Swap the step/placement functions in place — the elastic loop
        rebuilds both against a shrunken or regrown mesh and the loop
        (and its guard's retry path) must dispatch through the new ones.
        Counters, divergence EMA, and budgets deliberately survive: the
        run continues, only the execution substrate changed."""
        self._step_fn = step_fn
        self.guard._step_fn = step_fn
        self.guard._lr_scale_ok = _supports_lr_scale(step_fn)
        if place_fn is not None:
            self._place = place_fn
            self.guard._place = place_fn

    def rewind(self, idx: int) -> None:
        """Drop every in-flight (unverified) window entry and reset the
        stream coordinate to ``idx``.  Used after a device fault: the
        window's device aux handles belong to the broken mesh and must
        never be fetched; the elastic loop re-places state from ITS host
        anchor snapshot and re-dispatches the window's batches through
        the rebound step, so the coordinates line up again."""
        self._entries = []
        self._win_snapshot = None
        self._cycle = None
        self._idx = idx
        self.guard.step_index = idx
        self.guard._snapshot = None
        self.guard._since_snapshot = 0

    # -- step execution
    def _snapshot(self, state, idx: int):
        """Owning host copy of ``state``: the window's rollback point.
        → ``(snap, got_at, done_at)``: the ``time.perf_counter()`` at which
        the device→host transfer and the host-side copy returned."""
        import jax

        t0 = time.perf_counter()
        with tracing.span(tracing.GUARD_SNAPSHOT, step=idx):
            got = jax.device_get(state)
            got_at = time.perf_counter()
            snap = host_copy(got)  # host arrays: the same owning copy
        done_at = time.perf_counter()
        self.snapshots += 1
        self.snapshot_s += done_at - t0
        return snap, got_at, done_at

    def _dispatch(self, state, batch, rng, idx: int, tag: str):
        wd = self.guard.watchdog
        if wd is not None:
            wd.arm(tag=str(idx) if tag == "step" else f"{tag}@{idx}")
        try:
            with tracing.span(tracing.STEP_DISPATCH, step=idx, tag=tag):
                return self._step_fn(state, batch, rng)
        finally:
            if wd is not None:
                wd.disarm()

    def step(
        self, state: Any, batch: Dict[str, Any], rng: Any
    ) -> Tuple[Any, List[Tuple[int, Dict[str, Any]]], bool]:
        if self.aux_interval <= 1:
            idx = self.guard.step_index
            with tracing.span(tracing.STEP_DISPATCH, step=idx, tag="sync"):
                state, aux, ok = self.guard.step(state, batch, rng)
            return state, ([(idx, aux)] if ok else []), ok
        idx = self._idx
        self._idx += 1
        self.guard.step_index = self._idx  # shared step coordinate space
        if self._win_snapshot is None:
            # BEFORE the first dispatch of a window, as an owning copy:
            # the step may donate the buffers a device_get view aliases
            self._win_snapshot, _got, _done = self._snapshot(state, idx)
        faults.stall(idx)  # test injection, no-op in production
        state, aux = self._dispatch(state, batch, rng, idx, tag="step")
        if self._cycle is not None:
            self._close_cycle(time.perf_counter())
        self._entries.append(_Entry(idx, batch, rng, aux))
        self.sink.defer()
        if len(self._entries) >= self.aux_interval:
            return self._flush(state)
        return state, [], True

    def flush(
        self, state: Any
    ) -> Tuple[Any, List[Tuple[int, Dict[str, Any]]], bool]:
        """Force a fetch/verify of all pending steps (epoch end,
        checkpoint, explicit divergence check)."""
        if self.aux_interval <= 1 or not self._entries:
            return state, [], True
        return self._flush(state)

    def _flush(self, state):
        self.flushes += 1
        entries, self._entries = self._entries, []
        start = time.perf_counter()
        rollbacks = self.window_rollbacks
        with tracing.span(tracing.GUARD_FLUSH, step=entries[0].idx,
                          n=len(entries)):
            state, ready, ok = self._verify(state, entries)
            # window verified end-to-end: retain its snapshot for the next
            checked_at = time.perf_counter()
            self._win_snapshot, got_at, done_at = self._snapshot(
                state, self._idx)
        # a window that rolled back is the guard's recovery, not a cycle
        self._cycle = None if self.window_rollbacks != rollbacks else (
            entries[0].idx, len(entries), start, self.sink.last_fetch,
            (checked_at, got_at, done_at))
        return state, ready, ok

    def _close_cycle(self, resumed_at: float) -> None:
        """File the open flush cycle: the next window's first dispatch has
        returned at ``resumed_at``."""
        (step, n, start, (stalled, ready_at, got_at),
         (checked_at, snap_got_at, snap_done_at)) = self._cycle
        self._cycle = None

        def ms(a, b):
            return round((b - a) * 1e3, 3)

        self.cycles.append({
            "step": step,
            "n": n,
            "stalled": stalled,
            "drain_ms": ms(start, ready_at),
            "aux_get_ms": ms(ready_at, got_at),
            "check_ms": ms(got_at, checked_at),
            "snapshot_get_ms": ms(checked_at, snap_got_at),
            "snapshot_copy_ms": ms(snap_got_at, snap_done_at),
            "resume_ms": ms(snap_done_at, resumed_at),
            "idle_ms": ms(ready_at if stalled else start, resumed_at),
        })

    def _verify(self, state, entries: List[_Entry]):
        """Fetch and check the window's losses in stream order; on a
        flagged step roll back, replay, retry and re-run the suffix.
        → ``(state, ready, ok)``."""
        ready: List[Tuple[int, Dict[str, Any]]] = []
        ok = True
        while entries:
            wd = self.guard.watchdog
            if wd is not None:
                wd.arm(tag=f"flush@{entries[0].idx}")
            try:
                hosts = self.sink.fetch([e.aux for e in entries])
            finally:
                if wd is not None:
                    wd.disarm()
            bad_at, why = -1, ""
            for i, (e, ah) in enumerate(zip(entries, hosts)):
                ah = dict(ah)
                loss = float(np.mean(np.asarray(ah.get("loss", np.nan))))
                loss = faults.corrupt_loss(e.idx, loss)
                ah["loss"] = loss
                bad, why = self.guard.check_loss(loss)
                if bad:
                    bad_at = i
                    break
                self.guard.note_good(loss)
                ready.append((e.idx, ah))
            if bad_at < 0:
                break
            e_bad = entries[bad_at]
            logger.warning(
                "pipelined flush: step %d diverged (%s) — rolling back "
                "the window, replaying %d verified step(s), retrying the "
                "poison step synchronously",
                e_bad.idx, why, bad_at,
            )
            self.window_rollbacks += 1
            state = self._place(self._win_snapshot)
            # deterministic replay of the verified prefix: state.step is
            # restored by the rollback, so the in-graph rng fold
            # reproduces the identical draws — no progress is lost
            for e in entries[:bad_at]:
                state, _ = self._dispatch(state, e.batch, e.rng, e.idx,
                                          tag="replay")
                self.replayed_steps += 1
            # synchronous guarded retry at the SAME step coordinate so
            # fault injection / logging line up with the stream position
            self.guard.step_index = e_bad.idx
            self.guard._snapshot = None  # guard re-snapshots healthy state
            state, ah, step_ok = self.guard.step(state, e_bad.batch, e_bad.rng)
            self.guard.step_index = self._idx
            if step_ok:
                ready.append((e_bad.idx, ah))
            else:
                ok = False
            # the suffix ran on the poisoned lineage — re-dispatch fresh
            redo, entries = entries[bad_at + 1:], []
            for e in redo:
                state, aux = self._dispatch(state, e.batch, e.rng, e.idx,
                                            tag="redo")
                self.replayed_steps += 1
                entries.append(_Entry(e.idx, e.batch, e.rng, aux))
        return state, ready, ok

    def stats(self) -> Dict[str, Any]:
        return {
            "aux_interval": self.aux_interval,
            "steps": self._idx if self.aux_interval > 1 else self.guard.step_index,
            "flushes": self.flushes,
            "snapshots": self.snapshots,
            "snapshot_ms": round(self.snapshot_s * 1e3, 3),
            "window_rollbacks": self.window_rollbacks,
            "replayed_steps": self.replayed_steps,
            "retried_steps": self.guard.retried_steps,
            "skipped_batches": self.guard.skipped_batches,
            **self.sink.stats(),
            "cycles": list(self.cycles),
        }
