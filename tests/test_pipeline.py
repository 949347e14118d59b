"""Device-resident step pipeline (core/pipeline.py): feed overlap,
K-late aux flush vs the divergence guard, donation safety, shutdown.

All CPU-fast: toy jitted steps (no detection model compiles); the feed
overlap assertions use the producer-side counters + ``wait_staged``, so
nothing here depends on wall-clock ratios.
"""

import threading
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.core import pipeline as pipeline_mod
from mx_rcnn_tpu.core.pipeline import (
    AsyncAuxSink,
    DeviceFeed,
    PipelinedLoop,
)
from mx_rcnn_tpu.core.resilience import (
    DivergencePolicy,
    GuardedLoop,
    host_copy,
)
from mx_rcnn_tpu.utils import faults


def make_toy_step(donate=True):
    """Tiny train-step twin: same contract as make_train_step (state,
    batch, rng[, lr_scale]) -> (state, aux), donated input state."""

    @partial(jax.jit, donate_argnums=(0,) if donate else ())
    def _step(state, batch, rng):
        w = state["w"] + batch["x"].sum()
        return (
            {"w": w, "step": state["step"] + 1},
            {"loss": jnp.abs(w) + 1.0},
        )

    def step(state, batch, rng, lr_scale=1.0):
        del lr_scale  # toy loss needs no LR; kwarg keeps the guard's
        return _step(state, batch, rng)  # backoff path exercised

    return step


def fresh_state():
    return jax.device_put({"w": jnp.float32(0.0), "step": jnp.int32(0)})


def toy_batches(n):
    return [{"x": np.full((2, 2), 0.1 * i + 0.05, np.float32)}
            for i in range(n)]


def state_bytes(state):
    return b"".join(
        np.asarray(x).tobytes()
        for x in jax.tree_util.tree_leaves(jax.device_get(state))
    )


def run_sync(batches, policy=None):
    faults.reset()
    state, rng = fresh_state(), jax.random.key(0)
    guard = GuardedLoop(make_toy_step(), policy=policy)
    losses = []
    for b in batches:
        state, aux, ok = guard.step(state, b, rng)
        if ok:
            losses.append(aux["loss"])
    return state, losses, guard


def run_pipelined(batches, k, policy=None):
    faults.reset()
    state, rng = fresh_state(), jax.random.key(0)
    loop = PipelinedLoop(make_toy_step(), policy=policy, aux_interval=k)
    ready_all, between_flush_fetches = [], []
    for b in batches:
        fetches_before = loop.sink.fetches
        state, ready, _ok = loop.step(state, b, rng)
        if not ready:  # mid-window step: no fetch may have happened
            between_flush_fetches.append(loop.sink.fetches - fetches_before)
        ready_all += ready
    state, ready, _ok = loop.flush(state)
    ready_all += ready
    return state, ready_all, loop, between_flush_fetches


# ---------------------------------------------------------------- DeviceFeed
def test_device_feed_overlap_and_order():
    """Producer counters prove batch N+1 was staged before step N
    retired: after the consumer takes batch N, the worker refills the
    staged queue while the 'step' runs, so every later get is a hit."""
    feed = DeviceFeed(iter(toy_batches(6)), depth=2)
    assert feed.wait_staged(2, timeout=10.0), "worker never staged ahead"
    got = [feed.__next__()]
    for _ in range(5):
        # batch N 'executes' here; N+1 must already be on device
        assert feed.wait_staged(1, timeout=10.0)
        got.append(feed.__next__())
    with pytest.raises(StopIteration):
        feed.__next__()
    feed.close()
    s = feed.stats()
    assert s["fed"] == 6
    assert s["staged_hits"] == 6  # every get (incl. first: wait_staged'd)
    assert s["feed_starved_after_first"] == 0
    assert s["occupancy"] == 1.0
    # order preserved, payload placed on device
    for i, b in enumerate(got):
        np.testing.assert_allclose(
            np.asarray(b["x"]), 0.1 * i + 0.05, rtol=1e-6
        )
        assert isinstance(b["x"], jax.Array)


def test_device_feed_close_unblocks_worker_and_closes_source():
    """close() must free a worker parked on a full queue and close the
    source iterator (the loader's PrefetchIterator in production)."""
    closed = threading.Event()

    class Source:
        def __iter__(self):
            return self

        def __next__(self):
            return {"x": np.zeros((2,), np.float32)}  # endless

        def close(self):
            closed.set()

    feed = DeviceFeed(Source(), depth=2)
    assert feed.wait_staged(2, timeout=10.0)  # queue full, worker parked
    feed.__next__()
    feed.close()
    assert closed.is_set(), "source.close() not called"
    assert not feed._thread.is_alive(), "worker leaked past close()"
    with pytest.raises(StopIteration):
        feed.__next__()
    feed.close()  # idempotent


def test_device_feed_propagates_worker_error():
    def source():
        yield {"x": np.zeros((2,), np.float32)}
        raise RuntimeError("placement failed")

    feed = DeviceFeed(source(), depth=2)
    got = []
    with pytest.raises(RuntimeError, match="placement failed"):
        for b in feed:
            got.append(b)
    assert len(got) == 1
    feed.close()


def test_device_feed_clean_shutdown_under_record_faults(monkeypatch):
    """TrainLoader (record_fail injection) → DeviceFeed: the substituted
    stream arrives complete and shutdown leaves no live threads."""
    import dataclasses

    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.data.loader import TrainLoader
    from mx_rcnn_tpu.data.synthetic import SyntheticDataset

    monkeypatch.setenv("MX_RCNN_FAULTS", "record_fail@1x99")
    faults.reset()
    cfg = generate_config("resnet50", "PascalVOC")
    cfg = cfg.replace(
        SHAPE_BUCKETS=((128, 128),),
        dataset=dataclasses.replace(
            cfg.dataset, NUM_CLASSES=4, SCALES=((128, 128),), MAX_GT_BOXES=4
        ),
    )
    roidb = SyntheticDataset(
        num_images=6, num_classes=4, image_size=(128, 128), max_boxes=2
    ).gt_roidb()
    loader = TrainLoader(roidb, cfg, 2, shuffle=False, seed=0)
    before = threading.active_count()
    with DeviceFeed(iter(loader), depth=2) as feed:
        got = list(feed)
    assert len(got) == 3  # record 1 substituted, batch count intact
    assert loader.record_failures == 1
    assert loader.substituted_records == 1
    deadline = time.monotonic() + 5.0
    while threading.active_count() > before and time.monotonic() < deadline:
        time.sleep(0.01)
    assert threading.active_count() <= before, "feed/prefetch thread leaked"


def test_device_feed_clean_shutdown_under_stall_fault(monkeypatch):
    """A step stalled by fault injection must not wedge feed shutdown:
    the worker keeps staging, close() reclaims it regardless."""
    monkeypatch.setenv("MX_RCNN_FAULTS", "stall@1:0.3")
    faults.reset()
    batches = toy_batches(4)
    state, rng = fresh_state(), jax.random.key(0)
    loop = PipelinedLoop(make_toy_step(), aux_interval=2)
    with DeviceFeed(iter(batches), depth=2) as feed:
        for b in feed:
            state, _ready, _ok = loop.step(state, b, rng)
    state, _ready, _ok = loop.flush(state)
    assert int(jax.device_get(state)["step"]) == 4


# ------------------------------------------------------- PipelinedLoop: aux
def test_k1_byte_identical_to_guarded_loop():
    batches = toy_batches(8)
    sync_state, sync_losses, _ = run_sync(batches)
    pipe_state, ready, loop, _ = run_pipelined(batches, k=1)
    assert state_bytes(pipe_state) == state_bytes(sync_state)
    assert [i for i, _ in ready] == list(range(8))
    assert [a["loss"] for _, a in ready] == sync_losses
    assert loop.window_rollbacks == 0


def test_k4_clean_run_loss_equal_and_state_identical():
    batches = toy_batches(8)
    sync_state, sync_losses, _ = run_sync(batches)
    pipe_state, ready, loop, _ = run_pipelined(batches, k=4)
    assert state_bytes(pipe_state) == state_bytes(sync_state)
    assert [a["loss"] for _, a in ready] == sync_losses
    assert loop.replayed_steps == 0


def test_deferred_fetch_counts_and_flush_ordering():
    """8 steps at K=4 → exactly 2 batched fetches, both at window
    boundaries; mid-window steps perform ZERO blocking fetches and
    return no aux."""
    _state, ready, loop, between = run_pipelined(toy_batches(8), k=4)
    assert loop.sink.fetches == 2
    assert loop.flushes == 2
    assert between == [0] * 6  # 6 mid-window steps, no fetch in any
    assert loop.sink.fetched_trees == 8
    # flush delivers in stream order
    assert [i for i, _ in ready] == list(range(8))


def test_divergence_detected_k_late_with_rollback(monkeypatch):
    """nan_loss@5 under K=4: the poison is caught at the window flush,
    the verified prefix is replayed from the retained window snapshot,
    the poison batch is skipped through the guard's budget — and the
    final state matches the synchronous guarded path bit-for-bit."""
    monkeypatch.setenv("MX_RCNN_FAULTS", "nan_loss@5")
    batches = toy_batches(8)
    sync_state, _losses, sync_guard = run_sync(batches)
    assert sync_guard.skipped_batches == 1  # the fault really fired
    pipe_state, ready, loop, between = run_pipelined(batches, k=4)
    assert state_bytes(pipe_state) == state_bytes(sync_state)
    assert loop.skipped_batches == 1
    assert loop.window_rollbacks == 1
    assert loop.replayed_steps >= 1  # verified prefix re-run
    assert [i for i, _ in ready] == [0, 1, 2, 3, 4, 6, 7]  # 5 skipped
    assert between == [0] * 6  # deferral intact through recovery


def test_transient_spike_recovers_without_skip(monkeypatch):
    """A one-shot spike (spike@6x1) caught K steps late retries clean:
    no batch skipped, all aux delivered, final state = fault-free run."""
    monkeypatch.setenv("MX_RCNN_FAULTS", "spike@6x1:1e9")
    batches = toy_batches(8)
    pipe_state, ready, loop, _ = run_pipelined(batches, k=3)
    monkeypatch.setenv("MX_RCNN_FAULTS", "")
    clean_state, _losses, _ = run_sync(batches)
    assert state_bytes(pipe_state) == state_bytes(clean_state)
    assert loop.skipped_batches == 0
    assert loop.window_rollbacks == 1
    assert [i for i, _ in ready] == list(range(8))


def test_guard_check_note_parity():
    """GuardedLoop.check_loss/note_good (the flush's hooks) apply the
    same policy as the in-loop check: spikes flagged after warmup."""
    g = GuardedLoop(
        make_toy_step(),
        policy=DivergencePolicy(warmup_steps=2, spike_factor=10.0),
    )
    for loss in (1.0, 1.1, 0.9):
        bad, _ = g.check_loss(loss)
        assert not bad
        g.note_good(loss)
    assert g.check_loss(float("nan"))[0]
    assert g.check_loss(1000.0)[0]  # >10x ema after warmup
    assert not g.check_loss(2.0)[0]
    assert g.last_loss == 0.9


# ---------------------------------------------------------------- donation
def test_donation_is_real_and_rollback_never_reuses(monkeypatch):
    """CPU donation genuinely deletes the input buffers (this pins the
    environment assumption the whole design rests on), and the pipelined
    rollback/replay path never touches a donated buffer — a use-after-
    donate would raise RuntimeError('Array has been deleted')."""
    step = make_toy_step(donate=True)
    state = fresh_state()
    donated_w = state["w"]
    _new_state, _aux = step(state, toy_batches(1)[0], jax.random.key(0))
    with pytest.raises(RuntimeError):
        np.asarray(donated_w)  # buffer gone: donation is real on CPU
    # full rollback path (window rollback + guard retry + skip + replay)
    # under donation: completes without use-after-donate
    monkeypatch.setenv("MX_RCNN_FAULTS", "nan_loss@3")
    pipe_state, _ready, loop, _ = run_pipelined(toy_batches(6), k=3)
    assert loop.skipped_batches == 1
    assert int(jax.device_get(pipe_state)["step"]) == 5  # 6 steps - 1 skip


def test_snapshots_own_their_memory():
    """Guard and window snapshots must be owning copies, not device_get
    views: CPU ``device_get`` is zero-copy, so a view of a donated buffer
    silently mutates (or segfaults) once XLA reuses the memory.  OWNDATA
    is deterministic — no allocator-timing luck involved."""
    step = make_toy_step(donate=True)

    def owns(tree):
        return all(
            np.asarray(leaf).flags["OWNDATA"]
            for leaf in jax.tree_util.tree_leaves(tree)
        )

    # host_copy itself
    snap = host_copy(fresh_state())
    assert owns(snap)
    # ...unlike the raw device_get it replaces (pins the hazard exists)
    view = jax.device_get(fresh_state())
    assert not all(
        np.asarray(leaf).flags["OWNDATA"]
        for leaf in jax.tree_util.tree_leaves(view)
    )
    # GuardedLoop's rollback snapshot
    guard = GuardedLoop(step, policy=DivergencePolicy(warmup_steps=0))
    state = fresh_state()
    state, _aux, _ok = guard.step(state, toy_batches(1)[0], jax.random.key(0))
    assert guard._snapshot is not None and owns(guard._snapshot)
    # PipelinedLoop's window snapshot
    pipe = PipelinedLoop(step, aux_interval=3)
    state, _r, _ok = pipe.step(state, toy_batches(2)[1], jax.random.key(0))
    assert pipe._win_snapshot is not None and owns(pipe._win_snapshot)


# ------------------------------------------------------------ AsyncAuxSink
def test_aux_sink_counts_stalls():
    sink = AsyncAuxSink()
    ready = {"loss": jax.device_put(jnp.float32(1.0))}
    jax.block_until_ready(ready["loss"])
    out = sink.fetch([ready])
    assert float(out[0]["loss"]) == 1.0
    assert sink.fetches == 1 and sink.fetched_trees == 1
    assert sink.fetch([]) == []
    assert sink.fetches == 1  # empty fetch not counted
    stalled, ready_at, got_at = sink.last_fetch
    assert not stalled and ready_at <= got_at


# ------------------------------------------------------- flush cycle records
AFTER_DRAIN = ("aux_get_ms", "check_ms", "snapshot_get_ms",
               "snapshot_copy_ms", "resume_ms")


@pytest.mark.parametrize("stalled", [False, True])
def test_one_record_per_completed_flush_cycle(monkeypatch, stalled):
    """10 steps at K=3: the flushes of windows 0, 3 and 6 are each
    followed by a dispatch and filed; the closing flush (window 9) and the
    head-of-stream snapshot are not.  The parts follow each other, so
    they sum to ``idle_ms``: from the drain's end where a result was still
    being computed, from the flush's start (the drain included) where
    none was.  The chip has no work at least while the state is copied."""
    monkeypatch.setattr(AsyncAuxSink, "_ready",
                        staticmethod(lambda trees: not stalled))
    _state, ready, loop, _ = run_pipelined(toy_batches(10), k=3)
    assert [i for i, _ in ready] == list(range(10))
    assert loop.flushes == 4 and loop.snapshots == 5
    cycles = loop.stats()["cycles"]
    assert [(c["step"], c["n"], c["stalled"]) for c in cycles] == [
        (0, 3, stalled), (3, 3, stalled), (6, 3, stalled)]
    for c in cycles:
        parts = [c[k] for k in AFTER_DRAIN]
        if not stalled:
            parts.append(c["drain_ms"])
        assert min(parts) >= 0
        assert sum(parts) == pytest.approx(c["idle_ms"], abs=0.01)
        assert c["idle_ms"] >= c["snapshot_get_ms"] + c["snapshot_copy_ms"]


def test_rolled_back_flush_files_no_cycle(monkeypatch):
    """nan_loss@4 under K=3: the window of steps 3-5 rolls back and is
    the guard's recovery (``window_rollbacks``), not a flush cycle."""
    monkeypatch.setenv("MX_RCNN_FAULTS", "nan_loss@4")
    _state, ready, loop, _ = run_pipelined(toy_batches(10), k=3)
    assert loop.window_rollbacks == 1 and loop.skipped_batches == 1
    assert [i for i, _ in ready] == [0, 1, 2, 3, 5, 6, 7, 8, 9]
    assert [c["step"] for c in loop.stats()["cycles"]] == [0, 6]


def test_cycle_records_are_bounded(monkeypatch):
    monkeypatch.setattr(pipeline_mod, "CYCLE_RECORDS", 2)
    _state, _ready, loop, _ = run_pipelined(toy_batches(10), k=2)
    assert loop.flushes == 5
    assert [c["step"] for c in loop.stats()["cycles"]] == [4, 6]


def test_rewind_drops_the_open_cycle():
    """After a device fault the elastic loop rewinds: the flush before it
    has no next dispatch on the same lineage, so it is not filed."""
    loop = PipelinedLoop(make_toy_step(), aux_interval=2)
    state, rng = fresh_state(), jax.random.key(0)
    for b in toy_batches(2):
        state, _ready, _ok = loop.step(state, b, rng)
    loop.rewind(2)
    state, _ready, _ok = loop.step(state, toy_batches(3)[2], rng)
    assert loop.stats()["cycles"] == []


# ------------------------------------------------------- render cache (LRU)
def test_render_cache_lru_no_starvation():
    """Past-capacity inserts evict oldest instead of permanently
    refusing new entries (the old soft-cap counter starved every record
    after the first 1024 forever)."""
    from mx_rcnn_tpu.data.loader import _RenderLRU

    lru = _RenderLRU(max_entries=3)
    ims = {k: np.full((2, 2), k, np.uint8) for k in range(5)}
    for k in range(5):
        lru.put(("im", False, k), ims[k])
    assert len(lru) == 3
    assert lru.evictions == 2
    # newest entries cached (no starvation) …
    for k in (2, 3, 4):
        assert lru.get(("im", False, k)) is ims[k]
    # … oldest evicted
    assert lru.get(("im", False, 0)) is None
    assert lru.get(("im", False, 1)) is None
    # recency protects a re-touched entry from the next eviction
    lru.get(("im", False, 2))
    lru.put(("im", False, 9), ims[0])
    assert lru.get(("im", False, 2)) is not None
    assert lru.get(("im", False, 3)) is None  # LRU victim was 3, not 2


def test_render_cache_used_by_loader():
    from mx_rcnn_tpu.data.loader import _RENDER_CACHE, _load_record_image
    from mx_rcnn_tpu.data.synthetic import SyntheticDataset

    roidb = SyntheticDataset(
        num_images=2, num_classes=4, image_size=(128, 128), max_boxes=1
    ).gt_roidb()
    _load_record_image(roidb[0])
    h0, m0 = _RENDER_CACHE.hits, _RENDER_CACHE.misses
    im = _load_record_image(roidb[0])
    assert _RENDER_CACHE.hits == h0 + 1 and _RENDER_CACHE.misses == m0
    np.testing.assert_array_equal(im, _load_record_image(roidb[0]))


# --------------------------------------------------------- PrefetchIterator
def test_prefetch_iterator_close_reclaims_worker():
    from mx_rcnn_tpu.data.loader import PrefetchIterator

    it = PrefetchIterator(iter(range(100)), prefetch=2)
    assert next(it) == 0
    t = it._thread
    assert t is not None and t.is_alive()
    it.close()
    assert not t.is_alive(), "prefetch worker leaked past close()"
    with pytest.raises(StopIteration):
        next(it)
    # context-manager form
    with PrefetchIterator(iter(range(3)), prefetch=2) as it2:
        assert next(it2) == 0
    assert it2._thread is None or not it2._thread.is_alive()
