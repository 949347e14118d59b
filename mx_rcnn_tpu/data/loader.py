"""Batch loaders: roidb → padded device-ready numpy batches.

Reference: ``rcnn/core/loader.py`` (``AnchorLoader`` / ``ROIIter`` /
``TestLoader``).  Radically simpler here because anchor-target assignment
and roi sampling moved *inside* the jitted graph: the loader only decodes
images, resizes into shape buckets, and pads gt boxes — no
``feat_sym.infer_shape``, no per-image ``assign_anchor`` on host, no
per-GPU slicing (sharding is a jax.sharding concern, not a loader
concern).

Keeps the reference's aspect-ratio grouping trick (``AnchorLoader``'s
aspect grouping): batches are drawn from one orientation bucket at a
time so every image in a batch pads into the same (H, W) bucket and the
jit cache stays bounded at #buckets graphs.

A small background-thread prefetcher overlaps cv2 decode with TPU steps
(the reference relied on MXNet's async engine for the same overlap).
"""

from __future__ import annotations

import logging
import os
import queue
import threading
from collections import OrderedDict
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.core.resilience import RetryPolicy, make_retry_policy
from mx_rcnn_tpu.data.assembler import AssemblyPool, default_assembly_workers
from mx_rcnn_tpu.data.image import load_image, pick_bucket, prepare_image
from mx_rcnn_tpu.utils import faults, tracing

logger = logging.getLogger(__name__)


class LoaderFaultBudgetExceeded(RuntimeError):
    """More records failed to load than the configured budget — aborting
    so silent data loss can't masquerade as training."""

class _RenderLRU:
    """Locked LRU of rendered synthetic images, keyed by
    ``(uri, flipped, seed, geometry)``.

    Bounds render-cache memory (~7 MB/entry at flagship size, cap via
    ``MX_RCNN_RENDER_CACHE``) while keeping the gate sets — which
    revisit the same few images every epoch/sweep — fully cached.  An
    LRU rather than the old first-come soft cap: that counter was
    unsynchronized across prefetch threads and never reclaimed, so a
    >1024-record train roidb permanently starved every later sweep back
    to re-rendering.  Recency eviction keeps whatever the CURRENT sweep
    touches hot instead.  Keying by value (not on the record dict) also
    makes flip-safety structural: a flipped twin shallow-copied from its
    source record (``append_flipped_images``) simply has a different
    key, so it can never be served the unflipped pixels.
    """

    def __init__(self, max_entries: int):
        self.max_entries = max(0, int(max_entries))
        self._lock = threading.Lock()
        self._entries: "OrderedDict[Tuple, np.ndarray]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key) -> Optional[np.ndarray]:
        with self._lock:
            im = self._entries.get(key)
            if im is None:
                self.misses += 1
            else:
                self._entries.move_to_end(key)
                self.hits += 1
            return im

    def put(self, key, im: np.ndarray) -> None:
        if self.max_entries <= 0:
            return
        with self._lock:
            self._entries[key] = im
            self._entries.move_to_end(key)
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)
                self.evictions += 1

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.hits = self.misses = self.evictions = 0


_RENDER_CACHE = _RenderLRU(int(os.environ.get("MX_RCNN_RENDER_CACHE", "1024")))

# Prepared-canvas LRU: the (padded image, im_info) PAIR after resize /
# normalize-or-quantize / bucket-pad — the ~80 ms/img assembly tail the
# render cache doesn't cover.  Eval sweeps revisit the
# same records every pass, so the second pass skips assembly entirely.
# Keyed by record identity AND every input of the prep math (scales,
# bucket, uint8 flag, normalization constants), so a hit is bit-identical
# to recomputation by construction.  Default OFF (entries=0): a train
# stream with flip augmentation rarely revisits a key before eviction,
# and a flagship canvas is ~3 MB — opt in via MX_RCNN_PREPARED_CACHE or
# :func:`set_prepared_cache` where revisits are the workload (repeated
# eval).
_PREPARED_CACHE = _RenderLRU(int(os.environ.get("MX_RCNN_PREPARED_CACHE", "0")))


def set_prepared_cache(max_entries: int) -> None:
    """Resize (and clear) the prepared-canvas LRU at runtime; the env
    var covers child processes."""
    _PREPARED_CACHE.clear()
    _PREPARED_CACHE.max_entries = max(0, int(max_entries))


def _prepared_key(rec: Dict, scales, bucket, uint8: bool, means, stds):
    """Cache key = record identity + every parameter of the prep math."""
    base = (rec["image"], bool(rec.get("flipped")))
    if "synthetic_seed" in rec:
        base += (rec["synthetic_seed"],)
    norm = (
        None if uint8
        else (tuple(np.ravel(means).tolist()), tuple(np.ravel(stds).tolist()))
    )
    return base + (tuple(scales), tuple(bucket), uint8, norm)


def _render_geometry(rec: Dict) -> Tuple:
    """What ``synthetic_image`` renders from besides the seed: the extent,
    the boxes with their classes, and whether polygons shape them."""
    return (
        int(rec["height"]), int(rec["width"]),
        np.asarray(rec["boxes"]).tobytes(),
        np.asarray(rec["gt_classes"]).tobytes(),
        rec.get("segmentation") is not None,
    )


def _load_record_image(rec: Dict) -> np.ndarray:
    if str(rec["image"]).startswith("synthetic://"):
        from mx_rcnn_tpu.data.synthetic import synthetic_image

        # synthetic records render from their OWN (already-flipped)
        # geometry — flipping again would move pixels back to the
        # unflipped positions while gt stays flipped, silently training
        # half the flip-augmented epoch on mismatched targets.  The
        # render is deterministic per (uri, flipped, seed), so the LRU
        # key is exactly that triple; at ~17 ms/render (noise
        # generation, one core) re-rendering was the e2e eval
        # bottleneck once the eval pipeline overlapped (disk-backed
        # datasets get the same effect from the OS page cache).
        # The uri names a record only within ITS dataset (every synthetic
        # dataset counts from ``synthetic://0``), and the cache is the
        # process's: the record's geometry is part of the key, or a second
        # dataset of another size or other boxes is served the first's
        # pixels.  Read-only downstream: prepare_image copies.
        key = (rec["image"], bool(rec.get("flipped")), rec["synthetic_seed"],
               _render_geometry(rec))
        im = _RENDER_CACHE.get(key)
        if im is None:
            im = synthetic_image(rec, rec["synthetic_seed"])
            _RENDER_CACHE.put(key, im)
        return im
    im = load_image(rec["image"])
    if rec.get("flipped"):
        im = im[:, ::-1]
    return im


def make_batch(
    records: Sequence[Dict],
    cfg: Config,
    bucket: Tuple[int, int],
    images: Optional[Sequence[np.ndarray]] = None,
    proposal_count: int = 0,
    seeds: Optional[Sequence[int]] = None,
    with_masks: bool = False,
    uint8_images: bool = False,
) -> Dict[str, np.ndarray]:
    """Assemble one padded train batch from roidb records.

    Boxes are scaled by the resize factor (the reference scales gt_boxes by
    im_scale in ``get_rpn_batch``); gt arrays padded to MAX_GT_BOXES.

    ``proposal_count`` > 0 additionally emits ``proposals``/``prop_valid``
    padded to that count from each record's ``proposals`` field (the
    ROIIter role: Fast-RCNN batches from a proposal roidb,
    ``rcnn/io/rcnn.py :: get_rcnn_batch``).

    ``with_masks`` emits ``gt_masks`` (n, G, M, M) uint8 box-frame
    bitmaps (M = TRAIN.MASK_GT_SIZE) for Mask R-CNN training — records
    without a ``segmentation`` field get all-ones bitmaps (rectangle
    targets, the box-only convention).  Bitmaps are box-relative, so the
    resize scale does not affect them.
    """
    scales = cfg.dataset.SCALES[0]
    g = cfg.dataset.MAX_GT_BOXES
    n = len(records)
    bh, bw = bucket
    out_images = np.zeros(
        (n, bh, bw, 3), np.uint8 if uint8_images else np.float32
    )
    im_info = np.zeros((n, 3), np.float32)
    gt_boxes = np.zeros((n, g, 5), np.float32)
    gt_valid = np.zeros((n, g), bool)
    if with_masks:
        from mx_rcnn_tpu.data.masks import record_gt_masks

        msize = cfg.TRAIN.MASK_GT_SIZE
        gt_masks = np.zeros((n, g, msize, msize), np.uint8)
    if proposal_count:
        proposals = np.zeros((n, proposal_count, 4), np.float32)
        prop_valid = np.zeros((n, proposal_count), bool)
    for i, rec in enumerate(records):
        # prepared-canvas cache: only for loader-owned loads (a caller
        # passing ``images`` may have substituted fault slots, whose
        # pixels no longer match the record key)
        key = None
        prepared = None
        if images is None and _PREPARED_CACHE.max_entries > 0:
            key = _prepared_key(
                rec, scales, bucket, uint8_images,
                cfg.network.PIXEL_MEANS, cfg.network.PIXEL_STDS,
            )
            prepared = _PREPARED_CACHE.get(key)
        if prepared is None:
            im = images[i] if images is not None else _load_record_image(rec)
            prepared = prepare_image(
                im,
                scales[0],
                scales[1],
                cfg.network.PIXEL_MEANS,
                cfg.network.PIXEL_STDS,
                [bucket],
                uint8_out=uint8_images,
            )
            if key is not None:
                _PREPARED_CACHE.put(key, prepared)
        padded, info = prepared
        out_images[i] = padded
        im_info[i] = info
        boxes = rec["boxes"] * info[2]
        k = min(len(boxes), g)
        gt_boxes[i, :k, :4] = boxes[:k]
        gt_boxes[i, :k, 4] = rec["gt_classes"][:k]
        gt_valid[i, :k] = True
        if with_masks:
            rec_masks = record_gt_masks(rec, g, msize)
            gt_masks[i] = 1 if rec_masks is None else rec_masks
        if proposal_count:
            p = np.asarray(rec["proposals"], np.float32) * info[2]
            k = min(len(p), proposal_count)
            proposals[i, :k] = p[:k]
            prop_valid[i, :k] = True
    out = {
        "images": out_images,
        "im_info": im_info,
        "gt_boxes": gt_boxes,
        "gt_valid": gt_valid,
    }
    if with_masks:
        out["gt_masks"] = gt_masks
    if seeds is not None:
        # per-image sampling seeds: in-graph roi/anchor subsampling keys
        # derive from these, making draws identical across DP topologies
        out["sample_seeds"] = np.asarray(seeds, np.int32)
    if proposal_count:
        out["proposals"] = proposals
        out["prop_valid"] = prop_valid
    return out


def _orientation_bucket(rec: Dict, buckets) -> Tuple[int, int]:
    """Pick the bucket a record will land in post-resize (h<=w → wide)."""
    wide = rec["width"] >= rec["height"]
    for b in buckets:
        if (b[1] >= b[0]) == wide:
            return tuple(b)
    return tuple(buckets[0])


class PrefetchIterator:
    """Closeable host-prefetch stage: drains ``source`` through a daemon
    thread with a bounded queue so host batch assembly overlaps the
    consumer's device work.

    Worker exceptions are re-raised in the consumer — a swallowed decode
    error would silently truncate an epoch (or an eval sweep, corrupting
    mAP).  Shutdown is sentinel-based: :meth:`close` (also the context
    manager and, as a backstop, GC) signals the worker, drains queued
    batches, and joins the thread — an abandoned iterator no longer
    leaks the worker plus ``prefetch + 1`` pinned batches.  Shared by
    ``TrainLoader.__iter__`` and ``TestLoader.iter_batched``; the
    device-feed stage (``core/pipeline.py :: DeviceFeed``) stacks on top
    and closes its source through the same interface.

    ``prefetch <= 0`` degrades to a plain synchronous pass-through (no
    thread), keeping the deterministic no-thread path tests rely on.
    """

    def __init__(self, source, prefetch: int):
        self._closed = threading.Event()
        self._done = False
        if prefetch <= 0:
            self._it = iter(source)
            self._thread = None
            return
        self._it = None
        self._source = source
        self._q: "queue.Queue" = queue.Queue(maxsize=prefetch)
        self._thread = threading.Thread(
            target=self._worker, name="loader-prefetch", daemon=True
        )
        self._thread.start()

    def _put(self, msg) -> bool:
        # bounded put that gives up once the consumer is gone — a plain
        # q.put would park this thread forever when the iterator is
        # abandoned mid-iteration (exception in the consumer, partial
        # eval, GC), leaking the thread plus prefetch+1 pinned batches
        while not self._closed.is_set():
            try:
                self._q.put(msg, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    def _worker(self):
        try:
            for item in self._source:
                if not self._put(("item", item)):
                    return
            self._put(("stop", None))
        except BaseException as e:  # noqa: BLE001 — handed to the consumer
            self._put(("err", e))

    def __iter__(self):
        return self

    def __next__(self):
        if self._done or self._closed.is_set():
            raise StopIteration
        if self._thread is None:
            return next(self._it)
        while True:
            try:
                kind, payload = self._q.get(timeout=0.2)
                break
            except queue.Empty:
                if self._closed.is_set():
                    raise StopIteration from None
        if kind == "stop":
            self._done = True
            raise StopIteration
        if kind == "err":
            self._done = True
            raise payload
        return payload

    def close(self, timeout: float = 5.0) -> None:
        """Idempotent: stop the worker, drop queued batches, join."""
        self._closed.set()
        if self._thread is None:
            return
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        if self._thread.is_alive():
            self._thread.join(timeout)

    def __enter__(self) -> "PrefetchIterator":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):  # abandoned without close(): still reclaim
        try:
            self.close(timeout=0.2)
        except Exception:  # noqa: BLE001 — interpreter may be tearing down
            pass


def _prefetch_iter(source, prefetch: int):
    """Back-compat alias for :class:`PrefetchIterator`."""
    return PrefetchIterator(source, prefetch)


class _AssembledStream:
    """Closeable iterator over pool-assembled batches — the
    ``assembly_workers > 0`` twin of :class:`PrefetchIterator`, so
    consumers (DeviceFeed, ``pipelined``, early-stopping eval) tear
    down either path through the same ``close()``.

    Drops ``None`` results (whole-batch failures already accounted by
    the loader's fault counters); worker exceptions — including
    :class:`LoaderFaultBudgetExceeded` — surface at their submission
    position, exactly where the serial loop would have raised.
    ``stats()`` exposes the pool's occupancy counters.
    """

    def __init__(self, pool: AssemblyPool, results):
        self._pool = pool
        self._results = results

    def __iter__(self) -> "_AssembledStream":
        return self

    def __next__(self):
        while True:
            try:
                out = next(self._results)
            except StopIteration:
                self._pool.close()
                raise
            if out is not None:
                return out

    def stats(self) -> Dict:
        return self._pool.stats()

    def close(self, timeout: float = 5.0) -> None:
        self._results.close()
        self._pool.close()

    def __enter__(self) -> "_AssembledStream":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter may be tearing down
            pass


class TrainLoader:
    """AnchorLoader twin: shuffled, aspect-grouped, bucket-padded batches.

    Fault tolerance: a record whose image fails to load (missing file,
    corrupt decode, NFS hiccup) no longer kills the prefetch worker — the
    read is retried per ``retry`` (deterministic, jitter-free), then the
    record is dropped from the batch plan: its slot is filled by the
    batch's first good record (shapes must stay fixed for the jit cache)
    and ``substituted_records``/``record_failures`` count the damage.  A
    batch with NO loadable record is dropped whole.  More failures than
    ``failure_budget`` abort the run with
    :class:`LoaderFaultBudgetExceeded` — bounded, loud data loss.
    """

    def __init__(
        self,
        roidb: List[Dict],
        cfg: Config,
        batch_size: int,
        shuffle: bool = True,
        seed: int = 0,
        prefetch: int = 2,
        proposal_count: int = 0,
        row_slice: Optional[slice] = None,
        retry: Optional[RetryPolicy] = None,
        failure_budget: Optional[int] = None,
        assembly_workers: Optional[int] = None,
    ):
        self.roidb = roidb
        self.cfg = cfg
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.seed = seed
        self.prefetch = prefetch
        self.proposal_count = proposal_count
        # multi-host: every process computes the identical (seeded) global
        # plan, then loads only its rows of each global batch — the global
        # data order is process-count-invariant (parallel/distributed.py)
        self.row_slice = row_slice
        self.epoch = 0
        # consumed by the next __iter__: resume-from-preemption skips the
        # batches already trained this epoch (the plan is deterministic
        # per (seed, epoch), so skipping reproduces the exact stream)
        self.skip_batches = 0
        self.retry = retry or make_retry_policy("loader")
        # default budget: 1% of the roidb, floored so tiny smoke runs
        # aren't aborted by a single flaky read
        self.failure_budget = (
            failure_budget if failure_budget is not None
            else max(32, len(roidb) // 100)
        )
        self.record_failures = 0
        self.substituted_records = 0
        self.dropped_batches = 0
        # None → MX_RCNN_ASSEMBLY_WORKERS (default 0 = the serial
        # prefetch path); > 0 assembles batches in an AssemblyPool
        self.assembly_workers = assembly_workers
        # fault accounting is shared mutable state once assembly goes
        # parallel: counters and the budget check update atomically
        self._fault_lock = threading.Lock()

    def _load_guarded(self, i: int) -> Optional[np.ndarray]:
        """Load record ``i``'s image with bounded retry; None = the
        record is skipped (budget permitting)."""
        rec = self.roidb[i]

        def attempt(_k: int) -> np.ndarray:
            faults.fail_record(i)  # test injection, no-op in production
            return _load_record_image(rec)

        try:
            return self.retry.run(attempt)
        except Exception as e:  # noqa: BLE001 — any read/decode failure
            with self._fault_lock:
                self.record_failures += 1
                failures = self.record_failures
            logger.warning(
                "record %d (%s) failed after %d attempts: %r — dropped "
                "(%d/%d failure budget)",
                i, rec.get("image"), self.retry.tries, e,
                failures, self.failure_budget,
            )
            if failures > self.failure_budget:
                raise LoaderFaultBudgetExceeded(
                    f"{failures} records failed to load "
                    f"(budget {self.failure_budget}); latest: record {i} "
                    f"({rec.get('image')}): {e!r}"
                ) from e
            return None

    def __len__(self) -> int:
        return len(self.roidb) // self.batch_size

    def _epoch_plan(self, epoch: int) -> List[Tuple[Tuple[int, int], List[int]]]:
        """Group indices by orientation bucket, shuffle within groups,
        emit whole batches (dropping the ragged tail like the reference's
        ``pad`` handling drops/wraps)."""
        rng = np.random.RandomState(self.seed + epoch)
        groups: Dict[Tuple[int, int], List[int]] = {}
        for i, rec in enumerate(self.roidb):
            b = _orientation_bucket(rec, self.cfg.SHAPE_BUCKETS)
            groups.setdefault(b, []).append(i)
        plan = []
        for b, idxs in groups.items():
            idxs = np.asarray(idxs)
            if self.shuffle:
                rng.shuffle(idxs)
            for s in range(0, len(idxs) - self.batch_size + 1, self.batch_size):
                plan.append((b, idxs[s : s + self.batch_size].tolist()))
        if self.shuffle:
            order = rng.permutation(len(plan))
            plan = [plan[i] for i in order]
        return plan

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        plan = self._epoch_plan(self.epoch)
        self.epoch += 1
        first = self.skip_batches
        if first:
            plan = plan[first:]
            self.skip_batches = 0
        if self.row_slice is not None:
            plan = [(b, idxs[self.row_slice]) for b, idxs in plan]
        pc = self.proposal_count
        # the plan index rides along as the assembly span's ``batch`` id
        plan = [(first + i, b, idxs) for i, (b, idxs) in enumerate(plan)]

        def build(index, bucket, idxs):
            with tracing.span(tracing.LOADER_ASSEMBLE, batch=index):
                return assemble(bucket, idxs)

        def assemble(bucket, idxs):
            images = [self._load_guarded(i) for i in idxs]
            good = [(i, im) for i, im in zip(idxs, images) if im is not None]
            if not good:
                with self._fault_lock:
                    self.dropped_batches += 1
                logger.warning(
                    "dropping whole batch %s — no loadable record", idxs
                )
                return None
            # deterministic skip: a failed slot is filled with the batch's
            # first good record (record + pixels + seed stay consistent),
            # keeping the batch shape fixed for the jit cache
            filled, imgs = [], []
            for i, im in zip(idxs, images):
                if im is None:
                    i, im = good[0]
                    with self._fault_lock:
                        self.substituted_records += 1
                filled.append(i)
                imgs.append(im)
            return make_batch(
                [self.roidb[i] for i in filled], self.cfg, bucket,
                images=imgs, proposal_count=pc, seeds=filled,
                with_masks=self.cfg.network.USE_MASK,
            )

        workers = (
            default_assembly_workers() if self.assembly_workers is None
            else max(0, int(self.assembly_workers))
        )
        if workers > 0:
            # parallel assembly: ``build`` is pure per plan entry (its
            # only shared state — render/prepared LRUs, fault counters —
            # is locked), so the ordered pool stream is bit-identical to
            # the serial one for the same seed; the pool's run-ahead
            # window doubles as the prefetch stage
            pool = AssemblyPool(workers, name="train-assembly")
            return _AssembledStream(
                pool,
                pool.imap(
                    lambda entry: build(*entry), plan,
                    window=max(self.prefetch, workers + 2),
                ),
            )
        source = (
            batch
            for entry in plan
            if (batch := build(*entry)) is not None
        )
        # a real PrefetchIterator (not a generator) so consumers that
        # stop early — or the DeviceFeed stage stacked on top — can
        # close() it deterministically instead of waiting on GC
        return PrefetchIterator(source, self.prefetch)


class TestLoader:
    """Inference iterator (TestLoader twin); also yields the roidb record
    so eval can undo the resize scale.  ``proposal_count`` > 0 emits each
    record's dumped proposals too (Fast-RCNN test mode).

    ``batch_size`` > 1 batches same-orientation-bucket images onto the
    device in one forward — a beyond-reference upgrade (the reference
    tester is hardwired batch=1); iterate with :meth:`iter_batched`,
    which yields ``(dataset_indices, records, batch)``.  The ragged tail
    of each bucket group runs at its own (smaller) batch size, so the jit
    cache stays at ≤ 2 graphs per bucket.
    """

    def __init__(
        self,
        roidb: List[Dict],
        cfg: Config,
        proposal_count: int = 0,
        batch_size: int = 1,
    ):
        self.roidb = roidb
        self.cfg = cfg
        self.proposal_count = proposal_count
        self.batch_size = batch_size

    def __len__(self) -> int:
        return len(self.roidb)

    def __iter__(self):
        for rec in self.roidb:
            bucket = _orientation_bucket(rec, self.cfg.SHAPE_BUCKETS)
            batch = make_batch(
                [rec], self.cfg, bucket, proposal_count=self.proposal_count,
                uint8_images=self.cfg.TEST.UINT8_TRANSFER,
            )
            batch["orig_hw"] = np.asarray(
                [[rec["height"], rec["width"]]], np.float32
            )
            yield rec, batch

    def iter_batched(
        self, prefetch: int = 2, assembly_workers: Optional[int] = None
    ):
        """Yields ``(dataset_indices, records, batch)``; a background
        thread overlaps host image assembly with the consumer's device
        forward + fetch (same prefetcher discipline as TrainLoader —
        host decode/resize is the eval bottleneck, not the TPU).

        ``assembly_workers`` (None → ``MX_RCNN_ASSEMBLY_WORKERS``,
        default 0): > 0 assembles batches concurrently in an
        :class:`~mx_rcnn_tpu.data.assembler.AssemblyPool` instead of the
        single prefetch thread — same yield order and bit-identical
        batches, ``stats()`` on the returned stream reports occupancy."""
        groups: Dict[Tuple[int, int], List[int]] = {}
        for i, rec in enumerate(self.roidb):
            b = _orientation_bucket(rec, self.cfg.SHAPE_BUCKETS)
            groups.setdefault(b, []).append(i)
        plan = [
            (bucket, idxs[s : s + self.batch_size])
            for bucket, idxs in groups.items()
            for s in range(0, len(idxs), self.batch_size)
        ]

        def build(bucket, chunk):
            recs = [self.roidb[i] for i in chunk]
            batch = make_batch(
                recs, self.cfg, bucket, proposal_count=self.proposal_count,
                uint8_images=self.cfg.TEST.UINT8_TRANSFER,
            )
            batch["orig_hw"] = np.asarray(
                [[r["height"], r["width"]] for r in recs], np.float32
            )
            return chunk, recs, batch

        workers = (
            default_assembly_workers() if assembly_workers is None
            else max(0, int(assembly_workers))
        )
        if workers > 0:
            pool = AssemblyPool(workers, name="test-assembly")
            return _AssembledStream(
                pool,
                pool.imap(
                    lambda entry: build(*entry), plan,
                    window=max(prefetch, workers + 2),
                ),
            )
        source = (build(bucket, chunk) for bucket, chunk in plan)
        return PrefetchIterator(source, prefetch)
