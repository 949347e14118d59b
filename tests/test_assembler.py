"""Host data plane (ISSUE 5): assembly/completion pools, parallel ==
serial bit-identical streams, fault-budget propagation from workers,
and overlapped pred_eval equivalence.

Everything here is numpy-only — no model build, no jit compile — so the
whole file runs in a few seconds.
"""

import dataclasses
import threading
import time
import zlib

import numpy as np
import pytest

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.data.assembler import (
    AssemblyPool,
    CompletionPool,
    default_assembly_workers,
)
from mx_rcnn_tpu.data.loader import (
    LoaderFaultBudgetExceeded,
    TestLoader,
    TrainLoader,
    set_prepared_cache,
)
from mx_rcnn_tpu.data.synthetic import SyntheticDataset
from mx_rcnn_tpu.utils import faults


def small_cfg():
    cfg = generate_config("resnet50", "PascalVOC")
    return cfg.replace(
        SHAPE_BUCKETS=((128, 128),),
        dataset=dataclasses.replace(
            cfg.dataset, NUM_CLASSES=4, SCALES=((128, 128),), MAX_GT_BOXES=8
        ),
    )


@pytest.fixture(scope="module")
def roidb():
    return SyntheticDataset(
        num_images=8, num_classes=4, image_size=(128, 128), max_boxes=2
    ).gt_roidb()


def _assert_batches_equal(got, want):
    assert len(got) == len(want) > 0
    for a, b in zip(got, want):
        assert set(a) == set(b)
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])


# ------------------------------------------------------------ AssemblyPool
class TestAssemblyPool:
    def test_imap_yields_in_submission_order(self):
        """Later items finishing FIRST (inverted sleeps) must not reorder
        the stream — imap is ordered by submission, like the serial map."""
        items = list(range(12))

        def work(i):
            time.sleep((12 - i) * 0.002)  # item 11 completes way early
            return i * i

        pool = AssemblyPool(4, name="t")
        got = list(pool.imap(work, items))
        assert got == [i * i for i in items]
        s = pool.stats()
        assert s["submitted"] == s["completed"] == s["yielded"] == 12
        assert 0.0 <= s["occupancy"] <= 1.0
        assert s["queue_depth_max"] >= 1
        pool.close()

    def test_exception_surfaces_at_its_position(self):
        """A worker exception re-raises when ITS item is consumed — the
        items before it are still delivered."""

        def work(i):
            if i == 3:
                raise ValueError("boom at 3")
            return i

        pool = AssemblyPool(2, name="t")
        it = pool.imap(work, range(6))
        assert [next(it) for _ in range(3)] == [0, 1, 2]
        with pytest.raises(ValueError, match="boom at 3"):
            next(it)
        pool.close()
        pool.close()  # idempotent

    def test_workers_zero_is_serial_inline(self):
        pool = AssemblyPool(0, name="t")
        it = pool.imap(lambda i: i + 1, range(5))
        assert list(it) == [1, 2, 3, 4, 5]
        assert pool.stats()["workers"] == 0
        pool.close()

    def test_close_abandons_unconsumed_work(self):
        """Closing with items still queued neither deadlocks nor leaks —
        the partially consumed stream just stops."""
        started = []

        def work(i):
            started.append(i)
            time.sleep(0.002)
            return i

        pool = AssemblyPool(2, name="t")
        it = pool.imap(work, range(50), window=4)
        assert next(it) == 0
        pool.close()
        # in-flight work drained, queued-but-unstarted work cancelled
        assert len(started) < 50

    def test_default_workers_env(self, monkeypatch):
        monkeypatch.delenv("MX_RCNN_ASSEMBLY_WORKERS", raising=False)
        assert default_assembly_workers() == 0  # serial unless opted in
        monkeypatch.setenv("MX_RCNN_ASSEMBLY_WORKERS", "3")
        assert default_assembly_workers() == 3


# ---------------------------------------------------------- CompletionPool
class TestCompletionPool:
    def test_index_addressed_accumulation_is_deterministic(self):
        """Scrambled completion order + disjoint slot writes == serial
        result (the pred_eval accumulation contract)."""
        n = 24
        want = [i * 3 for i in range(n)]

        def run(workers):
            slots = [None] * n
            pool = CompletionPool(workers, name="t")

            def work(i):
                time.sleep(((i * 7) % 5) * 0.001)
                slots[i] = i * 3

            for i in range(n):
                pool.submit(work, i)
            pool.drain()
            pool.close()
            return slots

        assert run(0) == want
        assert run(4) == want

    def test_drain_reraises_first_worker_error(self):
        pool = CompletionPool(2, name="t")

        def work(i):
            if i == 5:
                raise RuntimeError("postprocess died")

        for i in range(10):
            pool.submit(work, i)
        with pytest.raises(RuntimeError, match="postprocess died"):
            pool.drain()
        assert pool.stats()["errors"] == 1
        pool.close()

    def test_inline_error_raises_at_submit(self):
        pool = CompletionPool(0, name="t")
        with pytest.raises(RuntimeError, match="inline"):
            pool.submit(lambda: (_ for _ in ()).throw(RuntimeError("inline")))
        pool.close()

    def test_inflight_bounded_by_depth(self):
        """Blocking submit: at most ``depth`` tasks in flight, ever —
        the engine's device-queue bound."""
        depth = 2
        pool = CompletionPool(2, depth=depth, name="t")
        live = [0]
        peak = [0]
        lock = threading.Lock()

        def work():
            with lock:
                live[0] += 1
                peak[0] = max(peak[0], live[0])
            time.sleep(0.003)
            with lock:
                live[0] -= 1

        for _ in range(12):
            pool.submit(work)
        pool.drain()
        s = pool.stats()
        pool.close()
        assert peak[0] <= depth
        assert s["inflight_max"] <= depth
        assert s["submitted"] == s["completed"] == 12


# ------------------------------------------------- parallel == serial
class TestParallelAssemblyEquivalence:
    def test_train_loader_parallel_matches_serial(self, roidb):
        cfg = small_cfg()
        serial = list(
            TrainLoader(roidb, cfg, 2, shuffle=True, seed=11, prefetch=0,
                        assembly_workers=0)
        )
        parallel = list(
            TrainLoader(roidb, cfg, 2, shuffle=True, seed=11, prefetch=2,
                        assembly_workers=3)
        )
        _assert_batches_equal(parallel, serial)

    def test_test_loader_parallel_matches_serial(self, roidb):
        cfg = small_cfg()
        loader = TestLoader(roidb, cfg, batch_size=2)
        serial = [
            (idxs, b) for idxs, _, b in loader.iter_batched(assembly_workers=0)
        ]
        stream = loader.iter_batched(assembly_workers=3)
        parallel = [(idxs, b) for idxs, _, b in stream]
        assert [i for i, _ in parallel] == [i for i, _ in serial]
        _assert_batches_equal(
            [b for _, b in parallel], [b for _, b in serial]
        )
        s = stream.stats()
        assert s["workers"] == 3
        assert s["yielded"] == len(serial)
        assert 0.0 <= s["occupancy"] <= 1.0

    def test_prepared_cache_hits_are_byte_identical(self, roidb):
        cfg = small_cfg()
        loader = TestLoader(roidb, cfg, batch_size=2)
        set_prepared_cache(0)
        try:
            cold = [b for _, _, b in loader.iter_batched(assembly_workers=0)]
            set_prepared_cache(32)
            fill = [b for _, _, b in loader.iter_batched(assembly_workers=0)]
            from mx_rcnn_tpu.data.loader import _PREPARED_CACHE

            assert _PREPARED_CACHE.misses > 0
            warm = [b for _, _, b in loader.iter_batched(assembly_workers=2)]
            assert _PREPARED_CACHE.hits > 0
            _assert_batches_equal(fill, cold)
            _assert_batches_equal(warm, cold)
        finally:
            set_prepared_cache(0)


# ------------------------------------------------------ fault propagation
class TestFaultPropagation:
    def test_budget_abort_propagates_from_assembly_workers(self, monkeypatch):
        """LoaderFaultBudgetExceeded raised inside a pool worker surfaces
        to the consuming thread (not swallowed in the pool)."""
        monkeypatch.setenv(faults.ENV_VAR, "record_fail@0,record_fail@4")
        faults.reset()
        loader = TrainLoader(
            SyntheticDataset(num_images=8, num_classes=4,
                             image_size=(128, 128), max_boxes=2).gt_roidb(),
            small_cfg(), 2, shuffle=False, prefetch=2, failure_budget=1,
            assembly_workers=2,
        )
        with pytest.raises(LoaderFaultBudgetExceeded):
            list(loader)
        faults.reset()

    def test_substitution_parity_under_parallel_assembly(self, monkeypatch):
        """A substituted fault slot produces the identical stream whether
        assembly ran serial or in the pool, and the shared counters see
        exactly the injected failure count."""
        imdb = SyntheticDataset(num_images=8, num_classes=4,
                                image_size=(128, 128), max_boxes=2)
        monkeypatch.setenv(faults.ENV_VAR, "record_fail@2")
        faults.reset()
        serial_loader = TrainLoader(
            imdb.gt_roidb(), small_cfg(), 2, shuffle=False, prefetch=0,
            failure_budget=4, assembly_workers=0,
        )
        serial = list(serial_loader)

        faults.reset()
        parallel_loader = TrainLoader(
            imdb.gt_roidb(), small_cfg(), 2, shuffle=False, prefetch=2,
            failure_budget=4, assembly_workers=3,
        )
        parallel = list(parallel_loader)
        _assert_batches_equal(parallel, serial)
        assert parallel_loader.record_failures == 1
        assert parallel_loader.substituted_records == 1
        faults.reset()


# --------------------------------------------------- overlapped pred_eval
class _FakeMaskPredictor:
    """Deterministic numpy predictor: raw head outputs + mask logits
    seeded per batch from the pixel content, so serial and overlapped
    pred_eval see identical device results."""

    def __init__(self, num_classes: int, rois: int = 16, mask_size: int = 7):
        self.num_classes = num_classes
        self.rois = rois
        self.mask_size = mask_size

    def predict(self, batch):
        n = np.asarray(batch["im_info"]).shape[0]
        sample = np.ascontiguousarray(np.asarray(batch["images"])[:, ::16, ::16])
        rng = np.random.RandomState(zlib.crc32(sample.tobytes()) & 0x7FFFFFFF)
        r, k, s = self.rois, self.num_classes, self.mask_size
        im_info = np.asarray(batch["im_info"], np.float32)
        h = im_info[:, 0][:, None, None]
        w = im_info[:, 1][:, None, None]
        xy = rng.uniform(0.0, 0.6, (n, r, 2))
        wh = rng.uniform(0.1, 0.35, (n, r, 2))
        rois = np.concatenate(
            [xy[..., :1] * w, xy[..., 1:] * h,
             (xy[..., :1] + wh[..., :1]) * w,
             (xy[..., 1:] + wh[..., 1:]) * h],
            axis=-1,
        ).astype(np.float32)
        return {
            "rois": rois,
            "roi_valid": np.ones((n, r), np.float32),
            "cls_prob": rng.dirichlet(np.ones(k), (n, r)).astype(np.float32),
            "bbox_deltas": (rng.standard_normal((n, r, 4 * k)) * 0.05
                            ).astype(np.float32),
            "mask_logits": (rng.standard_normal((n, r, s, s, k)) * 2.0
                            ).astype(np.float32),
        }

    def predict_async(self, batch):
        return self.predict(batch)


class _NoEval:
    def __init__(self, num_classes):
        self.num_classes = num_classes
        self.classes = ["__background__"] + [
            f"class{i}" for i in range(1, num_classes)
        ]

    def evaluate_detections(self, all_boxes, all_masks=None):
        return {}


class TestOverlappedPredEval:
    def test_overlapped_equals_serial_including_masks(self, roidb):
        """pred_eval with a completion pool + parallel assembly must be
        BYTE-identical to the inline serial loop — boxes and RLE masks —
        regardless of worker completion order."""
        from mx_rcnn_tpu.core.tester import pred_eval

        cfg = small_cfg()
        cfg = cfg.replace(
            TEST=dataclasses.replace(cfg.TEST, DEVICE_POSTPROCESS=False)
        )
        imdb = _NoEval(cfg.dataset.NUM_CLASSES)
        predictor = _FakeMaskPredictor(imdb.num_classes)

        def run(pw, aw):
            stats = {}
            boxes, _ = pred_eval(
                predictor, TestLoader(roidb, cfg, batch_size=2), imdb, cfg,
                postprocess_workers=pw, assembly_workers=aw,
                stats_out=stats,
            )
            return boxes, stats

        serial_boxes, serial_stats = run(0, 0)
        over_boxes, over_stats = run(3, 2)
        assert serial_stats["completion"]["workers"] == 0
        assert over_stats["completion"]["workers"] == 3
        assert over_stats["completion"]["errors"] == 0
        assert over_stats["completion"]["completed"] == len(roidb)
        n_dets = 0
        for j in range(1, imdb.num_classes):
            for i in range(len(roidb)):
                np.testing.assert_array_equal(
                    over_boxes[j][i], serial_boxes[j][i]
                )
                n_dets += len(serial_boxes[j][i])
        assert n_dets > 0, "degenerate run: no detections compared"

    def test_overlapped_mask_rles_equal_serial(self, roidb):
        """The segm path: RLE dicts accumulated via the completion pool
        match the serial ones exactly (dump via evaluate_detections)."""
        from mx_rcnn_tpu.core.tester import pred_eval

        cfg = small_cfg()
        cfg = cfg.replace(
            TEST=dataclasses.replace(cfg.TEST, DEVICE_POSTPROCESS=False)
        )

        captured = {}

        class Capture(_NoEval):
            def __init__(self, num_classes, tag):
                super().__init__(num_classes)
                self.tag = tag

            def evaluate_detections(self, all_boxes, all_masks=None):
                captured[self.tag] = all_masks
                return {}

        predictor = _FakeMaskPredictor(4)
        for tag, pw, aw in (("serial", 0, 0), ("overlapped", 3, 2)):
            pred_eval(
                predictor, TestLoader(roidb, cfg, batch_size=2),
                Capture(4, tag), cfg,
                postprocess_workers=pw, assembly_workers=aw,
            )
        serial, overlapped = captured["serial"], captured["overlapped"]
        assert serial is not None and overlapped is not None
        assert len(serial) == len(overlapped)
        n_rles = 0
        for j in range(1, 4):
            for i in range(len(roidb)):
                assert overlapped[j][i] == serial[j][i]
                n_rles += len(serial[j][i])
        assert n_rles > 0, "degenerate run: no masks compared"
