"""Loader behaviors: resume data-order determinism, proposal batches,
bucket-overflow guard."""

import dataclasses

import numpy as np
import pytest

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.data.image import pad_to_bucket
from mx_rcnn_tpu.data.loader import TrainLoader, make_batch
from mx_rcnn_tpu.data.synthetic import SyntheticDataset


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


class TestResumeDataOrder:
    def test_epoch_sync_reproduces_fresh_run(self, roidb):
        """A loader fast-forwarded via ``loader.epoch = N`` must replay the
        exact batch sequence a fresh run reaches at epoch N (VERDICT r1
        weak #6: resumed runs used epoch-0 data order)."""
        cfg = small_cfg()
        fresh = TrainLoader(roidb, cfg, 2, shuffle=True, seed=7, prefetch=0)
        for _ in range(3):  # epochs 0..2 consumed
            list(fresh)
        resumed = TrainLoader(roidb, cfg, 2, shuffle=True, seed=7, prefetch=0)
        resumed.epoch = 3
        a = [b["gt_boxes"] for b in fresh]      # epoch 3 of the fresh run
        b = [b["gt_boxes"] for b in resumed]    # epoch 3 after sync
        assert len(a) == len(b) > 0
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)

    def test_epochs_differ(self, roidb):
        cfg = small_cfg()
        loader = TrainLoader(roidb, cfg, 2, shuffle=True, seed=7, prefetch=0)
        e0 = [b["gt_boxes"] for b in loader]
        e1 = [b["gt_boxes"] for b in loader]
        assert any(
            not np.array_equal(x, y) for x, y in zip(e0, e1)
        ), "shuffle should vary across epochs"


class TestProposalBatches:
    def test_make_batch_emits_padded_proposals(self, roidb):
        cfg = small_cfg()
        recs = [
            dict(r, proposals=r["boxes"].astype(np.float32)) for r in roidb[:2]
        ]
        batch = make_batch(recs, cfg, (128, 128), proposal_count=16)
        assert batch["proposals"].shape == (2, 16, 4)
        assert batch["prop_valid"].shape == (2, 16)
        n0 = len(recs[0]["proposals"])
        assert batch["prop_valid"][0].sum() == n0
        # proposals are scaled like gt boxes
        scale = batch["im_info"][0][2]
        np.testing.assert_allclose(
            batch["proposals"][0][:n0], recs[0]["proposals"] * scale, rtol=1e-5
        )

    def test_train_loader_passes_proposal_count(self, roidb):
        cfg = small_cfg()
        recs = [dict(r, proposals=r["boxes"].astype(np.float32)) for r in roidb]
        loader = TrainLoader(
            recs, cfg, 2, shuffle=False, prefetch=0, proposal_count=8
        )
        batch = next(iter(loader))
        assert batch["proposals"].shape == (2, 8, 4)


class TestBucketGuard:
    def test_oversize_image_raises(self):
        with pytest.raises(ValueError):
            pad_to_bucket(np.zeros((200, 100, 3), np.float32), (128, 128))


class TestDsUtils:
    def test_unique_boxes(self):
        from mx_rcnn_tpu.data.ds_utils import unique_boxes

        boxes = np.array(
            [[1, 2, 3, 4], [1, 2, 3, 4], [5, 6, 7, 8], [1, 2, 3, 4.2]],
            np.float32,
        )
        keep = unique_boxes(boxes)
        # 4.2 rounds to 4 → duplicate of row 0 at scale 1
        np.testing.assert_array_equal(keep, [0, 2])
        keep16 = unique_boxes(boxes, scale=16.0)
        np.testing.assert_array_equal(keep16, [0, 2, 3])

    def test_filter_small_boxes(self):
        from mx_rcnn_tpu.data.ds_utils import filter_small_boxes

        boxes = np.array(
            [[0, 0, 9, 9], [0, 0, 3, 9], [0, 0, 9, 3]], np.float32
        )
        np.testing.assert_array_equal(filter_small_boxes(boxes, 5), [0])
        np.testing.assert_array_equal(
            filter_small_boxes(boxes, 4), [0, 1, 2]
        )


def test_prefetch_iter_propagates_worker_exception():
    """A decode error inside the prefetch thread must reach the consumer
    — swallowing it would silently truncate an epoch or an eval sweep."""
    import pytest

    from mx_rcnn_tpu.data.loader import _prefetch_iter

    def source():
        yield 1
        yield 2
        raise RuntimeError("decode failed")

    got = []
    with pytest.raises(RuntimeError, match="decode failed"):
        for x in _prefetch_iter(source(), prefetch=2):
            got.append(x)
    assert got == [1, 2]
    # prefetch=0 path propagates too
    with pytest.raises(RuntimeError, match="decode failed"):
        list(_prefetch_iter(source(), prefetch=0))


def test_synthetic_render_cache_tells_two_datasets_apart():
    """Every synthetic dataset names its records ``synthetic://0`` .. with
    the same seeds, and the render LRU is the process's: a second dataset
    of another extent (or other boxes) must not be served the first's
    pixels (what an earlier test in the same worker left in the cache made
    ``test_synthetic_render_cache_is_flip_safe`` fail in the whole run)."""
    from mx_rcnn_tpu.data.loader import _load_record_image
    from mx_rcnn_tpu.data.synthetic import synthetic_image

    first = SyntheticDataset(num_images=2, num_classes=4,
                             image_size=(128, 176), max_boxes=2).gt_roidb()
    for rec in first:
        _load_record_image(rec)                                   # caches
    for size, classes in (((160, 128), 4), ((128, 176), 7)):
        other = SyntheticDataset(num_images=2, num_classes=classes,
                                 image_size=size, max_boxes=3).gt_roidb()
        assert [r["image"] for r in other] == [r["image"] for r in first]
        for rec in other:
            np.testing.assert_array_equal(
                _load_record_image(rec),
                synthetic_image(rec, rec["synthetic_seed"]))


def test_synthetic_render_cache_is_flip_safe():
    """A flipped twin shallow-copies its source record; the render LRU
    keys on (uri, flipped, seed), so the twin must MISS the unflipped
    entry and render from the flipped geometry (pixels match flipped
    gt)."""
    from mx_rcnn_tpu.data.imdb import IMDB
    from mx_rcnn_tpu.data.loader import _load_record_image

    imdb = SyntheticDataset(num_images=2, num_classes=4,
                            image_size=(128, 128), max_boxes=2)
    roidb = imdb.gt_roidb()
    plain = [_load_record_image(rec).copy() for rec in roidb]  # caches
    both = IMDB.append_flipped_images(roidb)
    for rec, im_plain in zip(both[len(roidb):], plain):
        assert rec.get("flipped")
        im_flip = _load_record_image(rec)
        # must equal a FRESH render from the flipped geometry (the
        # noise background is seed-anchored, not mirrored, so this is
        # not simply im_plain[:, ::-1]) — and not the stale cache
        from mx_rcnn_tpu.data.synthetic import synthetic_image

        assert (im_flip != im_plain).any(), "stale unflipped cache served"
        np.testing.assert_array_equal(
            im_flip, synthetic_image(rec, rec["synthetic_seed"])
        )
