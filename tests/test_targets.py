"""Tests for in-jit anchor targets and roi sampling (fixed RNG goldens —
SURVEY §5.1's 'golden-batch tests for assign_anchor/sample_rois')."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.models.layers import per_image
from mx_rcnn_tpu.ops.anchors import shifted_anchors
from mx_rcnn_tpu.ops.boxes import bbox_transform, bbox_transform_planes
from mx_rcnn_tpu.ops.targets import _random_keep_k, assign_anchor, sample_rois

CFG = generate_config("resnet", "PascalVOC")


def pad_gt(boxes, g=8):
    boxes = np.asarray(boxes, np.float32).reshape(-1, 5)
    out = np.zeros((g, 5), np.float32)
    out[: len(boxes)] = boxes
    valid = np.zeros((g,), bool)
    valid[: len(boxes)] = True
    return jnp.array(out), jnp.array(valid)


def _keep_k_by_sort(key, candidate_mask, k):
    """The oracle: keep-k as it was before ``top_k`` (PR 31) - rank every
    element by a full stable sort of the negated priorities and invert
    the order by a full scatter."""
    n = candidate_mask.shape[0]
    priority = jax.random.uniform(key, (n,)) - (~candidate_mask) * 2.0
    order = jnp.argsort(-priority)
    rank = jnp.zeros((n,), jnp.int32).at[order].set(jnp.arange(n, dtype=jnp.int32))
    return candidate_mask & (rank < k)


class TestRandomKeepK:
    def test_exact_count(self):
        mask = jnp.array([True] * 50 + [False] * 14)
        out = _random_keep_k(jax.random.key(0), mask, 20, 20)
        assert int(out.sum()) == 20
        assert bool((out <= mask).all())

    def test_fewer_candidates_than_k(self):
        mask = jnp.array([True] * 5 + [False] * 59)
        out = _random_keep_k(jax.random.key(0), mask, 20, 20)
        assert int(out.sum()) == 5

    def test_uniformity(self):
        # every candidate should be picked roughly equally often
        mask = jnp.ones((10,), bool)
        counts = np.zeros(10)
        for i in range(200):
            counts += np.asarray(_random_keep_k(jax.random.key(i), mask, 5, 5))
        assert counts.min() > 60 and counts.max() < 140  # E=100

    N, K_MAX = 6000, 128

    @staticmethod
    def _mask(share, n):
        return jax.random.uniform(jax.random.key(int(share * 1000)), (n,)) < share

    # candidate shares of the RPN's two draws (fg: a fraction of a
    # percent of the anchors; bg: most of them); k under and over the
    # candidates' count, 0, and the static bound itself
    @pytest.mark.parametrize("share", [0.004, 0.9], ids=["sparse", "dense"])
    @pytest.mark.parametrize("k", [0, 7, 100, 128], ids=lambda k: f"k{k}")
    def test_keeps_the_set_a_full_sort_keeps(self, share, k):
        """16 keys a case, ``k`` traced under ``jit`` as the bg draw's is."""
        mask = self._mask(share, self.N)
        n_cand = int(mask.sum())
        assert (n_cand < 100) == (share < 0.1)  # 7 < sparse's count < 100
        new = jax.jit(lambda key, k: _random_keep_k(key, mask, k, self.K_MAX))
        old = jax.jit(lambda key, k: _keep_k_by_sort(key, mask, k))
        for i in range(16):
            key = jax.random.key(1000 + i)
            got, want = np.asarray(new(key, k)), np.asarray(old(key, k))
            assert (got == want).all()
            assert got.sum() == min(k, n_cand)

    @pytest.mark.parametrize("share", [0.004, 0.9], ids=["sparse", "dense"])
    @pytest.mark.parametrize("levels", [4, 64], ids=lambda n: f"{n}-levels")
    def test_ties_at_the_cut_fall_as_the_stable_sort_puts_them(
        self, share, levels, monkeypatch
    ):
        """Priorities quantised to a few levels, so that the cut at ``k``
        falls inside a run of equal priorities for every key: ``top_k``
        and the stable ``argsort(-priority)`` both put the lower index
        first."""
        uniform = jax.random.uniform
        monkeypatch.setattr(
            jax.random, "uniform",
            lambda *a, **kw: jnp.floor(uniform(*a, **kw) * levels) / levels,
        )
        mask = self._mask(share, self.N)
        for i in range(16):
            key = jax.random.key(2000 + i)
            for k in (5, 77):
                got = np.asarray(_random_keep_k(key, mask, k, self.K_MAX))
                want = np.asarray(_keep_k_by_sort(key, mask, k))
                assert (got == want).all()
                assert got.sum() == min(k, int(mask.sum()))

    def test_static_bound_over_the_length(self):
        """Tiny inputs: ``k_max`` over n is cut to n, and a ``k`` over
        the candidates keeps them all."""
        mask = jnp.array([True, False, True, True, False])
        for k in (0, 2, 3, 9):
            got = np.asarray(_random_keep_k(jax.random.key(3), mask, k, 256))
            want = np.asarray(_keep_k_by_sort(jax.random.key(3), mask, k))
            assert (got == want).all()


class TestAssignAnchor:
    def setup_method(self):
        self.anchors = jnp.array(shifted_anchors(25, 25, 16))  # 400x400 img
        self.im_info = jnp.array([400.0, 400.0, 1.0])

    def test_obvious_positive(self):
        # one gt exactly matching an anchor -> that anchor labelled fg
        gt, gv = pad_gt([[100, 100, 227, 227, 1]])  # 128x128 box
        tg = assign_anchor(
            self.anchors, gt[:, :4], gv, self.im_info, jax.random.key(0), CFG
        )
        labels = np.asarray(tg.labels)
        assert (labels == 1).sum() >= 1
        # fg anchors all have decent IoU with the gt
        from mx_rcnn_tpu.ops.boxes import bbox_overlaps

        ov = np.asarray(bbox_overlaps(self.anchors, gt[:1, :4]))[:, 0]
        assert ov[labels == 1].min() > 0.3

    def test_batch_size_budget(self):
        gt, gv = pad_gt([[50, 50, 180, 180, 1], [200, 200, 350, 320, 2]])
        tg = assign_anchor(
            self.anchors, gt[:, :4], gv, self.im_info, jax.random.key(1), CFG
        )
        labels = np.asarray(tg.labels)
        n_fg = (labels == 1).sum()
        n_bg = (labels == 0).sum()
        assert n_fg <= CFG.TRAIN.RPN_BATCH_SIZE * CFG.TRAIN.RPN_FG_FRACTION
        assert n_fg + n_bg == CFG.TRAIN.RPN_BATCH_SIZE

    def test_outside_anchors_ignored(self):
        gt, gv = pad_gt([[10, 10, 390, 390, 1]])
        small_info = jnp.array([100.0, 100.0, 1.0])  # image is only 100x100
        tg = assign_anchor(
            self.anchors, gt[:, :4], gv, small_info, jax.random.key(0), CFG
        )
        outside = ~(
            (np.asarray(self.anchors)[:, 2] < 100)
            & (np.asarray(self.anchors)[:, 3] < 100)
            & (np.asarray(self.anchors)[:, 0] >= 0)
            & (np.asarray(self.anchors)[:, 1] >= 0)
        )
        assert (np.asarray(tg.labels)[outside] == -1).all()

    def test_weights_only_on_fg(self):
        gt, gv = pad_gt([[100, 100, 227, 227, 1]])
        tg = assign_anchor(
            self.anchors, gt[:, :4], gv, self.im_info, jax.random.key(0), CFG
        )
        labels = np.asarray(tg.labels)
        w = np.asarray(tg.bbox_weights)
        assert (w[labels == 1] == 1.0).all()
        assert (w[labels != 1] == 0.0).all()

    def test_jit_and_determinism(self):
        gt, gv = pad_gt([[100, 100, 227, 227, 1]])
        f = jax.jit(
            lambda k: assign_anchor(self.anchors, gt[:, :4], gv, self.im_info, k, CFG)
        )
        a = f(jax.random.key(7))
        b = f(jax.random.key(7))
        assert (np.asarray(a.labels) == np.asarray(b.labels)).all()

    @pytest.mark.parametrize("key_impl", ["threefry2x32", "rbg"])
    def test_batch_one_gives_the_values_of_batch_twos_first_image(self, key_impl):
        """The models' call path (``per_image``): at batch 1 the image
        goes through without the batch axis (a rank-1 ``top_k``: ROADMAP
        R1), and reads what ``vmap`` reads for it."""
        anchors = self.anchors
        gts = [pad_gt([[100, 100, 227, 227, 1], [30, 40, 200, 140, 2]]),
               pad_gt([[10, 200, 150, 380, 1]])]
        gt = jnp.stack([g for g, _ in gts])
        gv = jnp.stack([v for _, v in gts])
        info = jnp.array([[400.0, 400.0, 1.0], [390.0, 400.0, 1.0]])
        keys = jax.random.split(jax.random.key(5, impl=key_impl), 2)

        def call(gt, gv, info, keys):
            return per_image(
                lambda g, v, i, k: assign_anchor(anchors, g[:, :4], v, i, k, CFG),
                gt, gv, info, keys)

        two = jax.jit(call)(gt, gv, info, keys)
        one = jax.jit(call)(gt[:1], gv[:1], info[:1], keys[:1])
        for a, b in zip(one, two):
            assert a.shape == (1,) + b.shape[1:]
            assert (np.asarray(a[0]) == np.asarray(b[0])).all()
        assert (np.asarray(one.labels) == 1).sum() > 0


def _np_overlaps(boxes, query):
    """float64 IoU with the +1 convention (``rcnn/cython/bbox.pyx``)."""
    b, q = boxes[:, None, :].astype(np.float64), query[None, :, :].astype(np.float64)
    iw = np.minimum(b[..., 2], q[..., 2]) - np.maximum(b[..., 0], q[..., 0]) + 1
    ih = np.minimum(b[..., 3], q[..., 3]) - np.maximum(b[..., 1], q[..., 1]) + 1
    inter = np.maximum(iw, 0) * np.maximum(ih, 0)
    area = lambda x: (x[..., 2] - x[..., 0] + 1) * (x[..., 3] - x[..., 1] + 1)  # noqa: E731
    return inter / (area(b) + area(q) - inter)


def _np_assign_anchor(anchors, gt, im_info, t, margin=1e-4):
    """The rules of ``rcnn/io/rpn.py :: assign_anchor`` before sampling,
    in float64 -> (fg candidates, bg candidates, matched gt index), or
    None where an IoU lies within ``margin`` of a threshold or of a gt's
    best without being it: float32 may then decide otherwise."""
    h, w = im_info[0], im_info[1]
    inside = ((anchors[:, 0] >= 0) & (anchors[:, 1] >= 0)
              & (anchors[:, 2] < w) & (anchors[:, 3] < h))
    ov = _np_overlaps(anchors, gt)
    ov[~inside] = -1.0
    max_ov, argmax = ov.max(axis=1), ov.argmax(axis=1)
    gt_max = ov.max(axis=0)
    near_best = np.abs(ov - gt_max[None, :]) < margin
    is_best = (np.abs(ov - gt_max[None, :]) < 1e-12) & (gt_max[None, :] > 0)
    for thresh in (t.RPN_POSITIVE_OVERLAP, t.RPN_NEGATIVE_OVERLAP):
        if (np.abs(ov[inside] - thresh) < margin).any():
            return None
    if (near_best & ~is_best & (gt_max[None, :] > 0)).any():
        return None
    # a second gt as close as ``margin`` to an anchor's best: argmax unsure
    second = np.sort(ov, axis=1)[:, -2] if ov.shape[1] > 1 else np.full(len(ov), -1.0)
    fg = inside & (is_best.any(axis=1) | (max_ov >= t.RPN_POSITIVE_OVERLAP))
    if (fg & (max_ov - second < margin)).any():
        return None
    bg = inside & (max_ov < t.RPN_NEGATIVE_OVERLAP) & ~fg
    return fg, bg, argmax


def _np_transform(ex, gt):
    ex, gt = ex.astype(np.float64), gt.astype(np.float64)
    ew, eh = ex[:, 2] - ex[:, 0] + 1, ex[:, 3] - ex[:, 1] + 1
    gw, gh = gt[:, 2] - gt[:, 0] + 1, gt[:, 3] - gt[:, 1] + 1
    ecx, ecy = ex[:, 0] + 0.5 * (ew - 1), ex[:, 1] + 0.5 * (eh - 1)
    gcx, gcy = gt[:, 0] + 0.5 * (gw - 1), gt[:, 1] + 0.5 * (gh - 1)
    return np.stack([(gcx - ecx) / ew, (gcy - ecy) / eh,
                     np.log(gw / ew), np.log(gh / eh)], axis=1)


def _scenes(count, rng):
    """Images of 1-6 integer gt boxes inside 400x400 whose IoUs with the
    25x25x9 anchors keep clear of every decision of the oracle."""
    anchors = shifted_anchors(25, 25, 16)
    im_info = np.array([400.0, 400.0, 1.0], np.float32)
    out = []
    while len(out) < count:
        g = rng.randint(1, 7)
        x1y1 = rng.randint(0, 300, size=(g, 2))
        wh = rng.randint(20, 260, size=(g, 2))
        boxes = np.concatenate(
            [x1y1, np.minimum(x1y1 + wh, 399)], axis=1).astype(np.float32)
        want = _np_assign_anchor(anchors, boxes, im_info, CFG.TRAIN)
        if want is not None:
            out.append((boxes, want))
    return anchors, im_info, out


class TestAssignAnchorAgainstTheReferencesRules:
    """``assign_anchor`` against a float64 numpy oracle of ``rcnn/io/
    rpn.py``'s rules, on boxes whose IoUs keep 1e-4 away from both
    thresholds and from each gt's best."""

    SCENES = 6

    @pytest.fixture(scope="class")
    def scenes(self):
        return _scenes(self.SCENES, np.random.RandomState(31))

    @pytest.mark.parametrize("i", range(SCENES))
    def test_candidate_sets_before_sampling(self, scenes, i):
        """A budget over 2N keeps every candidate, so the labels ARE the
        candidate sets."""
        anchors, im_info, cases = scenes
        boxes, (fg, bg, _) = cases[i]
        n = len(anchors)
        cfg = dataclasses.replace(CFG, TRAIN=dataclasses.replace(
            CFG.TRAIN, RPN_BATCH_SIZE=4 * n, RPN_FG_FRACTION=0.5))
        gt, gv = pad_gt(np.concatenate([boxes, np.ones((len(boxes), 1))], 1))
        tg = assign_anchor(jnp.array(anchors), gt[:, :4], gv,
                           jnp.array(im_info), jax.random.key(i), cfg)
        labels = np.asarray(tg.labels)
        assert ((labels == 1) == fg).all()
        assert ((labels == 0) == bg).all()
        assert fg.sum() >= len(boxes)  # every gt here touches an anchor

    @pytest.mark.parametrize("i", range(SCENES))
    def test_counts_and_fg_targets(self, scenes, i):
        anchors, im_info, cases = scenes
        boxes, (fg, bg, argmax) = cases[i]
        t = CFG.TRAIN
        gt, gv = pad_gt(np.concatenate([boxes, np.ones((len(boxes), 1))], 1))
        tg = jax.jit(lambda k: assign_anchor(
            jnp.array(anchors), gt[:, :4], gv, jnp.array(im_info), k, CFG
        ))(jax.random.key(100 + i))
        labels = np.asarray(tg.labels)
        n_fg = min(int(t.RPN_FG_FRACTION * t.RPN_BATCH_SIZE), int(fg.sum()))
        assert (labels == 1).sum() == n_fg
        assert (labels == 0).sum() == min(t.RPN_BATCH_SIZE - n_fg, int(bg.sum()))
        assert (fg[labels == 1]).all() and (bg[labels == 0]).all()
        sel = labels == 1
        want = _np_transform(anchors[sel], boxes[argmax[sel]])
        np.testing.assert_allclose(
            np.asarray(tg.bbox_targets)[sel], want, atol=1e-5, rtol=0)
        assert (np.asarray(tg.bbox_targets)[~sel] == 0).all()
        w = np.asarray(tg.bbox_weights)
        assert (w[sel] == 1).all() and (w[~sel] == 0).all()


class TestBboxTransformPlanes:
    def test_plane_transform_is_bbox_transform_bitwise(self, rng):
        """``bbox_transform_planes`` on eight (N,) planes against
        ``bbox_transform`` on two (N, 4) arrays: the same float32 terms,
        operation by operation and under ``jit``."""
        ex = rng.rand(4096, 4).astype(np.float32) * 600 - 100
        ex[:, 2:] = ex[:, :2] + rng.rand(4096, 2).astype(np.float32) * 500
        gt = rng.rand(4096, 4).astype(np.float32) * 600
        gt[:, 2:] = gt[:, :2] + rng.rand(4096, 2).astype(np.float32) * 400
        gt[:7] = 0.0  # padded gt slots
        ex, gt = jnp.array(ex), jnp.array(gt)

        def planes(e, g):
            return jnp.stack(bbox_transform_planes(
                [e[:, i] for i in range(4)], [g[:, i] for i in range(4)]), axis=1)

        # like with like: a fused program may contract a multiply-add
        # that the eager operations round twice
        for wrap in (lambda f: f, jax.jit):
            want = np.asarray(wrap(bbox_transform)(ex, gt))
            assert want.shape == (4096, 4) and np.isfinite(want).all()
            assert (np.asarray(wrap(planes)(ex, gt)) == want).all()


class TestSampleRois:
    def make_rois(self, rng, n=300, lo=0, hi=380):
        r = rng.rand(n, 4).astype(np.float32) * (hi - lo) + lo
        r[:, 2:] = np.minimum(r[:, :2] + rng.rand(n, 2) * 100 + 10, 399)
        return jnp.array(r), jnp.ones((n,), bool)

    def test_shapes_and_budget(self, rng):
        rois, rv = self.make_rois(rng)
        gt, gv = pad_gt([[50, 50, 150, 150, 3], [200, 200, 300, 300, 7]])
        s = sample_rois(rois, rv, gt, gv, jax.random.key(0), CFG)
        R, K = CFG.TRAIN.BATCH_ROIS, CFG.dataset.NUM_CLASSES
        assert s.rois.shape == (R, 4)
        assert s.bbox_targets.shape == (R, 4 * K)
        labels = np.asarray(s.labels)
        n_fg = (labels > 0).sum()
        assert n_fg <= round(CFG.TRAIN.FG_FRACTION * R)
        # gt boxes are appended as candidates -> at least the gts are fg
        assert n_fg >= 2

    def test_fg_labels_match_gt_class(self, rng):
        rois, rv = self.make_rois(rng, n=50)
        gt, gv = pad_gt([[50, 50, 150, 150, 3]])
        s = sample_rois(rois, rv, gt, gv, jax.random.key(1), CFG)
        labels = np.asarray(s.labels)
        assert set(labels[labels > 0].tolist()) <= {3}

    def test_bbox_target_layout(self, rng):
        # fg targets live exactly in their class's 4-slot block
        rois, rv = self.make_rois(rng, n=50)
        gt, gv = pad_gt([[50, 50, 150, 150, 3]])
        s = sample_rois(rois, rv, gt, gv, jax.random.key(2), CFG)
        labels = np.asarray(s.labels)
        w = np.asarray(s.bbox_weights).reshape(len(labels), -1, 4)
        for i, lab in enumerate(labels):
            if lab > 0:
                assert (w[i, lab] == 1).all()
                assert w[i].sum() == 4
            else:
                assert w[i].sum() == 0

    def test_gt_roi_regresses_to_zero_after_norm_inverse(self, rng):
        # a roi that IS the gt box must have ~zero raw target
        gt, gv = pad_gt([[50, 50, 150, 150, 3]])
        rois = jnp.tile(gt[:1, :4], (30, 1))
        rv = jnp.ones((30,), bool)
        s = sample_rois(rois, rv, gt, gv, jax.random.key(3), CFG)
        labels = np.asarray(s.labels)
        tgt = np.asarray(s.bbox_targets).reshape(len(labels), -1, 4)
        means = np.array(CFG.TRAIN.BBOX_MEANS)
        stds = np.array(CFG.TRAIN.BBOX_STDS)
        for i, lab in enumerate(labels):
            if lab > 0:
                raw = tgt[i, lab] * stds + means
                np.testing.assert_allclose(raw, 0, atol=1e-5)
