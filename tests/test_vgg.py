"""VGG-16 Faster R-CNN path (BASELINE config 1): fwd/bwd, roi_pool mode,
overfit — VERDICT r1 weak #4 ("VGG path is write-only code")."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.core.train import (
    create_train_state,
    is_frozen_path,
    make_optimizer,
    make_train_step,
)
from mx_rcnn_tpu.models import build_model
from tests.test_model import tiny_batch


def vgg_cfg():
    cfg = generate_config("vgg", "PascalVOC")
    assert cfg.network.ROI_MODE == "roi_pool"       # MXNet-compat mode
    assert cfg.network.POOLED_SIZE == (7, 7)
    return cfg.replace(
        dataset=dataclasses.replace(cfg.dataset, NUM_CLASSES=4),
        TRAIN=dataclasses.replace(
            cfg.TRAIN,
            RPN_PRE_NMS_TOP_N=400,
            RPN_POST_NMS_TOP_N=64,
            BATCH_ROIS=32,
            RPN_BATCH_SIZE=64,
        ),
        TEST=dataclasses.replace(
            cfg.TEST, RPN_PRE_NMS_TOP_N=200, RPN_POST_NMS_TOP_N=32
        ),
    )


@pytest.fixture(scope="module")
def vgg_model_and_params():
    cfg = vgg_cfg()
    model = build_model(cfg)
    # 192: smallest anchor (128 px) must fit inside the border
    batch = tiny_batch(np.random.RandomState(0), h=192, w=192)
    # under jit: op by op, the 103 M-parameter fc6 alone takes a minute
    params = jax.jit(lambda: model.init(
        {"params": jax.random.key(0), "sampling": jax.random.key(1)},
        train=True, **batch,
    )["params"])()
    return cfg, model, params


class TestVGGFasterRCNN:
    def test_train_forward_and_frozen_blocks(self, vgg_model_and_params):
        cfg, model, params = vgg_model_and_params
        batch = tiny_batch(np.random.RandomState(1), h=192, w=192)
        loss, aux = model.apply(
            {"params": params}, train=True,
            rngs={"sampling": jax.random.key(2)}, **batch,
        )
        assert np.isfinite(float(loss)) and float(loss) > 0
        assert float(aux["num_fg_anchors"]) > 0
        # conv1/conv2 frozen (reference FIXED_PARAMS for vgg)
        assert is_frozen_path(
            ("backbone", "conv1_1", "kernel"), cfg.network.FIXED_PARAMS
        )
        assert is_frozen_path(
            ("backbone", "conv2_2", "bias"), cfg.network.FIXED_PARAMS
        )
        assert not is_frozen_path(
            ("backbone", "conv3_1", "kernel"), cfg.network.FIXED_PARAMS
        )

    def test_test_forward_shapes(self, vgg_model_and_params):
        cfg, model, params = vgg_model_and_params
        batch = tiny_batch(np.random.RandomState(1), h=192, w=192)
        out = model.apply(
            {"params": params}, batch["images"], batch["im_info"], train=False
        )
        r = cfg.TEST.RPN_POST_NMS_TOP_N
        k = cfg.dataset.NUM_CLASSES
        assert out["cls_prob"].shape == (1, r, k)
        assert out["bbox_deltas"].shape == (1, r, 4 * k)
        assert out["roi_valid"].sum() > 0

    def test_overfit_loss_decreases(self, vgg_model_and_params):
        cfg, model, params = vgg_model_and_params
        tx = make_optimizer(cfg, lambda s: 0.001)
        state = create_train_state(params, tx)
        step = make_train_step(model, tx, donate=False)
        batch = tiny_batch(np.random.RandomState(3), h=192, w=192)
        losses = []
        for _ in range(20):
            state, aux = step(state, batch, jax.random.key(42))
            losses.append(float(aux["loss"]))
        assert np.isfinite(losses).all()
        assert np.mean(losses[-3:]) < np.mean(losses[:3]) * 0.9


# ------------------------------------------------------------------------
# The VGG detector against its plain reference
# (``benchmark/reference/models/vgg.py``): the comparison that decides
# ``correct`` in the cell ``vgg_train_b8`` on the chip, rehearsed at a tiny
# size on the CPU.  Same batch, same sampling keys, seeded weights (the two
# trees carry the same leaf names, so ``model.init`` draws the same
# values), float32 on both sides.  Both sides take ROIPooling's bins in
# whole numbers (each its own formulation, each held to an independent
# loop in ``tests/test_roi_align.py``) and draw the same dropout masks, so
# what differs is the order of the operations around them (the program
# runs fc6 / fc7 on all images' rois at once, the reference one image
# after the other): float32 round-off, held to the tolerances ``tests/test_fpn_reference.py`` uses
# for the pyramid: 1e-5 on the loss (a sum of some thousands of float32
# terms), 1e-4 on a gradient leaf's norm against the leaf's own or the
# median leaf's, as ``check_train`` measures it.  The counts are exact.
# A head without dropout reads 6e-4 on the loss and far more on a leaf of
# the head (the planted fault).

import os  # noqa: E402
import sys  # noqa: E402

_BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)

from harness.check_train import worst_leaf_gap  # noqa: E402
from harness.train_driver import leaf_norms  # noqa: E402

from mx_rcnn_tpu.models import vgg as program_vgg  # noqa: E402

LOSS_RTOL = 1e-5
LEAF_RTOL = 1e-4
REF_COUNTS = ("num_fg_anchors", "num_valid_props", "num_fg_rois")
RH, RW, RB, RG = 160, 192, 4, 4


def _tiny_pair(generate_config):
    """``vgg`` at its published widths, one 160×192 bucket, 16 rois an
    image, 4 classes."""
    cfg = generate_config("vgg", "PascalVOC")
    return cfg.replace(
        SHAPE_BUCKETS=((RH, RW),),
        TRAIN=dataclasses.replace(
            cfg.TRAIN, BATCH_IMAGES=RB, BATCH_ROIS=16, RPN_BATCH_SIZE=64,
            RPN_PRE_NMS_TOP_N=400, RPN_POST_NMS_TOP_N=64),
        dataset=dataclasses.replace(
            cfg.dataset, NUM_CLASSES=4, SCALES=((RH, RW),), MAX_GT_BOXES=RG),
    )


def _ref_batch(seeds=True):
    batch = tiny_batch(np.random.RandomState(7), b=RB, h=RH, w=RW, g=RG)
    # the images differ in extent, so ``im_info`` and the padding matter
    batch["im_info"] = jnp.asarray(
        [[RH, RW, 1.0], [150, 180, 1.0], [RH, 170, 1.0], [140, RW, 1.0]],
        jnp.float32)
    if seeds:
        batch["sample_seeds"] = jnp.asarray([3, 11, 5, 8], jnp.int32)
    return batch


def _init(model, batch):
    first = {k: v[:1] for k, v in batch.items() if k != "sample_seeds"}
    return jax.jit(lambda: model.init(
        {"params": jax.random.key(5), "sampling": jax.random.key(1)},
        train=True, **first)["params"])()


def _loss_counts_grads(model, params, batch, **kw):
    @jax.jit
    def run(p):
        def loss_fn(q):
            return model.apply({"params": q}, train=True,
                               rngs={"sampling": jax.random.key(9)},
                               **batch, **kw)

        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(p)
        return loss, {k: aux[k] for k in REF_COUNTS}, grads

    loss, counts, grads = run(params)
    return float(loss), {k: int(v) for k, v in counts.items()}, grads


@pytest.fixture(scope="module")
def reference_model():
    from reference.config import generate_config
    from reference.models import build_model as build_reference

    model = build_reference(_tiny_pair(generate_config), "vgg")
    return model, _init(model, _ref_batch())


@pytest.fixture(scope="module")
def reference_side(reference_model):
    model, params = reference_model
    return _loss_counts_grads(model, params, _ref_batch())


@pytest.fixture(scope="module")
def program_model():
    model = build_model(_tiny_pair(generate_config))
    return model, _init(model, _ref_batch())


@pytest.fixture(scope="module")
def program_side(program_model):
    return _loss_counts_grads(*program_model, _ref_batch())


class TestVGGAgainstItsReference:
    def test_reference_imports_nothing_of_the_program(self):
        with open(os.path.join(_BENCH, "reference", "models", "vgg.py")) as f:
            text = f.read()
        assert "import mx_rcnn_tpu" not in text
        assert "from mx_rcnn_tpu" not in text

    def test_loss_agrees(self, program_side, reference_side):
        assert np.isfinite(reference_side[0])
        assert abs(program_side[0] - reference_side[0]) <= LOSS_RTOL * abs(
            reference_side[0])

    @pytest.mark.parametrize("name", REF_COUNTS)
    def test_count_agrees(self, program_side, reference_side, name):
        assert program_side[1][name] == reference_side[1][name] > 0

    def test_gradient_leaves_agree_and_the_fixed_blocks_get_none(
            self, program_side, reference_side):
        got, ref = leaf_norms(program_side[2]), leaf_norms(reference_side[2])
        gap, leaf = worst_leaf_gap(got, ref)
        assert gap <= LEAF_RTOL, (gap, leaf)
        for side in (got, ref):
            for name, norm in side.items():
                fixed = name.startswith(("backbone/conv1_", "backbone/conv2_"))
                assert (norm == 0.0) == fixed, (name, norm)

    def test_blocks_of_two_rows_draw_the_whole_batch_s_masks(
            self, reference_model, program_side, reference_side):
        """The reference followed in blocks of 2 rows, as ``check_train``
        follows a step of 8: each row draws the sampling keys AND the
        dropout masks it draws in the whole batch, so the mean over the
        blocks is the batch's loss and gradient (by ``sample_seeds`` here;
        by ``full_batch`` / ``row_offset`` below)."""
        model, params = reference_model
        batch = _ref_batch()
        parts = [
            _loss_counts_grads(
                model, params, {k: v[i:i + 2] for k, v in batch.items()})
            for i in (0, 2)]
        loss = sum(p[0] for p in parts) / 2
        assert abs(loss - reference_side[0]) <= LOSS_RTOL * abs(
            reference_side[0])
        assert abs(loss - program_side[0]) <= LOSS_RTOL * abs(program_side[0])
        grads = jax.tree_util.tree_map(
            lambda a, b: (a + b) / 2, parts[0][2], parts[1][2])
        gap, leaf = worst_leaf_gap(
            leaf_norms(grads), leaf_norms(reference_side[2]))
        assert gap <= LEAF_RTOL, (gap, leaf)

    def test_rows_without_seeds_follow_by_offset(self, reference_model,
                                                 program_model):
        """No ``sample_seeds``: the program splits the step's key over the
        batch's rows; the reference's blocks pick their rows' keys out of
        that split by ``full_batch`` / ``row_offset``."""
        model, params = reference_model
        batch = _ref_batch(seeds=False)
        whole = _loss_counts_grads(*program_model, batch)
        parts = [
            _loss_counts_grads(
                model, params, {k: v[i:i + 2] for k, v in batch.items()},
                full_batch=RB, row_offset=i)
            for i in (0, 2)]
        loss = sum(p[0] for p in parts) / 2
        assert abs(loss - whole[0]) <= LOSS_RTOL * abs(whole[0])

    def test_a_head_without_dropout_is_seen(self, monkeypatch, program_model,
                                            reference_side):
        """Dropout left out of the program (rate 0: every unit kept,
        nothing scaled): the loss leaves its tolerance by 60 times (on
        these weights the outputs start near zero, so the loss hangs
        little on the head) and the head's gradient leaves by thousands:
        the kept half of ``fc6``'s units is what ``fc7``'s gradient is an
        outer product with."""
        monkeypatch.setattr(program_vgg, "DROPOUT_RATE", 0.0)
        loss, _counts, grads = _loss_counts_grads(*program_model, _ref_batch())
        loss_gap = abs(loss - reference_side[0]) / abs(reference_side[0])
        leaf_gap, leaf = worst_leaf_gap(
            leaf_norms(grads), leaf_norms(reference_side[2]))
        assert loss_gap > 10 * LOSS_RTOL, loss_gap
        assert leaf_gap > 1e3 * LEAF_RTOL, (leaf_gap, leaf)
        print("no dropout:", loss_gap, leaf_gap, leaf)


class TestVGGTopHeadDropout:
    def _head(self):
        head = program_vgg.VGGTopHead()
        x = jnp.asarray(
            np.random.RandomState(0).rand(6, 7, 7, 8).astype(np.float32))
        params = head.init(jax.random.key(0), x)
        return head, params, x

    def test_training_drops_half_and_doubles_the_rest(self):
        keys = jax.random.split(jax.random.key(3), 2)     # two images
        ones = program_vgg.dropout_rows(jnp.ones((6, 4096)), keys, 0.5)
        assert set(np.unique(ones).tolist()) == {0.0, 2.0}
        assert 0.47 < float((ones == 0).mean()) < 0.53
        head, params, x = self._head()
        plain = head.apply(params, x)
        dropped = head.apply(params, x, keys)
        assert dropped.shape == plain.shape == (6, 4096)
        # fc7's mask alone zeroes half of the units, ReLU some of the rest
        assert float((dropped == 0).mean()) > 0.47
        assert float((plain == 0).mean()) < float((dropped == 0).mean())

    def test_a_row_s_mask_is_its_image_s_key_alone(self):
        """Image 1's rows under (k0, k1) equal image 0's rows under (k1,
        k0) when both images hold the same rois: the mask hangs on the
        image's key, not on its place in the batch."""
        head, params, x = self._head()
        x = jnp.concatenate([x[:3], x[:3]])
        k = jax.random.split(jax.random.key(3), 2)
        a = head.apply(params, x, k)
        b = head.apply(params, x, k[::-1])
        np.testing.assert_array_equal(a[:3], b[3:])
        np.testing.assert_array_equal(a[3:], b[:3])
        assert bool((a[:3] != a[3:]).any())

    def test_test_forward_draws_nothing(self, vgg_model_and_params):
        """``test_forward`` needs no rng stream (``make_rng`` would raise
        without one) and answers the same twice."""
        cfg, model, params = vgg_model_and_params
        batch = tiny_batch(np.random.RandomState(1), h=192, w=192)
        outs = [model.apply({"params": params}, batch["images"],
                            batch["im_info"], train=False) for _ in range(2)]
        np.testing.assert_array_equal(outs[0]["cls_prob"], outs[1]["cls_prob"])
