"""Faster R-CNN with Deformable ConvNets (``--network resnet_dcn``): the
graph, its counters, and the detector against its plain reference
(``benchmark/reference/models/dcn.py``) - the comparison that decides
``correct`` in the cell ``dcn_train_b8`` on the chip, rehearsed at a tiny
size on the CPU.

Same batch, same sampling keys, seeded weights (the two trees carry the
same leaf names and initialisers, so ``model.init`` draws the same
values), float32 at ``highest`` on both sides.  Both sides sample the
same points; what differs is the order of the operations around them
(the program gathers every tap's four corners as rows and multiplies
once, the reference loops over the taps; the program pools every image's
rois at once, the reference one roi after the other): float32 round-off,
held to the tolerances ``tests/test_fpn_reference.py`` uses for the
pyramid: 1e-5 on the loss (a sum of some thousands of float32 terms),
1e-4 on a gradient leaf's norm against the leaf's own or the median
leaf's, as ``check_train`` measures it.  The counts are exact.  The
program with its offsets forced to zero reads far outside both (the
planted fault the chip's limits are held against)."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.config import NETWORKS, generate_config
from mx_rcnn_tpu.core.train import is_frozen_path
from mx_rcnn_tpu.models import build_model
from mx_rcnn_tpu.models import faster_rcnn as program_frcnn
from mx_rcnn_tpu.models import resnet as program_resnet
from mx_rcnn_tpu.models.stage_models import FastRCNN, RPNOnly
from tests.test_model import tiny_batch

_BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)

from harness.check_train import worst_leaf_gap  # noqa: E402
from harness.train_driver import leaf_norms  # noqa: E402

LOSS_RTOL = 1e-5
LEAF_RTOL = 1e-4
REF_COUNTS = ("num_fg_anchors", "num_valid_props", "num_fg_rois")
RH, RW, RB, RG = 160, 192, 2, 4


def _tiny(generate, network):
    """``network`` at its published widths and depth, one 160×192
    bucket, 16 rois an image, 4 classes."""
    cfg = generate(network, "PascalVOC")
    return cfg.replace(
        SHAPE_BUCKETS=((RH, RW),),
        TRAIN=dataclasses.replace(
            cfg.TRAIN, BATCH_IMAGES=RB, BATCH_ROIS=16, RPN_BATCH_SIZE=64,
            RPN_PRE_NMS_TOP_N=400, RPN_POST_NMS_TOP_N=64),
        TEST=dataclasses.replace(
            cfg.TEST, RPN_PRE_NMS_TOP_N=200, RPN_POST_NMS_TOP_N=32),
        dataset=dataclasses.replace(
            cfg.dataset, NUM_CLASSES=4, SCALES=((RH, RW),), MAX_GT_BOXES=RG),
    )


def _batch():
    batch = tiny_batch(np.random.RandomState(7), b=RB, h=RH, w=RW, g=RG)
    # the images differ in extent, so ``im_info`` and the padding matter
    batch["im_info"] = jnp.asarray([[RH, RW, 1.0], [150, 180, 1.0]],
                                   jnp.float32)
    batch["sample_seeds"] = jnp.asarray([3, 11], jnp.int32)
    return batch


def _init(model):
    first = {k: v[:1] for k, v in _batch().items() if k != "sample_seeds"}
    return jax.jit(lambda: model.init(
        {"params": jax.random.key(5), "sampling": jax.random.key(1)},
        train=True, **first)["params"])()


def _loss_aux_grads(model, params):
    batch = _batch()

    @jax.jit
    def run(p):
        def loss_fn(q):
            return model.apply({"params": q}, train=True,
                               rngs={"sampling": jax.random.key(9)}, **batch)

        return jax.value_and_grad(loss_fn, has_aux=True)(p)

    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = run(params)
    return float(loss), {k: float(v) for k, v in aux.items()}, grads


@pytest.fixture(scope="module")
def program_model():
    model = build_model(_tiny(generate_config, "resnet_dcn"))
    return model, _init(model)


@pytest.fixture(scope="module")
def program_side(program_model):
    return _loss_aux_grads(*program_model)


@pytest.fixture(scope="module")
def reference_model():
    from reference.config import generate_config as reference_config
    from reference.models import build_model as build_reference

    model = build_reference(_tiny(reference_config, "resnet"), "dcn")
    return model, _init(model)


@pytest.fixture(scope="module")
def reference_side(reference_model):
    return _loss_aux_grads(*reference_model)


class TestTheGraph:
    def test_the_registry_entry(self):
        net = NETWORKS["resnet_dcn"]
        assert (net.name, net.depth, net.ROI_MODE, net.POOLED_SIZE,
                net.ROI_SAMPLE_RATIO) == (
            "resnet", 101, "deform_roi_pool", (7, 7), 4)
        # the one switch the graph reads; no other entry turns it on
        assert [k for k, n in NETWORKS.items() if n.deformable] == [
            "resnet_dcn"]
        # what the reference's own batches are built from: ``resnet``'s
        for field in ("PIXEL_MEANS", "PIXEL_STDS", "ANCHOR_SCALES",
                      "ANCHOR_RATIOS", "RPN_FEAT_STRIDE", "RCNN_FEAT_STRIDE",
                      "FIXED_PARAMS", "USE_FPN", "COMPUTE_DTYPE"):
            assert getattr(net, field) == getattr(NETWORKS["resnet"], field)

    def test_the_leaves_and_what_trains(self, program_model):
        _model, params = program_model
        flat = {"/".join(p.key for p in k): v.shape for k, v in
                jax.tree_util.tree_flatten_with_path(params)[0]}
        for u in (1, 2, 3):
            unit = f"backbone/stage4/unit{u}"
            assert flat[f"{unit}/conv2/kernel"] == (3, 3, 512, 512)
            assert flat[f"{unit}/conv2_offset/kernel"] == (3, 3, 512, 72)
            assert flat[f"{unit}/conv2_offset/bias"] == (72,)
            assert f"{unit}/conv2/bias" not in flat
        assert flat["backbone/stage4/unit1/sc/kernel"] == (1, 1, 1024, 2048)
        assert flat["backbone/conv_new_1/kernel"] == (1, 1, 2048, 256)
        assert flat["roi_offset/kernel"] == (7 * 7 * 256, 98)
        assert flat["top_head/fc_new_1/kernel"] == (7 * 7 * 256, 1024)
        assert flat["top_head/fc_new_2/kernel"] == (1024, 1024)
        fixed = NETWORKS["resnet_dcn"].FIXED_PARAMS
        trained = {n for n in flat if not is_frozen_path(tuple(n.split("/")),
                                                          fixed)}
        assert "backbone/stage4/unit2/conv2_offset/kernel" in trained
        assert "roi_offset/kernel" in trained
        assert not any(n.startswith(("backbone/conv0", "backbone/stage1"))
                       for n in trained)
        # 64 M parameters, as the configuration states
        assert sum(int(np.prod(s)) for s in flat.values()) == pytest.approx(
            64e6, rel=0.02)

    def test_the_counters(self, program_side):
        _loss, aux, _grads = program_side
        # 2 images × 10 × 12 positions × 9 taps × 4 groups a layer
        assert aux["deform_points"] == RB * 10 * 12 * 9 * 4
        for u in (1, 2, 3):
            assert 0.5 * aux["deform_points"] < aux[f"deform_inside_u{u}"] < (
                aux["deform_points"])
        assert aux["deform_pool_bins"] == RB * 16 * 49
        assert 0 <= aux["deform_pool_empty_bins"] < aux["deform_pool_bins"]

    def test_test_forward_runs(self, program_model):
        model, params = program_model
        batch = _batch()
        out = model.apply({"params": params}, batch["images"],
                          batch["im_info"], train=False)
        assert out["cls_prob"].shape == (RB, 32, 4)
        assert out["bbox_deltas"].shape == (RB, 32, 16)
        assert bool(jnp.isfinite(out["cls_prob"]).all())
        assert int(out["roi_valid"].sum()) > 0

    @pytest.mark.parametrize("stage_model", [RPNOnly, FastRCNN])
    def test_the_stage_graphs_refuse_it(self, stage_model):
        model = stage_model(_tiny(generate_config, "resnet_dcn"))
        batch = _batch()
        with pytest.raises(NotImplementedError, match="resnet_dcn"):
            jax.eval_shape(lambda: model.init(
                {"params": jax.random.key(0), "sampling": jax.random.key(1)},
                batch["images"], batch["im_info"]))


@pytest.mark.parametrize("network, graph", [("resnet", "c4"),
                                            ("resnet_fpn", "fpn")])
def test_c4_and_the_pyramid_keep_their_param_trees(network, graph):
    """The program's C4 and pyramid trees, leaf for leaf and shape for
    shape, are the frozen reference's copies of them: the deformable
    branch added nothing to either."""
    from reference.config import generate_config as reference_config
    from reference.models import build_model as build_reference

    def tree(model):
        batch = {k: v[:1] for k, v in _batch().items() if k != "sample_seeds"}
        shapes = jax.eval_shape(lambda: model.init(
            {"params": jax.random.key(0), "sampling": jax.random.key(1)},
            train=True, **batch)["params"])
        return {"/".join(p.key for p in k): v.shape for k, v in
                jax.tree_util.tree_flatten_with_path(shapes)[0]}

    program = tree(build_model(_tiny(generate_config, network)))
    reference = tree(build_reference(_tiny(reference_config, network), graph))
    assert program == reference
    assert not any("offset" in n or "conv_new" in n for n in program)


class TestDCNAgainstItsReference:
    def test_reference_imports_nothing_of_the_program(self):
        with open(os.path.join(_BENCH, "reference", "models", "dcn.py")) as f:
            text = f.read()
        assert "import mx_rcnn_tpu" not in text
        assert "from mx_rcnn_tpu" not in text

    def test_the_seed_draws_the_same_weights(self, program_model,
                                             reference_model):
        """The reference makes its parameters on a small canvas; they are
        the program's, made by a forward at the batch's size, leaf for
        leaf and bit for bit."""
        got = jax.tree_util.tree_flatten_with_path(reference_model[1])[0]
        want = dict(jax.tree_util.tree_flatten_with_path(program_model[1])[0])
        assert len(got) == len(want)
        for path, value in got:
            np.testing.assert_array_equal(np.asarray(value),
                                          np.asarray(want[path]))

    def test_loss_agrees(self, program_side, reference_side):
        assert np.isfinite(reference_side[0])
        assert abs(program_side[0] - reference_side[0]) <= LOSS_RTOL * abs(
            reference_side[0])

    @pytest.mark.parametrize("name", REF_COUNTS)
    def test_count_agrees(self, program_side, reference_side, name):
        assert program_side[1][name] == reference_side[1][name] > 0

    def test_gradient_leaves_agree_the_offset_leaves_too(
            self, program_side, reference_side):
        got, ref = leaf_norms(program_side[2]), leaf_norms(reference_side[2])
        gap, leaf = worst_leaf_gap(got, ref)
        assert gap <= LEAF_RTOL, (gap, leaf)
        offsets = [n for n in ref if "offset" in n]
        assert len(offsets) == 3 * 2 + 2
        for n in offsets:
            assert ref[n] > 0
            assert abs(got[n] - ref[n]) <= LEAF_RTOL * ref[n], n

    def test_offsets_forced_to_zero_are_seen(self, monkeypatch, program_model,
                                             reference_side):
        """The program with every offset forced to 0 (a plain dilated
        conv5 and a fixed-grid pooling): the offset layers' gradients
        vanish, so their leaves read a gap of 1."""
        conv = program_resnet.deform_conv
        pool = program_frcnn.deform_roi_pool_batched
        monkeypatch.setattr(program_resnet, "deform_conv",
                            lambda x, o, k, d, g: conv(x, o * 0, k, d, g))
        monkeypatch.setattr(program_frcnn, "deform_roi_pool_batched",
                            lambda *a, **kw: pool(*a, **dict(kw, offsets=None)))
        _loss, _aux, grads = _loss_aux_grads(*program_model)
        gap, leaf = worst_leaf_gap(leaf_norms(grads),
                                   leaf_norms(reference_side[2]))
        assert gap > 0.99 and "offset" in leaf, (gap, leaf)
