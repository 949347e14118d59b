"""The pyramid detector against its plain reference
(``benchmark/reference/models/fpn.py``), at a tiny size on the CPU: the
comparison that decides ``correct`` in the cell ``fpn_train_b8`` on the
chip, rehearsed where it costs nothing.

Same batch, same sampling keys, seeded weights (the two trees carry the
same leaf names, so ``model.init`` draws the same values), float32 on both
sides.  What differs is how the second stage pools: the program pools
every roi on every one of P2..P5 and masks three results away, through
one-hot matrix products on a TPU and through the chunked gather here; the
reference pools each roi once, from its own level, one roi after the
other.  In exact arithmetic the two are equal, so the tolerance is
float32 round-off in another order of summation: 1e-5 on the loss, 1e-4
on a gradient leaf's norm (against the leaf's own norm or the median
leaf's, as ``check_train`` measures it); read here 0 and 2.4e-7.  The
counts are exact.  A roi → level map shifted by one level reads 3.8e-2
and 2.2 (the planted fault).
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)

from harness.check_train import worst_leaf_gap  # noqa: E402
from harness.train_driver import leaf_norms  # noqa: E402

from mx_rcnn_tpu.models import fpn as program_fpn  # noqa: E402

LOSS_RTOL = 1e-5
LEAF_RTOL = 1e-4
COUNTS = ("num_fg_anchors", "num_valid_props", "num_fg_rois",
          "num_rois_p2", "num_rois_p3", "num_rois_p4", "num_rois_p5")
H, W, B, G = 128, 192, 2, 8


def _tiny(generate_config):
    """``resnet_fpn`` (ResNet-50, the shallowest trunk both sides build)
    cut to one 128×192 bucket, 16 rois an image, 5 classes."""
    cfg = generate_config("resnet_fpn", "coco")
    return cfg.replace(
        SHAPE_BUCKETS=((H, W),),
        TRAIN=dataclasses.replace(
            cfg.TRAIN, BATCH_IMAGES=B, BATCH_ROIS=16, RPN_BATCH_SIZE=64,
            RPN_PRE_NMS_TOP_N=1280, RPN_POST_NMS_TOP_N=64),
        dataset=dataclasses.replace(
            cfg.dataset, NUM_CLASSES=5, SCALES=((H, W),), MAX_GT_BOXES=G),
    )


def _batch():
    """Two images of noise with three boxes each, from 12 to 150 pixels:
    eq. 1 sends them to different levels."""
    rng = np.random.RandomState(7)
    gt = np.zeros((B, G, 5), np.float32)
    gt[0, :3] = [[10, 12, 40, 50, 1], [60, 20, 180, 120, 2],
                 [100, 70, 112, 84, 3]]
    gt[1, :3] = [[5, 5, 150, 125, 4], [90, 30, 130, 60, 1],
                 [20, 80, 70, 120, 2]]
    valid = np.zeros((B, G), bool)
    valid[:, :3] = True
    return {
        "images": jnp.asarray(rng.randn(B, H, W, 3).astype(np.float32)),
        "im_info": jnp.asarray([[H, W, 1.0]] * B, jnp.float32),
        "gt_boxes": jnp.asarray(gt),
        "gt_valid": jnp.asarray(valid),
        "sample_seeds": jnp.asarray([3, 11], jnp.int32),
    }


def _loss_counts_grads(model, batch):
    """→ (loss, {count: value}, {leaf: gradient norm}), one compile."""
    init = {k: v[:1] for k, v in batch.items() if k != "sample_seeds"}
    params = model.init(
        {"params": jax.random.key(5), "sampling": jax.random.key(1)},
        train=True, **init)["params"]

    @jax.jit
    def run(p):
        def loss_fn(q):
            return model.apply({"params": q}, train=True,
                               rngs={"sampling": jax.random.key(9)}, **batch)

        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(p)
        return loss, {k: aux[k] for k in COUNTS}, grads

    loss, counts, grads = run(params)
    return (float(loss), {k: int(v) for k, v in counts.items()},
            leaf_norms(grads))


@pytest.fixture(scope="module")
def reference_side():
    from reference.config import generate_config
    from reference.models import build_model

    return _loss_counts_grads(
        build_model(_tiny(generate_config), "fpn"), _batch())


def _program_side():
    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.models import build_model

    return _loss_counts_grads(build_model(_tiny(generate_config)), _batch())


@pytest.fixture(scope="module")
def program_side():
    return _program_side()


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(_BENCH, "reference", "models", "fpn.py")) as f:
        text = f.read()
    assert "import mx_rcnn_tpu" not in text
    assert "from mx_rcnn_tpu" not in text


def test_loss_agrees(program_side, reference_side):
    assert np.isfinite(reference_side[0])
    assert abs(program_side[0] - reference_side[0]) <= LOSS_RTOL * abs(
        reference_side[0])


@pytest.mark.parametrize("name", COUNTS)
def test_count_agrees(program_side, reference_side, name):
    assert program_side[1][name] == reference_side[1][name]


def test_counts_say_something(reference_side):
    counts = reference_side[1]
    assert counts["num_fg_anchors"] > 0 and counts["num_fg_rois"] > 0
    per_level = [counts[f"num_rois_p{lv}"] for lv in (2, 3, 4, 5)]
    assert sum(per_level) == B * 16            # every sampled roi, once
    assert sum(n > 0 for n in per_level) >= 2  # more than one level pools


def test_gradient_leaves_agree(program_side, reference_side):
    """The same leaf names (``worst_leaf_gap`` raises otherwise) and every
    leaf's gradient norm within ``LEAF_RTOL``."""
    gap, leaf = worst_leaf_gap(program_side[2], reference_side[2])
    assert gap <= LEAF_RTOL, (gap, leaf)
    trained = [n for n, v in reference_side[2].items() if v > 0]
    assert any(n.startswith("neck/") for n in trained)
    assert any(n.startswith("top_head/fc1") for n in trained)


def test_planted_level_fault_is_seen(monkeypatch, reference_side):
    """The program's copy with every roi pooled one level too coarse (P5
    stays): the loss or a leaf leaves the tolerance, by far."""
    sound = program_fpn.roi_levels
    monkeypatch.setattr(
        program_fpn, "roi_levels",
        lambda rois, *a, **kw: jnp.minimum(sound(rois, *a, **kw) + 1, 5))
    loss, _counts, grads = _program_side()
    loss_gap = abs(loss - reference_side[0]) / abs(reference_side[0])
    leaf_gap, _leaf = worst_leaf_gap(grads, reference_side[2])
    assert loss_gap > 10 * LOSS_RTOL or leaf_gap > 10 * LEAF_RTOL, (
        loss_gap, leaf_gap)


def test_level_counter_follows_eq_1():
    """``num_rois_p2..p5`` against ``roi_levels`` on boxes made by hand:
    sides 32, 111 (< 112: P2), 112, 223 (P3), 224, 447 (P4), 448, 900
    (P5), and one of a single pixel (held to P2)."""
    sides = [32, 111, 112, 223, 224, 447, 448, 900, 1]
    rois = jnp.asarray([[10.0, 20.0, 10.0 + s - 1, 20.0 + s - 1]
                        for s in sides])
    want = [2, 2, 3, 3, 4, 4, 5, 5, 2]
    assert program_fpn.roi_levels(rois).tolist() == want
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "reference_fpn_for_levels",
        os.path.join(_BENCH, "reference", "models", "fpn.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    assert ref.roi_levels(rois).tolist() == want
    # a 2:1 box of the area of a 224 square still goes to P4
    wide = jnp.asarray([[0.0, 0.0, 316.0, 157.4]])
    assert program_fpn.roi_levels(wide).tolist() == [4]
    # the step's counter is that map, counted: over a batch of two rows
    counts = program_fpn.roi_level_counts(jnp.stack([rois, rois]))
    assert {k: int(v) for k, v in counts.items()} == {
        "num_rois_p2": 6, "num_rois_p3": 4, "num_rois_p4": 4,
        "num_rois_p5": 4}


def test_per_image_selects_what_vmap_selects():
    """``per_image`` at batch 1 (no batch axis: the shape the chip's
    compiler takes) against ``jax.vmap`` on scores without ties: the same
    ``top_k`` set, in the same order, as row 0 of a batch of two."""
    rng = np.random.RandomState(3)
    scores = jnp.asarray(rng.permutation(4096).astype(np.float32))[None]
    pick = lambda s: jax.lax.top_k(s, 300)                      # noqa: E731
    one_v, one_i = program_fpn.per_image(pick, scores)
    two_v, two_i = program_fpn.per_image(
        pick, jnp.concatenate([scores, scores[:, ::-1]]))
    want_v, want_i = jax.vmap(pick)(scores)
    assert one_v.shape == (1, 300) and two_v.shape == (2, 300)
    np.testing.assert_array_equal(one_i, want_i)
    np.testing.assert_array_equal(one_v, want_v)
    np.testing.assert_array_equal(two_i[0], want_i[0])
    # a descending sort and a slice (the reference's statement) agrees
    np.testing.assert_array_equal(
        np.sort(np.asarray(one_i[0])),
        np.sort(np.argsort(-np.asarray(scores[0]), kind="stable")[:300]))


def test_pyramid_step_names_its_scopes(monkeypatch):
    """The tiny pyramid's train step lowered FOR THE TPU (no compile, no
    chip): flax's ``neck`` and one scope component a pooled level next to
    ``roi_align``, each holding a forward and a backward ROIAlign kernel
    whose names the benchmark's pattern finds."""
    import json
    import re

    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.core.train import (
        create_train_state, make_lr_schedule, make_optimizer, make_train_step,
    )
    from mx_rcnn_tpu.models import build_model
    from mx_rcnn_tpu.utils import tracing

    cfg = _tiny(generate_config)
    model = build_model(cfg)
    batch = _batch()
    params = jax.eval_shape(lambda: model.init(
        {"params": jax.random.key(0), "sampling": jax.random.key(1)},
        train=True, **batch)["params"])
    tx = make_optimizer(cfg, make_lr_schedule(cfg, 10))
    state = jax.eval_shape(lambda p: create_train_state(p, tx), params)
    monkeypatch.setenv("MX_RCNN_TPU_PALLAS", "1")
    text = make_train_step(model, tx).trace(
        state, batch, jax.random.key(2)).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    names = set()
    for name in re.findall(r'loc\("([^"]+)"', text):
        names.add("/".join(re.sub(r"^(?:[\w.]+\()+(.*?)\)+$", r"\1", c)
                           for c in name.split("/")))
    for scope in tracing.FPN_SCOPES:
        assert any(f"/{scope}/" in n + "/" for n in names), scope
    with open(os.path.join(_BENCH, "metrics",
                           "roi_align_roofline.fpn_train.json")) as f:
        pattern = json.load(f)["args"]["pattern"]
    for lv in (2, 3, 4, 5):
        kernels = {n.split("/")[-2] for n in names
                   if f"/roi_head/" in n and f"/roi_align/p{lv}/" in n
                   and n.endswith("/pallas_call")}
        assert len(kernels) == 2, (lv, kernels)     # forward and backward
        for k in kernels:
            assert re.search(pattern, f"%{k}.1 = bf16[8] custom-call(...), "
                             'custom_call_target="tpu_custom_call"')


# ------------------------------------------------ pool_levels, sorted by level
# A tiny pyramid (B, H, W, C) a level at strides 4..32 under a 96x128
# image, and 21 rois an image whose sides put some on every level.
_STRIDES = (4, 8, 16, 32)
_POOL_SHAPES = ((2, 24, 32, 128), (2, 12, 16, 128), (2, 6, 8, 128),
                (2, 3, 4, 128))
_POOL_R = 21


def _pool_inputs(seed=5):
    rng = np.random.RandomState(seed)
    pyramid = tuple(jnp.asarray(rng.randn(*s).astype(np.float32))
                    for s in _POOL_SHAPES)
    side = rng.choice([20.0, 60.0, 150.0, 300.0, 500.0, 1000.0],
                      size=(2, _POOL_R))
    x1 = rng.rand(2, _POOL_R) * 100
    y1 = rng.rand(2, _POOL_R) * 70
    rois = jnp.asarray(np.stack(
        [x1, y1, x1 + side - 1, y1 + side * 0.8 - 1], -1).astype(np.float32))
    cot = jnp.asarray(rng.randn(2, _POOL_R, 7, 7, 128).astype(np.float32))
    assert set(np.asarray(program_fpn.roi_levels(rois)).ravel()) == {2, 3, 4, 5}
    return pyramid, rois, cot


def _pool_levels_as_pr28_left_it(pyramid, rois, pooled_size, strides,
                                 sample_ratio):
    """The pool before the sort: every level's call gets the rois in the
    sampler's order, another level's as zero boxes, and no span."""
    levels = program_fpn.roi_levels(rois)
    pooled = None
    for li, stride in enumerate(strides):
        own = levels == li + 2
        feats = program_fpn.extract_roi_features_batched(
            pyramid[li], jnp.where(own[..., None], rois, 0.0), "roi_align",
            pooled_size, 1.0 / stride, sample_ratio)
        contrib = jnp.where(own[..., None, None, None], feats, 0.0)
        pooled = contrib if pooled is None else pooled + contrib
    return pooled


@pytest.fixture
def interpreted_kernels(monkeypatch):
    """A TPU's choice of kernels, interpreted on the CPU: P2 and P3 count
    as over the VMEM budget (the streaming pair, in row blocks of 8 rows
    and roi blocks of 8 rois), P4 and P5 take the resident pair.  → the
    streaming calls' spans, as traced."""
    from mx_rcnn_tpu.ops.pallas import roi_align as resident_mod
    from mx_rcnn_tpu.ops.pallas import roi_align_stream as stream_mod
    from mx_rcnn_tpu.utils import platform

    monkeypatch.setattr(platform, "use_pallas", lambda: True)
    monkeypatch.setattr(resident_mod, "fits_vmem",
                        lambda h, w, c, pooled, esize: h < 12)
    monkeypatch.setattr(stream_mod, "_pick_hblk", lambda w, cblk, budget=0: 8)
    monkeypatch.setattr(stream_mod, "_pick_rblk",
                        lambda pooled, cblk, budget=0: 8)
    resident, stream = resident_mod.roi_align_pallas, stream_mod.roi_align_stream
    spans = []

    def streamed(feat, rois, pooled, scale, ratio, span=None):
        spans.append(span)
        return stream(feat, rois, pooled, scale, ratio, True, span)

    monkeypatch.setattr(stream_mod, "roi_align_stream", streamed)
    monkeypatch.setattr(
        resident_mod, "roi_align_pallas",
        lambda feat, rois, pooled, scale, ratio, valid_hw=None: resident(
            feat, rois, pooled, scale, ratio, True, valid_hw))
    return spans


def _pool_and_grad(pool, pyramid, rois, cot):
    def loss(pyr):
        out = pool(pyr, rois, (7, 7), _STRIDES, 2)
        return (out * cot).sum(), out

    (_, out), grads = jax.value_and_grad(loss, has_aux=True)(pyramid)
    return np.asarray(out), [np.asarray(g) for g in grads]


def _assert_same_pool(got, want):
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6, atol=1e-6)
    for lv, (g, w) in enumerate(zip(got[1], want[1]), 2):
        assert np.abs(w).max() > 0, lv          # every level pools something
        np.testing.assert_allclose(
            g, w, rtol=1e-5, atol=1e-5 * np.abs(w).max(), err_msg=f"P{lv}")


def test_pool_levels_sorted_is_the_old_pool_on_the_gather_path():
    """Same values in the sampler's order, same gradient of every map."""
    pyramid, rois, cot = _pool_inputs()
    _assert_same_pool(
        _pool_and_grad(program_fpn.pool_levels, pyramid, rois, cot),
        _pool_and_grad(_pool_levels_as_pr28_left_it, pyramid, rois, cot))


def test_pool_levels_sorted_is_the_old_pool_with_the_kernels(
        interpreted_kernels):
    pyramid, rois, cot = _pool_inputs()
    new = _pool_and_grad(program_fpn.pool_levels, pyramid, rois, cot)
    # P2 and P3 streamed with the level's own span, forward (the backward
    # reuses it); the spans tile each image's 21 rois in level order
    spans = [np.asarray(s) for s in interpreted_kernels]
    assert len(spans) == 2 and all(s.shape == (2, 2) for s in spans)
    assert (spans[0][:, 0] == 0).all()
    assert (spans[1][:, 0] == spans[0][:, 1]).all()
    del interpreted_kernels[:]
    old = _pool_and_grad(_pool_levels_as_pr28_left_it, pyramid, rois, cot)
    assert interpreted_kernels == [None, None]
    _assert_same_pool(new, old)


def test_pool_levels_never_reads_a_dead_steps_rows(
        interpreted_kernels, monkeypatch):
    """On the chip a roi block outside the span is never written: its rows
    hold what the buffer held.  NaN there, on every row outside the span,
    reaches neither the pooled rois nor the maps' gradients."""
    from mx_rcnn_tpu.ops.pallas import roi_align_stream as stream_mod

    pyramid, rois, cot = _pool_inputs()
    sound = _pool_and_grad(program_fpn.pool_levels, pyramid, rois, cot)
    streamed = stream_mod.roi_align_stream
    poisoned = []

    def poison(feat, rois, pooled, scale, ratio, span=None):
        out = streamed(feat, rois, pooled, scale, ratio, span)
        r = jnp.arange(out.shape[1])[None]
        own = (r >= span[:, :1]) & (r < span[:, :1] + span[:, 1:])
        poisoned.append(int((~own).sum()))
        return jnp.where(own[..., None, None, None], out, jnp.nan)

    monkeypatch.setattr(stream_mod, "roi_align_stream", poison)
    got = _pool_and_grad(program_fpn.pool_levels, pyramid, rois, cot)
    assert len(poisoned) == 2 and min(poisoned) > 0
    assert np.isfinite(got[0]).all()
    np.testing.assert_array_equal(got[0], sound[0])
    for g, w in zip(got[1], sound[1]):
        np.testing.assert_array_equal(g, w)


def test_pool_levels_way_back_is_a_gather(monkeypatch):
    """Lowered for the TPU (kernels as custom calls, nothing compiled):
    the pool and its gradient hold no scatter — the sort's way back
    transposes to a gather by the inverse order."""
    monkeypatch.setenv("MX_RCNN_TPU_PALLAS", "1")
    pyramid, rois, cot = _pool_inputs()

    def pooled_and_grads(pyr, rois):
        return jax.value_and_grad(lambda p: (program_fpn.pool_levels(
            p, rois, (7, 7), _STRIDES, 2) * cot).sum())(pyr)

    text = jax.jit(pooled_and_grads).trace(pyramid, rois).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 8
    assert "gather" in text and "scatter" not in text


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_live_step_counter_against_a_count_by_hand(interpreted_kernels, seed):
    """``roi_steps_live_p<l>``: the (roi block, image) pairs that hold a
    roi of the level once the image's rois are sorted by level, for the
    levels that stream (P2, P3 here), against the same count in numpy; of
    ``roi_steps_p<l>`` = 3 blocks of 8 x 2 images walked."""
    pyramid, rois, _cot = _pool_inputs(seed)
    got = {k: int(v) for k, v in program_fpn.roi_stream_steps(
        pyramid, rois, (7, 7)).items()}
    levels = np.asarray(program_fpn.roi_levels(rois))
    want = {}
    for lv in (2, 3):
        live = 0
        for row in levels:
            at = np.nonzero(np.sort(row, kind="stable") == lv)[0]
            live += len(set(at // 8))
        want[f"roi_steps_live_p{lv}"] = live
        want[f"roi_steps_p{lv}"] = 6
    assert got == want
    assert 0 < got["roi_steps_live_p2"] + got["roi_steps_live_p3"] < 12


def test_live_step_counter_is_empty_where_nothing_streams():
    pyramid, rois, _cot = _pool_inputs()
    assert program_fpn.roi_stream_steps(pyramid, rois, (7, 7)) == {}
