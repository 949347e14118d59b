"""The main path's Pallas kernels, compiled by the TPU's own compiler at
real widths — for a v5e that is DESCRIBED, not attached.

Interpret mode (tests/test_pallas_roi_align.py, tests/test_pallas_roi_pool.py,
tests/test_pallas_nms.py) checks what the kernels compute; it cannot see what Mosaic refuses: a
block over the scoped-VMEM limit, an unaligned slice, a kernel that does
not fit.  These compiles can, in about two seconds each and with no chip
time (ISSUE 21: the f32 resident backward had passed every interpret
test and was refused at the flagship C4 map and at FPN P3).

Nothing runs, so nothing here says a result or a time.  All cases live in
THIS file and the topology is described inside a fixture: one process at
a time may load the TPU's library, xdist gives a file to one worker, and
a call at import would make the workers collect different tests.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from mx_rcnn_tpu.ops.nms import batched_class_nms
from mx_rcnn_tpu.ops.pallas.nms import nms_mask_sorted_pallas
from mx_rcnn_tpu.ops.pallas.roi_align import fits_vmem, roi_align_pallas
from mx_rcnn_tpu.ops.pallas.roi_align_stream import roi_align_stream
from mx_rcnn_tpu.ops.pallas.roi_pool import roi_pool_pallas


@pytest.fixture(scope="module")
def one_chip():
    """Sharding on one device of a described v5e:2x2; skips where the
    compiler cannot describe one.  The persistent cache is off around
    these compiles: an entry written for a described chip cannot be read
    back without one, and the next run would warn and recompile."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs to /tmp
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compiled_text(fn, one_chip, *shapes) -> str:
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes
    ]
    return jax.jit(fn).lower(*args).compile().as_text()


# (kernel, feature map, rois per image, pooled, spatial scale)
_ROI_ALIGN_CASES = [
    # flagship C4, bench batch, landscape — f32 here was refused (17.5 MiB)
    pytest.param(roi_align_pallas, (8, 38, 64, 1024), 128, (14, 14), 1 / 16,
                 id="resident-c4-landscape-14"),
    pytest.param(roi_align_pallas, (2, 64, 38, 1024), 128, (14, 14), 1 / 16,
                 id="resident-c4-portrait-14"),
    # FPN P3 under the 7x7 box head — f32 here was refused (16.02 MiB)
    pytest.param(roi_align_pallas, (2, 76, 128, 256), 512, (7, 7), 1 / 8,
                 id="resident-p3-7"),
    # FPN P2: over the resident budget at any dtype
    pytest.param(roi_align_stream, (2, 152, 256, 256), 512, (7, 7), 1 / 4,
                 id="stream-p2-7"),
    pytest.param(roi_align_stream, (2, 152, 256, 256), 512, (14, 14), 1 / 4,
                 id="stream-p2-14"),
    # the pyramid's train step (cell fpn_train_b8): 8 images, 128 sampled
    # rois, 14x14 - P2 and P3 are over the resident budget at both dtypes
    pytest.param(roi_align_stream, (8, 152, 256, 256), 128, (14, 14), 1 / 4,
                 id="stream-p2-train"),
    pytest.param(roi_align_stream, (8, 76, 128, 256), 128, (14, 14), 1 / 8,
                 id="stream-p3-train"),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("kernel,feat,n_rois,pooled,scale", _ROI_ALIGN_CASES)
def test_roi_align_fwd_bwd_compiles(one_chip, kernel, feat, n_rois, pooled,
                                    scale, dtype):
    """Forward AND backward (the loss keeps the forward alive) of the
    kernel the dispatcher would pick for this map."""
    resident = kernel is roi_align_pallas
    assert fits_vmem(*feat[1:], pooled, jnp.dtype(dtype).itemsize) == resident

    def fwd_bwd(f, rois):
        def loss(x):
            out = kernel(x, rois, pooled, scale, 2)
            return (out.astype(jnp.float32) ** 2).sum()

        return jax.value_and_grad(loss)(f)

    text = _compiled_text(
        fwd_bwd, one_chip,
        (feat, dtype), ((feat[0], n_rois, 4), jnp.float32),
    )
    assert text.count("tpu_custom_call") >= 2  # fwd + bwd kernels


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("feat,scale", [
    pytest.param((8, 152, 256, 256), 1 / 4, id="stream-p2-train"),
    pytest.param((8, 76, 128, 256), 1 / 8, id="stream-p3-train"),
])
def test_roi_align_stream_with_a_span_compiles(one_chip, feat, scale, dtype):
    """The streaming pair as ``pool_levels`` calls it in the pyramid's
    train step: the level's ``[start, count]`` an image as a second
    scalar-prefetch operand, the roi loop's bounds and the dead steps'
    block indices computed from it (dynamic ``fori_loop`` bounds and an
    index map that reads SMEM are what Mosaic could refuse)."""
    def fwd_bwd(f, rois, span):
        def loss(x):
            out = roi_align_stream(x, rois, (14, 14), scale, 2, False, span)
            return (out.astype(jnp.float32) ** 2).sum()

        return jax.value_and_grad(loss)(f)

    text = _compiled_text(
        fwd_bwd, one_chip, (feat, dtype), ((feat[0], 128, 4), jnp.float32),
        ((feat[0], 2), jnp.int32),
    )
    assert "pallas_roi_features_stream_fwd" in text
    assert "pallas_roi_features_stream_bwd" in text
    assert text.count("tpu_custom_call") == 2


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_roi_align_serve_valid_hw_compiles(one_chip, dtype):
    """The serve graph's second stage as ``test_forward`` hands it over:
    the C4 map padded to the ladder's extent, 300 rois an image, forward
    only, with the per-image valid extents as the kernel's second
    scalar-prefetch operand."""
    feat, pooled = (8, 64, 64, 1024), (14, 14)
    assert fits_vmem(*feat[1:], pooled, jnp.dtype(dtype).itemsize)
    text = _compiled_text(
        lambda f, rois, valid_hw: roi_align_pallas(
            f, rois, pooled, 1 / 16, 2, valid_hw=valid_hw
        ),
        one_chip, (feat, dtype), ((8, 300, 4), jnp.float32),
        ((8, 2), jnp.float32),
    )
    assert "pallas_roi_features_fwd" in text
    assert text.count("tpu_custom_call") == 1


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_roi_pool_fwd_bwd_compiles(one_chip, dtype):
    """The ROI max pooling pair at the VGG train step's shape (cell
    ``vgg_train_b8``): conv5_3 of eight 608x1024 images, 128 rois each.
    Loops with the bins' bounds, dynamic row indices into the resident
    map and an int32 output are what Mosaic could refuse."""
    def fwd_bwd(f, rois):
        def loss(x):
            out = roi_pool_pallas(x, rois, (7, 7), 1 / 16)
            return (out.astype(jnp.float32) ** 2).sum()

        return jax.value_and_grad(loss)(f)

    text = _compiled_text(
        fwd_bwd, one_chip,
        ((8, 38, 64, 512), dtype), ((8, 128, 4), jnp.float32),
    )
    assert "pallas_roi_pool_fwd" in text and "pallas_roi_pool_bwd" in text
    assert text.count("tpu_custom_call") == 2


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_roi_pool_valid_hw_compiles(one_chip, dtype):
    """The forward as ``test_forward`` hands it over: the map padded to the
    ladder's extent, 300 rois an image (a count the roi block does not
    divide), every image's valid extent in the edges."""
    text = _compiled_text(
        lambda f, rois, valid_hw: roi_pool_pallas(
            f, rois, (7, 7), 1 / 16, valid_hw=valid_hw
        ),
        one_chip, ((8, 64, 64, 512), dtype), ((8, 300, 4), jnp.float32),
        ((8, 2), jnp.float32),
    )
    assert "pallas_roi_pool_fwd" in text
    assert text.count("tpu_custom_call") == 1


def test_vgg_step_holds_the_roi_pool_pair_and_no_sweep(one_chip, monkeypatch):
    """The bf16 batch-8 VGG-16 train step (cell ``vgg_train_b8``) compiled
    whole: the pooling is the two kernels, under the ``roi_pool`` scope
    both ways, and none of the four ``while`` loops the jnp sweep was is
    left under it.  ``use_pallas`` asks the backend, which is the CPU
    here, so the test steers it."""
    from mx_rcnn_tpu.core.train import (
        create_train_state, make_lr_schedule, make_optimizer, make_train_step,
    )
    from mx_rcnn_tpu.models import build_model
    from mx_rcnn_tpu.tools import train_end2end as cli

    monkeypatch.setenv("MX_RCNN_TPU_PALLAS", "1")
    cfg = cli.config_from_args(cli.parse_args([
        "--network", "vgg", "--dataset", "PascalVOC", "--synthetic", "64",
        "--batch_images", "8", "--compute_dtype", "bfloat16",
        "--prefix", "/nowhere"]))
    model = build_model(cfg)
    b, (h, w), g = 8, cfg.SHAPE_BUCKETS[0], cfg.dataset.MAX_GT_BOXES
    batch = {
        "images": jnp.zeros((b, h, w, 3)),
        "im_info": jnp.tile(jnp.array([[h, w, 1.0]]), (b, 1)),
        "gt_boxes": jnp.zeros((b, g, 5)),
        "gt_valid": jnp.zeros((b, g), bool),
    }
    params = jax.eval_shape(lambda: model.init(
        {"params": jax.random.key(0), "sampling": jax.random.key(1)},
        train=True, **batch)["params"])
    tx = make_optimizer(cfg, make_lr_schedule(cfg, 10))
    state = jax.eval_shape(lambda p: create_train_state(p, tx), params)
    args = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        (state, batch, jax.eval_shape(lambda: jax.random.key(2))))
    text = make_train_step(model, tx).lower(*args).compile().as_text()
    for kernel in ("pallas_roi_pool_fwd", "pallas_roi_pool_bwd"):
        assert re.search(
            rf"%{kernel}[.\d]* = .*op_name=\"[^\"]*/roi_pool/", text), kernel
    # the sweep was four: ``%while.66 = (s32[], bf16[8,38,64,512], ...)
    # while(...), ... op_name=".../roi_pool/while"``
    loops = [line[:80] for line in text.splitlines()
             if " while(" in line and "/roi_pool/" in line]
    assert not loops, loops


def test_pyramid_top_k_compiles_at_batch_one(one_chip):
    """The finest level's 152x256x3 anchor scores at per-chip batch 1: as
    ``[1, 116736]`` the chip's compiler aborts the PROCESS in its TopK
    emitter (ROADMAP R1; not tried here for that reason), so
    ``models/layers.py::per_image`` hands it the one image without the batch
    axis.  Batch 2 goes through ``vmap`` as before."""
    from mx_rcnn_tpu.models.layers import per_image

    for batch in (1, 2):
        text = _compiled_text(
            lambda s: per_image(lambda row: jax.lax.top_k(row, 2400), s),
            one_chip, ((batch, 116736), jnp.float32),
        )
        assert f"f32[{batch},2400]" in text  # it compiled, whole


_HLO_ARRAY = re.compile(r"\b[a-z]+\d*\[([\d,]+)\]\{([\d,]+):T\(8,128\)")


def _lane_padded(text: str, at_least: int):
    """Arrays of ``at_least`` elements or more in ``T(8,128)`` tiling
    whose MINOR dimension (the first of the layout's minor-to-major
    list) is 1 or 4: the TPU pads that dimension to 128 lanes."""
    found = set()
    for dims, layout in _HLO_ARRAY.findall(text):
        dims = [int(d) for d in dims.split(",")]
        minor = dims[int(layout.split(",")[0])]
        if minor in (1, 4) and np.prod(dims) >= at_least:
            found.add((tuple(dims), layout))
    return found


@pytest.mark.parametrize("n", [
    pytest.param(38 * 64 * 9, id="c4-21888"),
    # the pyramid's five levels: a minute to compile here
    pytest.param(155520, id="fpn-155520", marks=pytest.mark.slow),
])
def test_anchor_targets_are_dense_planes(one_chip, n):
    """``vmap(assign_anchor)`` as the train cells call it (batch 8, 100
    gt slots, N anchors): nothing of length N is sorted, no array of N
    elements keeps a dimension of 1 or 4 on the lanes, and the program
    needs next to no temporaries.  Before PR 31 it held, at C4's N, two
    sorts of ``[8,21888]``, a row gather into ``f32[175104,4]`` padded to
    128 lanes, four ``f32[8,21888,1]`` slices of it and 0.36 GB of
    temporaries (3.19 GB at the pyramid's N)."""
    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.ops.targets import assign_anchor

    cfg = generate_config("resnet", "PascalVOC")
    batch, g = 8, 100

    def targets(anchors, gt, gt_valid, im_info, keys):
        return jax.vmap(
            lambda gtb, gtv, info, k: assign_anchor(
                anchors, gtb[:, :4], gtv, info, k, cfg)
        )(gt, gt_valid, im_info, keys)

    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in (
            ((n, 4), jnp.float32), ((batch, g, 5), jnp.float32),
            ((batch, g), jnp.bool_), ((batch, 3), jnp.float32),
            ((batch, 2), jnp.uint32),
        )
    ]
    compiled = jax.jit(targets).lower(*args).compile()
    text = compiled.as_text()
    sorted_lengths = [
        int(np.prod([int(d) for d in dims.split(",")]))
        for dims in re.findall(r"= \(?[a-z]+\d*\[([\d,]+)\][^=]* sort\(", text)
    ]
    assert all(length < n for length in sorted_lengths), sorted_lengths
    assert text.count('custom_call_target="TopK"') == 2
    assert " gather(" not in text
    assert not _lane_padded(text, at_least=n)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


@pytest.mark.parametrize("n,max_keep", [(12000, 2000), (6000, 300)],
                         ids=["train-12000-2000", "test-6000-300"])
def test_sorted_nms_compiles(one_chip, n, max_keep):
    """The proposal path's NMS at the train and test top-N."""
    text = _compiled_text(
        lambda boxes, valid: nms_mask_sorted_pallas(
            boxes, valid, 0.7, max_keep=max_keep
        ),
        one_chip, ((n, 4), jnp.float32), ((n,), jnp.bool_),
    )
    assert "tpu_custom_call" in text


def test_vmapped_class_nms_compiles(one_chip, monkeypatch):
    """The device postprocess's per-class NMS: the kernel under two vmaps
    (images x classes), reached through ops.nms as the serve graph does.
    ``use_pallas`` asks the backend, which is the CPU here, so the test
    steers it."""
    monkeypatch.setenv("MX_RCNN_TPU_PALLAS", "1")
    text = _compiled_text(
        jax.vmap(lambda b, s: batched_class_nms(b, s, 0.3, 100)),
        one_chip, ((4, 20, 300, 4), jnp.float32), ((4, 20, 300), jnp.float32),
    )
    assert "tpu_custom_call" in text
