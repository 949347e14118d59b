"""``"graph": "vgg"``: Faster R-CNN on VGG-16 — the training forward in
plain ``jax.numpy``, float32, nothing of the program.

Simonyan & Zisserman's configuration D (arXiv:1409.1556) under Ren et
al.'s two stages (arXiv:1506.01497 §3), as the upstream project builds it
(``rcnn/symbol/symbol_vgg.py::get_vgg_train``, its default ``--network
vgg``) and this project publishes it (``generate_config("vgg",
"PascalVOC")``):

- **trunk**: thirteen 3×3 convolutions with biases and ReLU, 64-64 /
  128-128 / 256×3 / 512×3 / 512×3, a 2×2 max pool after each of the first
  four blocks → one map at stride 16, 512 channels; ``conv1_*`` and
  ``conv2_*`` are fixed (no gradient is taken below ``conv3_1``).
- **RPN**: 3×3 conv 512 → 512, ReLU, 1×1 objectness and box outputs for 9
  anchors a cell (scales 8, 16, 32 × ratios 0.5, 1, 2).
- **second stage**: ``ROIPooling`` 7×7 at 1/16 (max over quantised bins:
  :func:`roi_max_pool` below, written here from MXNet's
  ``src/operator/roi_pooling.cc`` with whole-number bin edges; the frozen
  ``reference/ops/roi_align.py::roi_pool`` is the program's old float32
  formulation, which takes one cell more at a sixth of the far edges, and
  is not used), ``fc6`` 25088 → 4096, ReLU,
  dropout 0.5, ``fc7`` 4096 → 4096, ReLU, dropout 0.5, ``cls_score`` and
  ``bbox_pred``.
- **losses**: softmax over the batch's rois and anchors, smooth-L1 with σ 3
  (RPN) and 1 (head), normalised by 256 and 128 an image.

Departures from ``get_vgg_train``, each the project's own: NHWC; images
padded into a shape bucket, with ``im_info`` carrying the true extent (the
padding is zero in normalised space and the train graph does not re-mask
it); the proposal layer (12000 → NMS 0.7 → 2000), the anchor targets and
the roi sampling are inside the graph, not Python operators beside it;
eight images a step where the upstream trains one a GPU.

**Dropout masks.**  Inverted dropout, as MXNet's ``Dropout``: a kept unit
is doubled.  Image ``i`` draws the (rois, 4096) masks of ``fc6`` and
``fc7`` from ``fold_in(fold_in(k_i, "drop"), 0 | 1)`` where ``k_i`` is the
image's roi-sampling key: the step's ``sampling`` stream folded with the
image's ``sample_seeds`` entry (or split over the whole batch's rows), so
a row followed in a block of two draws the mask it draws in the batch of
eight.  ``harness/check_train.py`` asks the rest of this file's contract:
the program's leaf names (``backbone/conv<b>_<i>``, ``rpn``,
``top_head/fc6``, ``fc7``, ``rcnn/cls_score``, ``bbox_pred``) and the
sampling keys of ``reference/models/faster_rcnn.py``.
"""

# No ``from __future__ import annotations`` here: ``build_model`` executes
# this file without entering it in ``sys.modules``, and a flax module's
# dataclass looks string annotations up there.
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from reference.config import Config
from reference.models.heads import RCNNHead
from reference.models.layers import conv, normalize_images
from reference.models.rpn import RPNHead
from reference.ops.anchors import shifted_anchors
from reference.ops.losses import (
    accuracy,
    softmax_cross_entropy,
    weighted_smooth_l1,
)
from reference.ops.proposal import propose
from reference.ops.targets import assign_anchor, sample_rois

#: (convolutions, channels) of the five blocks of configuration D
BLOCKS = ((2, 64), (2, 128), (3, 256), (3, 512), (3, 512))
DROPOUT_RATE = 0.5
#: folds the head's dropout stream out of an image's roi-sampling key
DROP_STREAM = 0x64726F70  # "drop"


class VGG16(nn.Module):
    """(B, H, W, 3) → (B, H/16, W/16, 512).  ``fixed_blocks`` leading
    blocks take no gradient."""

    fixed_blocks: int = 2

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        for b, (n, ch) in enumerate(BLOCKS, start=1):
            for i in range(1, n + 1):
                x = nn.relu(conv(ch, 3, 1, name=f"conv{b}_{i}", use_bias=True)(x))
            if b < len(BLOCKS):     # pool5 is the roi pooling
                x = nn.max_pool(x, (2, 2), strides=(2, 2))
            if b == self.fixed_blocks:
                x = jax.lax.stop_gradient(x)
        return x


def roi_max_pool(fmap: jnp.ndarray, rois: jnp.ndarray, pooled, scale: float):
    """MXNet's ``ROIPooling``: (H, W, C) map × (R, 4) rois in image
    coordinates → (R, ph, pw, C).

    As ``roi_pooling.cc`` has it: a roi's corners go to whole cells by
    C's ``round`` (a half goes away from zero); it covers ``end − start +
    1`` cells, at least one; bin ``p`` of ``n`` starts at ``start + floor(p
    · extent / n)`` and ends before ``start + ceil((p + 1) · extent / n)``,
    both clipped to the map; the answer is the maximum over the bin's
    cells, 0 for a bin that holds none.  The edges are taken in whole
    numbers, so they are the quotients' true floor and ceiling.  One roi
    at a time, every bin a masked maximum over the whole map (the mask
    stands for the kernel's slice, whose extent a traced program cannot
    take from data).  The gradient goes to a bin's largest cell (shared
    between cells that tie, where the kernel takes the first: ties are
    cells at a ReLU's zero, through which none flows)."""
    hf, wf, _ = fmap.shape
    ph, pw = pooled

    def cells_of_bins(start, end, nbins, size):
        extent = jnp.maximum(end - start + 1, 1)
        p = jnp.arange(nbins)
        first = jnp.clip(start + (p * extent) // nbins, 0, size)
        last = jnp.clip(
            start + ((p + 1) * extent + nbins - 1) // nbins, 0, size)
        cell = jnp.arange(size)
        return (cell >= first[:, None]) & (cell < last[:, None])

    @jax.checkpoint   # or the backward pass keeps every roi's masked map
    def one_roi(roi):
        v = roi * scale
        x1, y1, x2, y2 = (
            jnp.sign(v) * jnp.floor(jnp.abs(v) + 0.5)).astype(jnp.int32)
        rows = cells_of_bins(y1, y2, ph, hf)             # (ph, H)
        cols = cells_of_bins(x1, x2, pw, wf)             # (pw, W)
        inside = rows[:, None, :, None] & cols[None, :, None, :]
        best = jnp.where(inside[..., None], fmap, -jnp.inf).max(axis=(2, 3))
        return jnp.where(inside.any(axis=(2, 3))[..., None], best, 0.0)

    return jax.lax.map(one_roi, rois)


def dropout(x: jnp.ndarray, key) -> jnp.ndarray:
    keep = jax.random.bernoulli(key, 1.0 - DROPOUT_RATE, x.shape)
    return jnp.where(keep, x / (1.0 - DROPOUT_RATE), 0.0)


class FCHead(nn.Module):
    """One image's pooled rois (R, 7, 7, 512) → (R, 4096)."""

    @nn.compact
    def __call__(self, pooled: jnp.ndarray, key) -> jnp.ndarray:
        x = pooled.reshape(pooled.shape[0], -1)
        x = dropout(nn.relu(nn.Dense(4096, name="fc6")(x)),
                    jax.random.fold_in(key, 0))
        return dropout(nn.relu(nn.Dense(4096, name="fc7")(x)),
                       jax.random.fold_in(key, 1))


def fixed_blocks(fixed_params) -> int:
    """How many leading blocks ``FIXED_PARAMS`` names (``conv1``,
    ``conv2``: a contiguous prefix, or no gradient may be stopped)."""
    n = 0
    while f"conv{n + 1}" in fixed_params:
        n += 1
    if {f"conv{i + 1}" for i in range(n)} != {
            p for p in fixed_params if p.startswith("conv")}:
        raise ValueError(f"{fixed_params} is no leading run of conv blocks")
    return n


class VGGFasterRCNN(nn.Module):
    cfg: Config

    def setup(self):
        net = self.cfg.network
        if net.COMPUTE_DTYPE != "float32" or net.ROI_MODE != "roi_pool":
            raise ValueError(
                "the VGG reference computes in float32 and max-pools its rois")
        self.backbone = VGG16(fixed_blocks=fixed_blocks(net.FIXED_PARAMS))
        self.rpn = RPNHead(num_anchors=net.NUM_ANCHORS, channels=512)
        self.top_head = FCHead()
        self.rcnn = RCNNHead(num_classes=self.cfg.dataset.NUM_CLASSES)

    def __call__(
        self,
        images: jnp.ndarray,
        im_info: jnp.ndarray,
        gt_boxes: jnp.ndarray,
        gt_valid: jnp.ndarray,
        train: bool = True,
        sample_seeds: Optional[jnp.ndarray] = None,
        full_batch: Optional[int] = None,
        row_offset: int = 0,
    ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
        if not train:
            raise NotImplementedError("the VGG reference trains only")
        cfg = self.cfg
        net, t = cfg.network, cfg.TRAIN
        b = images.shape[0]
        feat = self.backbone(normalize_images(images, im_info, cfg))
        rpn_logits, rpn_deltas = self.rpn(feat)          # (B, N, 2 / 4)
        anchors = jnp.asarray(shifted_anchors(
            feat.shape[1], feat.shape[2], net.RPN_FEAT_STRIDE,
            ratios=net.ANCHOR_RATIOS, scales=net.ANCHOR_SCALES))

        # --- the sampling keys the whole-batch step draws for these rows
        key = self.make_rng("sampling")
        if sample_seeds is not None:
            keys = jax.vmap(
                lambda s: jax.random.split(jax.random.fold_in(key, s), 2)
            )(sample_seeds)
        elif full_batch is not None:
            keys = jax.random.split(key, (full_batch, 2))[
                row_offset:row_offset + b]
        else:
            keys = jax.random.split(key, (b, 2))

        atgt = jax.vmap(
            lambda gtb, gtv, info, k: assign_anchor(
                anchors, gtb[:, :4], gtv, info, k, cfg)
        )(gt_boxes, gt_valid, im_info, keys[:, 0])

        # --- proposals (no gradient flows through them)
        fg_scores = jax.nn.softmax(rpn_logits, axis=-1)[..., 1]
        props = jax.vmap(
            lambda s, d, info: propose(
                s, d, anchors, info, t.RPN_PRE_NMS_TOP_N,
                t.RPN_POST_NMS_TOP_N, t.RPN_NMS_THRESH, t.RPN_MIN_SIZE)
        )(jax.lax.stop_gradient(fg_scores),
          jax.lax.stop_gradient(rpn_deltas), im_info)

        samples = jax.vmap(
            lambda r, rv, gtb, gtv, k: sample_rois(r, rv, gtb, gtv, k, cfg)
        )(props.rois, props.valid, gt_boxes, gt_valid, keys[:, 1])

        # --- second stage, one image after the other: max-pool its rois
        # from its map, then fc6 / fc7 under the image's own masks
        def one_image(args):
            fmap, rois, k = args
            pooled = roi_max_pool(fmap, rois, tuple(net.POOLED_SIZE),
                                  1.0 / net.RCNN_FEAT_STRIDE)
            return self.top_head(pooled, jax.random.fold_in(k, DROP_STREAM))

        trunk = jnp.concatenate([
            one_image((feat[i], samples.rois[i], keys[i, 1]))
            for i in range(b)])
        cls_logits, box_out = self.rcnn(trunk)

        labels = samples.labels.reshape(-1)
        box_targets = samples.bbox_targets.reshape(box_out.shape)
        box_weights = samples.bbox_weights.reshape(box_out.shape)
        rpn_norm = float(t.RPN_BATCH_SIZE * b)
        rcnn_norm = float(t.BATCH_ROIS * b)
        rpn_cls_loss = softmax_cross_entropy(
            rpn_logits.reshape(-1, 2), atgt.labels.reshape(-1), -1, rpn_norm)
        rpn_box_loss = weighted_smooth_l1(
            rpn_deltas.reshape(-1, 4), atgt.bbox_targets.reshape(-1, 4),
            atgt.bbox_weights.reshape(-1, 4), sigma=3.0, norm=rpn_norm)
        rcnn_cls_loss = softmax_cross_entropy(
            cls_logits, labels, -1, rcnn_norm)
        rcnn_box_loss = weighted_smooth_l1(
            box_out, box_targets, box_weights, sigma=1.0, norm=rcnn_norm)
        total = rpn_cls_loss + rpn_box_loss + rcnn_cls_loss + rcnn_box_loss

        aux = {
            "RPNAcc": accuracy(
                rpn_logits.reshape(-1, 2), atgt.labels.reshape(-1)),
            "RPNLogLoss": rpn_cls_loss,
            "RPNL1Loss": rpn_box_loss,
            "RCNNAcc": accuracy(cls_logits, labels),
            "RCNNLogLoss": rcnn_cls_loss,
            "RCNNL1Loss": rcnn_box_loss,
            "num_fg_rois": (labels > 0).sum(),
            "num_valid_props": props.valid.sum(),
            "num_fg_anchors": (atgt.labels == 1).sum(),
        }
        return total, aux


def build(cfg):
    if cfg.network.name != "vgg" or cfg.network.USE_FPN:
        raise ValueError("graph vgg on a configuration of another network")
    return VGGFasterRCNN(cfg)
