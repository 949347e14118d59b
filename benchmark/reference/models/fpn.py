"""``"graph": "fpn"``: Faster R-CNN on a ResNet feature pyramid — the
training forward in plain ``jax.numpy``, float32, nothing of the program.

Lin et al., *Feature Pyramid Networks for Object Detection*
(arXiv:1612.03144) §3-§5 on He et al.'s ResNet (arXiv:1512.03385) and Ren
et al.'s two stages (arXiv:1506.01497), as this project publishes it
(``generate_config("resnet_fpn", "coco")``):

- **pyramid** (§3): a 1×1 lateral conv on C2..C5, the coarser map
  upsampled by nearest neighbour and added, a 3×3 conv on each sum →
  P2..P5 at 256 channels; P6 is a stride-2 subsampling of P5 (§4.1,
  footnote) and feeds the RPN only.
- **RPN** (§4.1): one head shared over P2..P6, three aspect ratios a cell
  at ONE scale a level (``FPN_ANCHOR_SCALES`` (8,) on strides 4..64 =
  areas 32²..512², as the paper).
- **roi → level** (§4.2, eq. 1): ``k = ⌊4 + log2(√(wh) / 224)⌋`` held to
  [2, 5]; **each roi is pooled once, from the map of its own level**.
- **head** (§4.2): two 1024-wide fully connected layers, then the class
  and box outputs.

Departures from the paper, each the project's own and kept as published:
the pool is 14×14 where the paper pools 7×7 (``POOLED_SIZE``; ``fc1`` is
14·14·256 × 1024); proposals are the best ``RPN_PRE_NMS_TOP_N // 5`` (at
least 256) of every level, then ONE greedy NMS over their union, cut to
``RPN_POST_NMS_TOP_N`` (the paper does not say; common practice runs the
NMS a level); the trunk is the post-activation ResNet of
``reference/models/resnet.py`` with every BN frozen; training samples
128 rois an image, 2000 proposals (``TRAIN``).

What the check asks of this file (``harness/check_train.py``): the
parameter tree carries the program's leaf names (``backbone``, ``neck/
lateral2..5``, ``neck/post2..5``, ``rpn``, ``top_head/fc1``, ``fc2``,
``rcnn/cls_score``, ``rcnn/bbox_pred``); a step followed in blocks of rows
draws the sampling keys the whole-batch step draws (``sample_seeds``, or
``full_batch`` / ``row_offset``: the contract of ``faster_rcnn.py``); and
the per-level selection is a full descending sort and a slice, which is
the plainest statement and compiles at any batch.
"""

# No ``from __future__ import annotations`` here: ``build_model`` executes
# this file without entering it in ``sys.modules``, and a flax module's
# dataclass looks string annotations up there.
from typing import Any, Dict, List, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from reference.config import Config
from reference.models.heads import RCNNHead
from reference.models.layers import conv, normalize_images
from reference.models.resnet import (
    RESNET_BLOCK_ORDER,
    ResNetBackbone,
    frozen_prefix_len,
)
from reference.models.rpn import RPNHead
from reference.ops.anchors import shifted_anchors
from reference.ops.boxes import bbox_pred, clip_boxes
from reference.ops.losses import (
    accuracy,
    softmax_cross_entropy,
    weighted_smooth_l1,
)
from reference.ops.nms import nms
from reference.ops.proposal import _NEG_INF
from reference.ops.roi_align import _bilinear_one_roi
from reference.ops.targets import assign_anchor, sample_rois

#: the levels a roi is pooled from (P6 feeds the RPN only)
ROI_LEVELS = (2, 3, 4, 5)


def upsample_nearest(x: jnp.ndarray, h: int, w: int) -> jnp.ndarray:
    """(B, h0, w0, C) → (B, h, w, C): output cell i reads input cell
    ⌊(i + ½)·h0/h⌋ (twice the extent: every coarse cell covers 2×2)."""
    rows = jnp.floor((jnp.arange(h) + 0.5) * x.shape[1] / h).astype(jnp.int32)
    cols = jnp.floor((jnp.arange(w) + 0.5) * x.shape[2] / w).astype(jnp.int32)
    return x[:, rows][:, :, cols]


class FPNNeck(nn.Module):
    """(C2, C3, C4, C5) → [P2, P3, P4, P5]."""

    channels: int = 256

    @nn.compact
    def __call__(self, feats: Tuple[jnp.ndarray, ...]) -> List[jnp.ndarray]:
        laterals = [
            conv(self.channels, 1, 1, name=f"lateral{lv}", use_bias=True)(c)
            for lv, c in zip(ROI_LEVELS, feats)
        ]
        merged = [laterals[-1]]                       # top-down from C5
        for lateral in laterals[-2::-1]:
            up = upsample_nearest(merged[0], lateral.shape[1], lateral.shape[2])
            merged.insert(0, lateral + up)
        return [
            conv(self.channels, 3, 1, name=f"post{lv}", use_bias=True)(m)
            for lv, m in zip(ROI_LEVELS, merged)
        ]


class FPNTopHead(nn.Module):
    """Pooled rois (R, ph, pw, C) → (R, width): fc1, relu, fc2, relu."""

    width: int = 1024

    @nn.compact
    def __call__(self, pooled: jnp.ndarray) -> jnp.ndarray:
        x = pooled.reshape(pooled.shape[0], -1)
        x = nn.relu(nn.Dense(self.width, name="fc1")(x))
        return nn.relu(nn.Dense(self.width, name="fc2")(x))


def roi_levels(rois: jnp.ndarray) -> jnp.ndarray:
    """(…, 4) boxes → the level that pools each (eq. 1), in [2, 5]."""
    w = jnp.maximum(rois[..., 2] - rois[..., 0] + 1.0, 1.0)
    h = jnp.maximum(rois[..., 3] - rois[..., 1] + 1.0, 1.0)
    k = jnp.floor(4 + jnp.log2(jnp.sqrt(w * h) / 224.0))
    return jnp.clip(k, ROI_LEVELS[0], ROI_LEVELS[-1]).astype(jnp.int32)


def best_k(scores: jnp.ndarray, k: int):
    """The ``k`` highest scores and where they were: a full descending
    sort (stable: of equal scores the earlier comes first) and a slice."""
    order = jnp.argsort(-scores, stable=True)[:k]
    return scores[order], order


def propose_multilevel(fg_scores, deltas, anchors, bounds, im_info,
                       per_level: int, post_nms: int, nms_thresh: float,
                       min_size: float):
    """One image: decode, clip, drop boxes under ``min_size``; the best
    ``per_level`` of every level; one sequential NMS over their union →
    (boxes (post_nms, 4), valid (post_nms,))."""
    h, w, scale = im_info[0], im_info[1], im_info[2]
    boxes = clip_boxes(bbox_pred(anchors, deltas), (h, w))
    ms = min_size * scale
    big = ((boxes[:, 2] - boxes[:, 0] + 1.0 >= ms)
           & (boxes[:, 3] - boxes[:, 1] + 1.0 >= ms))
    scores = jnp.where(big, fg_scores, _NEG_INF)
    top_scores, top_boxes = [], []
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        s, idx = best_k(scores[lo:hi], min(per_level, hi - lo))
        top_scores.append(s)
        top_boxes.append(boxes[lo:hi][idx])
    scores = jnp.concatenate(top_scores)
    boxes = jnp.concatenate(top_boxes, axis=0)
    out_boxes, _scores, out_valid = nms(
        boxes, scores, nms_thresh, post_nms, scores > _NEG_INF / 2)
    return out_boxes, out_valid


def pool_own_level(pyramid, rois, levels, pooled, sample_ratio, strides):
    """(B, Hl, Wl, C) maps of P2..P5 × (B, R, 4) rois × (B, R) levels →
    (B, R, ph, pw, C).  One image after the other, one roi after the
    other, each through the gather ROIAlign on the ONE map eq. 1 gives it
    (a conditional on the level: the other three maps are not read)."""

    def one_image(args):
        maps, rois_i, levels_i = args
        branches = [
            (lambda roi, m=m, s=s: _bilinear_one_roi(
                m, roi, pooled, sample_ratio, 1.0 / s))
            for m, s in zip(maps, strides)
        ]
        return jax.lax.map(
            lambda rl: jax.lax.switch(rl[1] - ROI_LEVELS[0], branches, rl[0]),
            (rois_i, levels_i))

    return jax.lax.map(one_image, (tuple(pyramid), rois, levels))


class FPNFasterRCNN(nn.Module):
    cfg: Config

    def setup(self):
        net = self.cfg.network
        if net.COMPUTE_DTYPE != "float32" or net.FOLD_BN:
            raise ValueError("the pyramid reference computes in plain float32")
        if net.USE_MASK:
            raise NotImplementedError("the reference holds no mask branch")
        self.backbone = ResNetBackbone(
            depth=net.depth, return_pyramid=True,
            frozen_prefix=frozen_prefix_len(
                net.FIXED_PARAMS, RESNET_BLOCK_ORDER, requires=("bn",)),
        )
        self.neck = FPNNeck(channels=net.FPN_CHANNELS)
        self.rpn = RPNHead(
            num_anchors=len(net.ANCHOR_RATIOS) * len(net.FPN_ANCHOR_SCALES),
            channels=net.FPN_CHANNELS,
        )
        self.top_head = FPNTopHead()
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
            raise NotImplementedError("the pyramid reference trains only")
        cfg = self.cfg
        net, t = cfg.network, cfg.TRAIN
        b = images.shape[0]
        images = normalize_images(images, im_info, cfg)

        # --- pyramid: P2..P5, and P6 by stride-2 subsampling of P5
        ps = self.neck(self.backbone(images))
        pyramid = ps + [ps[-1][:, ::2, ::2]]

        # --- the shared RPN head on every level; one anchor table
        logits, deltas, anchors = [], [], []
        for p, stride in zip(pyramid, net.FPN_FEAT_STRIDES):
            lg, dl = self.rpn(p)                      # (B, Hl·Wl·A, 2 / 4)
            logits.append(lg)
            deltas.append(dl)
            anchors.append(shifted_anchors(
                p.shape[1], p.shape[2], stride,
                ratios=net.ANCHOR_RATIOS, scales=net.FPN_ANCHOR_SCALES))
        bounds = [int(v) for v in np.cumsum([0] + [len(a) for a in anchors])]
        rpn_logits = jnp.concatenate(logits, axis=1)
        rpn_deltas = jnp.concatenate(deltas, axis=1)
        anchors = jnp.asarray(np.concatenate(anchors, axis=0))

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
        per_level = max(t.RPN_PRE_NMS_TOP_N // len(pyramid), 256)
        prop_boxes, prop_valid = jax.vmap(
            lambda s, d, info: propose_multilevel(
                s, d, anchors, bounds, info, per_level,
                t.RPN_POST_NMS_TOP_N, t.RPN_NMS_THRESH, t.RPN_MIN_SIZE)
        )(jax.lax.stop_gradient(fg_scores),
          jax.lax.stop_gradient(rpn_deltas), im_info)

        samples = jax.vmap(
            lambda r, rv, gtb, gtv, k: sample_rois(r, rv, gtb, gtv, k, cfg)
        )(prop_boxes, prop_valid, gt_boxes, gt_valid, keys[:, 1])

        # --- second stage: every roi pooled once, from its own level
        levels = roi_levels(samples.rois)
        pooled = pool_own_level(
            pyramid[:len(ROI_LEVELS)], samples.rois, levels, net.POOLED_SIZE,
            net.ROI_SAMPLE_RATIO, net.FPN_FEAT_STRIDES)
        trunk = self.top_head(pooled.reshape((-1,) + pooled.shape[2:]))
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
            "num_valid_props": prop_valid.sum(),
            "num_fg_anchors": (atgt.labels == 1).sum(),
        }
        for lv in ROI_LEVELS:
            aux[f"num_rois_p{lv}"] = (levels == lv).sum()
        return total, aux


def build(cfg):
    if not cfg.network.USE_FPN:
        raise ValueError("graph fpn on a configuration without a pyramid")
    return FPNFasterRCNN(cfg)
