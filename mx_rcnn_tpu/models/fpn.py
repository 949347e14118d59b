"""Feature Pyramid Network Faster R-CNN (BASELINE config 4).

No reference twin — the MXNet reference has no FPN (SURVEY §7.2 step 7
calls this new design work).  Design follows Lin et al. CVPR'17 with
TPU-native shape discipline throughout:

- **Neck**: lateral 1×1 convs on C2..C5 + nearest top-down upsample-add +
  3×3 smoothing → P2..P5; P6 = stride-2 maxpool of P5 (RPN only).
- **Anchors**: one scale per level (FPN_ANCHOR_SCALES) × 3 ratios on
  strides FPN_FEAT_STRIDES; all levels concatenated into ONE static
  anchor table, so RPN target assignment (``assign_anchor``) is the
  unmodified single-level code on a bigger N.
- **Proposals**: per-level top-k (bounds work per level), then one NMS
  over the union — fixed shapes, Pallas NMS on TPU.
- **ROI level assignment**: k = ⌊k0 + log2(√(wh)/224)⌋ clamped to
  [2, 5].  Rather than gathering rois per level (dynamic shapes), ROI
  features are extracted from ALL four levels with the batched Pallas
  ROIAlign and blended with a one-hot level mask — 4× flops on a cheap
  op in exchange for a single fused static-shape graph.
- **Head**: 2-fc (1024) box head (the standard FPN-RCNN head; conv5 has
  no place once the pyramid exists).

Param tree: {backbone, neck, rpn, top_head, rcnn} — backbone includes
stage4 (C5 is part of the pyramid), so the torchvision importer maps
layer4 into the backbone here (``import_resnet(..., fpn=True)``).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.models.heads import MaskHead, RCNNHead
from mx_rcnn_tpu.models.layers import conv, per_image
from mx_rcnn_tpu.models.resnet import (
    RESNET_BLOCK_ORDER,
    ResNetBackbone,
    frozen_prefix_len,
)
from mx_rcnn_tpu.models.rpn import RPNHead
from mx_rcnn_tpu.ops.anchors import shifted_anchors
from mx_rcnn_tpu.ops.losses import (
    accuracy,
    one_hot_select,
    softmax_cross_entropy,
    weighted_smooth_l1,
)
from mx_rcnn_tpu.ops.nms import nms
from mx_rcnn_tpu.ops.boxes import bbox_pred, clip_boxes
from mx_rcnn_tpu.ops.proposal import anchor_grid_mask
from mx_rcnn_tpu.ops.roi_align import extract_roi_features_batched
from mx_rcnn_tpu.ops.targets import assign_anchor, bbox_denorm_vectors, sample_rois

_NEG_INF = -1e10


def _dtype_of(cfg: Config):
    return jnp.bfloat16 if cfg.network.COMPUTE_DTYPE == "bfloat16" else jnp.float32


class FPNNeck(nn.Module):
    """C2..C5 → P2..P5 (+P6 via maxpool, appended by the caller)."""

    channels: int = 256
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, feats: Tuple[jnp.ndarray, ...],
                 pad_mask=None) -> List[jnp.ndarray]:
        # pad_mask (layers.make_pad_mask): re-zero bucket padding before
        # the 3×3 smoothing convs — the laterals' biases repaint it
        # nonzero, and the 1×1s / nearest upsample are spatially safe
        # (valid fine cell i reads coarse cell ⌊i/2⌋, itself valid)
        pm = pad_mask if pad_mask is not None else (lambda v: v)
        c2, c3, c4, c5 = feats
        laterals = [
            conv(self.channels, 1, 1, self.dtype, name=f"lateral{i + 2}",
                 use_bias=True)(c)
            for i, c in enumerate((c2, c3, c4, c5))
        ]
        # top-down: nearest-neighbour upsample + add
        outs = [laterals[3]]
        for i in (2, 1, 0):
            up = outs[0]
            target = laterals[i]
            up = jax.image.resize(
                up, target.shape[:1] + target.shape[1:3] + up.shape[3:],
                method="nearest",
            )
            outs.insert(0, target + up)
        return [
            conv(self.channels, 3, 1, self.dtype, name=f"post{i + 2}",
                 use_bias=True)(pm(p))
            for i, p in enumerate(outs)
        ]


class FPNTopHead(nn.Module):
    """2-fc box head on pooled rois: (R, 7, 7, C) → (R, 1024)."""

    width: int = 1024
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, rois_feat: jnp.ndarray) -> jnp.ndarray:
        x = rois_feat.reshape(rois_feat.shape[0], -1)
        x = nn.Dense(self.width, dtype=self.dtype, param_dtype=jnp.float32,
                     name="fc1")(x)
        x = nn.relu(x)
        x = nn.Dense(self.width, dtype=self.dtype, param_dtype=jnp.float32,
                     name="fc2")(x)
        return nn.relu(x)


def roi_levels(rois: jnp.ndarray, k0: int = 4, canonical: float = 224.0,
               lo: int = 2, hi: int = 5) -> jnp.ndarray:
    """(…, 4) boxes → FPN level index in [lo, hi] (Lin et al. eq. 1)."""
    w = jnp.maximum(rois[..., 2] - rois[..., 0] + 1.0, 1.0)
    h = jnp.maximum(rois[..., 3] - rois[..., 1] + 1.0, 1.0)
    k = jnp.floor(k0 + jnp.log2(jnp.sqrt(w * h) / canonical))
    return jnp.clip(k, lo, hi).astype(jnp.int32)


def roi_level_counts(rois: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """``num_rois_p2`` .. ``num_rois_p5``: how many of ``rois`` each level
    pools (:func:`roi_levels`), summed over every leading axis — how the
    second stage's work splits over the pyramid, a counter of the step's
    ``aux`` that ``train_net`` sums into ``report["roi_levels"]``."""
    levels = roi_levels(rois)
    return {f"num_rois_p{lv}": (levels == lv).sum() for lv in range(2, 6)}


def _level_spans(levels: jnp.ndarray) -> jnp.ndarray:
    """(B, R) levels → (B, 4, 2) int32: ``[start, count]`` of each level's
    rois in the image's list once it is sorted by level."""
    count = (levels[..., None] == jnp.arange(2, 6)).sum(axis=1)
    return jnp.stack([jnp.cumsum(count, axis=1) - count, count], axis=-1)


def roi_stream_steps(pyramid, rois: jnp.ndarray,
                     pooled_size) -> Dict[str, jnp.ndarray]:
    """``roi_steps_live_p<l>`` and ``roi_steps_p<l>`` for every level the
    streaming ROIAlign pair pools in a differentiated graph: of the
    (roi block, image) pairs its grid walks (the second, a constant), how
    many hold a roi of the level (the first; on the others the kernels do
    nothing and fetch nothing).  Counters of the step's ``aux`` beside
    :func:`roi_level_counts`; empty where no level streams."""
    from mx_rcnn_tpu.ops.pallas.roi_align_stream import live_roi_blocks
    from mx_rcnn_tpu.ops.roi_align import roi_align_kernel

    spans = _level_spans(roi_levels(rois))
    out = {}
    for li, feat in enumerate(pyramid[:4]):
        if roi_align_kernel(feat, tuple(pooled_size)) != "stream":
            continue
        live = live_roi_blocks(
            spans[:, li], rois.shape[1], tuple(pooled_size), feat.shape[-1]
        )
        out[f"roi_steps_live_p{li + 2}"] = live.sum()
        out[f"roi_steps_p{li + 2}"] = jnp.asarray(live.size, jnp.int32)
    return out


@jax.custom_vjp
def _reorder(x, order, inverse):
    """``x[b, order[b]]`` along axis 1, for a permutation ``order`` whose
    inverse the caller has: the cotangent comes back by a gather through
    ``inverse`` where autodiff would emit a scatter-add."""
    return jnp.take_along_axis(
        x, order.reshape(order.shape + (1,) * (x.ndim - 2)), axis=1
    )


def _reorder_fwd(x, order, inverse):
    return _reorder(x, order, inverse), (order, inverse)


def _reorder_bwd(res, g):
    order, inverse = res
    zero = np.zeros(order.shape, jax.dtypes.float0)
    return _reorder(g, inverse, order), zero, zero


_reorder.defvjp(_reorder_fwd, _reorder_bwd)


def pool_levels(pyramid, rois: jnp.ndarray, pooled_size, strides,
                sample_ratio: int, fwd_only: bool = False, valid_hw=None):
    """Masked multi-level ROIAlign: P2.. maps × (B, R, 4) rois →
    (B, R, ph, pw, C), each roi's features from the level eq. 1 gives it.

    Static shapes: every level's call takes all R rois and the results are
    selected by the level mask.  Each image's rois are sorted by level
    ONCE (stable), so level l owns the contiguous span
    ``[start, start + count)`` of the sorted list; every call gets the
    sorted rois and its span, and the pooled result goes back into the
    caller's order once, after the selection (a gather by the inverse
    order, forward and backward).  The streaming kernels (P2, P3 at
    flagship resolution in a differentiated graph) visit the span's rois
    alone and leave the other rows undefined — hence ``jnp.where`` below,
    never a product.  The resident kernels and the gather take no span:
    there a roi of ANOTHER level is a zero box, because the kernels' work
    grows with a roi's extent on the map — a 400-pixel roi pooled on a
    fine map for nothing cost more than every roi that belongs there, and
    made the step's time follow the proposals' sizes (9% between seeds on
    the chip; PERF.md §6, PR 28).  Exact: the rows that count are computed
    as before."""
    # sort and way back under roi_align (the scope's metric pays for
    # them), the selection outside it as it always was
    with jax.named_scope("roi_align"):
        levels = roi_levels(rois)                        # (B, R) in [2, 5]
        order = jnp.argsort(levels, axis=1, stable=True)
        inverse = jnp.argsort(order, axis=1)
        rois = _reorder(rois, order, inverse)
        levels = jnp.take_along_axis(levels, order, axis=1)
        spans = _level_spans(levels)
    pooled = None
    for li, stride in enumerate(strides):
        own = levels == li + 2
        # roi_align/p2 .. roi_align/p5: the streaming levels and the
        # resident ones told apart in a device trace (metadata only)
        with jax.named_scope("roi_align"), jax.named_scope(f"p{li + 2}"):
            feats = extract_roi_features_batched(
                pyramid[li], jnp.where(own[..., None], rois, 0.0),
                "roi_align", pooled_size, 1.0 / stride, sample_ratio,
                fwd_only=fwd_only, valid_hw=valid_hw, span=spans[:, li],
            )                                            # (B, R, ph, pw, C)
        contrib = jnp.where(own[..., None, None, None], feats, 0.0)
        pooled = contrib if pooled is None else pooled + contrib
    with jax.named_scope("roi_align"):
        return _reorder(pooled, inverse, order)


class FPNFasterRCNN(nn.Module):
    """Multi-level two-stage detector; same external contract as
    :class:`FasterRCNN` (train → (loss, aux); test → padded detections),
    so the trainer/Predictor/eval stack is reused unchanged."""

    cfg: Config

    def setup(self):
        cfg = self.cfg
        dtype = _dtype_of(cfg)
        self.backbone = ResNetBackbone(
            depth=cfg.network.depth,
            dtype=dtype,
            return_pyramid=True,
            frozen_prefix=frozen_prefix_len(
                cfg.network.FIXED_PARAMS, RESNET_BLOCK_ORDER, requires=("bn",)
            ),
            fold_bn=cfg.network.FOLD_BN,
        )
        self.neck = FPNNeck(channels=cfg.network.FPN_CHANNELS, dtype=dtype)
        # one RPN head shared across levels (FPN paper); 3 anchors/cell
        self.rpn = RPNHead(
            num_anchors=len(cfg.network.ANCHOR_RATIOS)
            * len(cfg.network.FPN_ANCHOR_SCALES),
            channels=cfg.network.FPN_CHANNELS,
            dtype=dtype,
        )
        self.top_head = FPNTopHead(dtype=dtype)
        self.rcnn = RCNNHead(num_classes=cfg.dataset.NUM_CLASSES, dtype=dtype)
        if cfg.network.USE_MASK:
            self.mask_head = MaskHead(
                num_classes=cfg.dataset.NUM_CLASSES, dtype=dtype
            )

    # ----------------------------------------------------------- helpers
    def _pyramid(self, images: jnp.ndarray, pad_mask=None) -> List[jnp.ndarray]:
        """→ [P2, P3, P4, P5, P6].  P6's 1×1-window pool mixes nothing
        spatially, so it needs no mask of its own."""
        c_feats = self.backbone(images, pad_mask=pad_mask)
        ps = self.neck(c_feats, pad_mask=pad_mask)
        p6 = nn.max_pool(ps[-1], (1, 1), strides=(2, 2))
        return ps + [p6]

    def _level_anchors(self, shapes) -> List[np.ndarray]:
        net = self.cfg.network
        return [
            shifted_anchors(
                h, w, stride,
                ratios=net.ANCHOR_RATIOS, scales=net.FPN_ANCHOR_SCALES,
            )
            for (h, w), stride in zip(shapes, net.FPN_FEAT_STRIDES)
        ]

    def _rpn_over_levels(self, pyramid):
        """Shared RPN on each level → concat logits/deltas + anchor table."""
        logits, deltas = [], []
        for p in pyramid:
            lg, dl = self.rpn(p)               # (B, Hl*Wl*A, 2/4)
            logits.append(lg)
            deltas.append(dl)
        shapes = [(p.shape[1], p.shape[2]) for p in pyramid]
        anchors = jnp.asarray(
            np.concatenate(self._level_anchors(shapes), axis=0)
        )
        bounds = np.cumsum(
            [0] + [lg.shape[1] for lg in logits]
        )  # python ints, static
        return (
            jnp.concatenate(logits, axis=1),
            jnp.concatenate(deltas, axis=1),
            anchors,
            bounds,
        )

    def _propose_multilevel(
        self, fg_scores, deltas, anchors, bounds, im_info,
        pre_per_level, post_nms, nms_thresh, min_size,
    ):
        """One image: per-level top-k → union NMS → fixed post_nms set."""
        h, w, scale = im_info[0], im_info[1], im_info[2]
        boxes = bbox_pred(anchors, deltas)
        boxes = clip_boxes(boxes, (h, w))
        ms = min_size * scale
        ws = boxes[:, 2] - boxes[:, 0] + 1.0
        hs = boxes[:, 3] - boxes[:, 1] + 1.0
        keep = (ws >= ms) & (hs >= ms)
        scores = jnp.where(keep, fg_scores, _NEG_INF)

        top_boxes, top_scores = [], []
        for li in range(len(bounds) - 1):
            s_l = scores[bounds[li]:bounds[li + 1]]
            b_l = boxes[bounds[li]:bounds[li + 1]]
            k = min(pre_per_level, s_l.shape[0])
            ts, idx = jax.lax.top_k(s_l, k)
            top_scores.append(ts)
            top_boxes.append(b_l[idx])
        cat_scores = jnp.concatenate(top_scores)
        cat_boxes = jnp.concatenate(top_boxes, axis=0)
        valid = cat_scores > _NEG_INF / 2
        out_boxes, out_scores, out_valid = nms(
            cat_boxes, cat_scores, nms_thresh, post_nms, valid
        )
        return out_boxes, out_scores, out_valid

    def _roi_features(
        self, pyramid, rois: jnp.ndarray, fwd_only: bool = False,
        valid_hw=None,
    ) -> jnp.ndarray:
        """Masked multi-level ROIAlign: (B, R, 4) → (B*R, D)."""
        net = self.cfg.network
        pooled = pool_levels(
            pyramid, rois, net.POOLED_SIZE, net.FPN_FEAT_STRIDES[:4],
            net.ROI_SAMPLE_RATIO, fwd_only, valid_hw,
        )
        b, r = pooled.shape[0], pooled.shape[1]
        return self.top_head(pooled.reshape((b * r,) + pooled.shape[2:]))

    # ------------------------------------------------------------------ api
    def __call__(
        self,
        images: jnp.ndarray,
        im_info: jnp.ndarray,
        gt_boxes: Optional[jnp.ndarray] = None,
        gt_valid: Optional[jnp.ndarray] = None,
        train: bool = False,
        sample_seeds: Optional[jnp.ndarray] = None,
        gt_masks: Optional[jnp.ndarray] = None,
        proposals: Optional[jnp.ndarray] = None,
        prop_valid: Optional[jnp.ndarray] = None,
    ):
        from mx_rcnn_tpu.models.layers import normalize_images

        images = normalize_images(images, im_info, self.cfg)
        if train:
            return self.train_forward(
                images, im_info, gt_boxes, gt_valid, sample_seeds, gt_masks,
                proposals, prop_valid,
            )
        return self.test_forward(images, im_info)

    def train_forward(self, images, im_info, gt_boxes, gt_valid,
                      sample_seeds=None, gt_masks=None,
                      proposals=None, prop_valid=None):
        cfg = self.cfg
        t = cfg.TRAIN
        b = images.shape[0]
        pyramid = self._pyramid(images)
        rpn_logits, rpn_deltas, anchors, bounds = self._rpn_over_levels(pyramid)

        key = self.make_rng("sampling")
        if sample_seeds is not None:
            keys = jax.vmap(
                lambda s: jax.random.split(jax.random.fold_in(key, s), 2)
            )(sample_seeds)
        else:
            keys = jax.random.split(key, (b, 2))

        # stage scopes as in FasterRCNN.train_forward (metadata only)
        with jax.named_scope("anchor_targets"):
            atgt = per_image(
                lambda gtb, gtv, info, k: assign_anchor(
                    anchors, gtb[:, :4], gtv, info, k, cfg
                ),
                gt_boxes, gt_valid, im_info, keys[:, 0],
            )

        if proposals is not None:
            # frozen-proposal mode (ROIIter role / churn ablation): the
            # RCNN+mask branches train on an EXTERNAL fixed proposal set
            # instead of the live RPN's — RPN losses still train the RPN,
            # but its drift no longer reshuffles roi labels step to step
            if prop_valid is None:
                raise ValueError(
                    "frozen-proposal mode needs prop_valid alongside "
                    "proposals (a padded-count validity mask)"
                )
            prop_boxes = proposals
        else:
            n_levels = len(bounds) - 1
            pre_per_level = max(t.RPN_PRE_NMS_TOP_N // n_levels, 256)
            with jax.named_scope("proposal"):
                fg_scores = jax.nn.softmax(rpn_logits, axis=-1)[..., 1]
                prop_boxes, prop_scores, prop_valid = per_image(
                    lambda s, d, info: self._propose_multilevel(
                        s, d, anchors, bounds, info, pre_per_level,
                        t.RPN_POST_NMS_TOP_N, t.RPN_NMS_THRESH,
                        t.RPN_MIN_SIZE,
                    ),
                    jax.lax.stop_gradient(fg_scores),
                    jax.lax.stop_gradient(rpn_deltas),
                    im_info,
                )

        with jax.named_scope("roi_sample"):
            samples = jax.vmap(
                lambda r, rv, gtb, gtv, k: sample_rois(r, rv, gtb, gtv, k, cfg)
            )(prop_boxes, prop_valid, gt_boxes, gt_valid, keys[:, 1])

        with jax.named_scope("roi_head"):
            trunk = self._roi_features(pyramid, samples.rois)
            cls_logits, bbox_pred_out = self.rcnn(trunk)
        labels = samples.labels.reshape(-1)
        bbox_targets = samples.bbox_targets.reshape(bbox_pred_out.shape)
        bbox_weights = samples.bbox_weights.reshape(bbox_pred_out.shape)

        rpn_norm = float(t.RPN_BATCH_SIZE * b)
        rcnn_norm = float(t.BATCH_ROIS * b)
        with jax.named_scope("losses"):
            rpn_cls_loss = softmax_cross_entropy(
                rpn_logits.reshape(-1, 2), atgt.labels.reshape(-1), -1, rpn_norm
            )
            rpn_bbox_loss = weighted_smooth_l1(
                rpn_deltas.reshape(-1, 4),
                atgt.bbox_targets.reshape(-1, 4),
                atgt.bbox_weights.reshape(-1, 4),
                sigma=3.0,
                norm=rpn_norm,
            )
            rcnn_cls_loss = softmax_cross_entropy(cls_logits, labels, -1, rcnn_norm)
            rcnn_bbox_loss = weighted_smooth_l1(
                bbox_pred_out, bbox_targets, bbox_weights, sigma=1.0, norm=rcnn_norm
            )
            total = rpn_cls_loss + rpn_bbox_loss + rcnn_cls_loss + rcnn_bbox_loss

        aux = {
            "RPNAcc": accuracy(rpn_logits.reshape(-1, 2), atgt.labels.reshape(-1)),
            "RPNLogLoss": rpn_cls_loss,
            "RPNL1Loss": rpn_bbox_loss,
            "RCNNAcc": accuracy(cls_logits, labels),
            "RCNNLogLoss": rcnn_cls_loss,
            "RCNNL1Loss": rcnn_bbox_loss,
            "num_fg_rois": (labels > 0).sum(),
            "num_valid_props": prop_valid.sum(),
            "num_fg_anchors": (atgt.labels == 1).sum(),
        }
        aux.update(roi_level_counts(samples.rois))
        aux.update(roi_stream_steps(
            pyramid, samples.rois, cfg.network.POOLED_SIZE))

        if cfg.network.USE_MASK:
            mask_loss, mask_aux = self._mask_loss(
                pyramid, samples, gt_boxes, gt_valid, gt_masks
            )
            total = total + mask_loss
            aux.update(mask_aux)
        return total, aux

    def test_forward(self, images, im_info):
        cfg = self.cfg
        te = cfg.TEST
        b = images.shape[0]
        k = cfg.dataset.NUM_CLASSES
        from mx_rcnn_tpu.models.layers import make_pad_mask

        # serving invariance (see FasterRCNN.test_forward): mask bucket
        # padding through the backbone/neck and on every pyramid level
        # before the shared RPN's 3×3 conv.  Exactness additionally needs
        # bucket dims divisible by the max feature stride (SHAPE_BUCKETS
        # are), else the nearest-upsample index map varies per canvas.
        pad_mask = make_pad_mask(im_info, (images.shape[1], images.shape[2]))
        pyramid = [pad_mask(p) for p in self._pyramid(images, pad_mask)]
        rpn_logits, rpn_deltas, anchors, bounds = self._rpn_over_levels(pyramid)
        with jax.named_scope("proposal"):
            fg_scores = jax.nn.softmax(rpn_logits, axis=-1)[..., 1]
            # padding-invariance (see FasterRCNN.test_forward): drop anchors
            # whose grid cell lies in the bucket padding, per level
            shapes = tuple((p.shape[1], p.shape[2]) for p in pyramid)
            a_per_cell = len(cfg.network.ANCHOR_RATIOS) * len(
                cfg.network.FPN_ANCHOR_SCALES
            )
            grid_ok = jax.vmap(
                lambda info: anchor_grid_mask(
                    shapes, cfg.network.FPN_FEAT_STRIDES, a_per_cell, info
                )
            )(im_info)
            fg_scores = jnp.where(grid_ok, fg_scores, _NEG_INF)
            n_levels = len(bounds) - 1
            pre_per_level = max(te.RPN_PRE_NMS_TOP_N // n_levels, 256)
            rois, roi_scores, roi_valid = per_image(
                lambda s, d, info: self._propose_multilevel(
                    s, d, anchors, bounds, info, pre_per_level,
                    te.RPN_POST_NMS_TOP_N, te.RPN_NMS_THRESH, te.RPN_MIN_SIZE,
                ),
                fg_scores, rpn_deltas, im_info,
            )

        # one ladder-wide shape per level into roi_align so the second
        # stage is the SAME program for every bucket (see
        # layers.pad_feat_to_ladder); P6 is RPN-only and stays unpadded
        from mx_rcnn_tpu.models.layers import pad_feat_to_ladder

        pyramid = [
            pad_feat_to_ladder(p, s, cfg.SHAPE_BUCKETS)
            for p, s in zip(pyramid[:4], cfg.network.FPN_FEAT_STRIDES[:4])
        ] + pyramid[4:]
        with jax.named_scope("roi_head"):
            trunk = self._roi_features(
                pyramid, rois, fwd_only=True, valid_hw=im_info[:, :2]
            )
            cls_logits, bbox_deltas = self.rcnn(trunk)
        r = te.RPN_POST_NMS_TOP_N
        means, stds = bbox_denorm_vectors(cfg, k)
        bbox_deltas = bbox_deltas * stds[None, :] + means[None, :]
        out = {
            "rois": rois,
            "roi_scores": roi_scores,
            "roi_valid": roi_valid,
            "cls_prob": jax.nn.softmax(cls_logits).reshape(b, r, k),
            "bbox_deltas": bbox_deltas.reshape(b, r, 4 * k),
        }
        if cfg.network.USE_MASK:
            out["mask_logits"] = self._mask_forward(
                pyramid, rois, valid_hw=im_info[:, :2]
            )
        return out

    # ------------------------------------------------------------- mask head
    def _mask_pooled(self, pyramid, rois, fwd_only: bool = False,
                     valid_hw=None):
        """(B, R, 4) → (B*R, 14, 14, C) mask-branch roi features."""
        net = self.cfg.network
        pooled = pool_levels(
            pyramid, rois, (14, 14), net.FPN_FEAT_STRIDES[:4],
            net.ROI_SAMPLE_RATIO, fwd_only, valid_hw,
        )
        b, r = pooled.shape[0], pooled.shape[1]
        return pooled.reshape((b * r,) + pooled.shape[2:])

    def _mask_forward(self, pyramid, rois, valid_hw=None):
        """→ (B, R, 28, 28, K) per-class mask logits (test path)."""
        b, r = rois.shape[0], rois.shape[1]
        logits = self.mask_head(
            self._mask_pooled(pyramid, rois, fwd_only=True, valid_hw=valid_hw)
        )
        return logits.reshape((b, r) + logits.shape[1:])

    def _mask_loss(self, pyramid, samples, gt_boxes, gt_valid, gt_masks=None):
        """Per-fg-roi BCE against gt masks cropped to the roi (28×28).

        The matched gt is ``samples.gt_index`` — the SAME assignment
        ``sample_rois`` derived the roi's label and bbox target from.
        Re-deriving a fresh best-IoU argmax here could pair a roi
        labeled class A with a mask cropped from a different
        (higher-IoU) gt.

        Targets: with ``gt_masks`` (B, G, M, M) box-frame bitmaps (real
        polygon/RLE gts via ``data/masks.py``), each fg roi's target is
        its matched bitmap bilinearly resampled under the roi grid and
        binarized at 0.5.  Without (box-only datasets), the gt "mask"
        is its full rectangle — ``rasterize_box_masks``.
        """
        from mx_rcnn_tpu.ops.mask_targets import (
            crop_resize_masks,
            rasterize_box_masks,
        )

        cfg = self.cfg
        size = cfg.TRAIN.MASK_SIZE
        # The mask branch only ever contributes loss on FG rois, and
        # sample_rois packs fg first (ops/targets.py: fg priority wins
        # the top_k, quota FG_FRACTION·BATCH_ROIS) — so the branch runs
        # on just the first nfg roi slots.  EXACT: every fg roi lives in
        # that prefix; bg rows that pad it get zero loss weight either
        # way.  At the published config this is 4× less mask-branch work
        # (second ROIAlign, 4conv+deconv head, target resampling: 128 →
        # 32 rois).
        nfg = min(
            int(round(cfg.TRAIN.FG_FRACTION * cfg.TRAIN.BATCH_ROIS)),
            samples.rois.shape[1],
        )
        m_rois = samples.rois[:, :nfg]
        m_labels = samples.labels[:, :nfg]
        m_gt_index = samples.gt_index[:, :nfg]
        b, r = m_rois.shape[0], m_rois.shape[1]
        logits = self.mask_head(self._mask_pooled(pyramid, m_rois))
        logits = logits.reshape(b, r, size, size, -1)

        fg = m_labels > 0                                         # (B, R)
        if gt_masks is None:
            targets = jax.vmap(
                lambda rois_i, gi, gtb: rasterize_box_masks(
                    rois_i, gtb[gi, :4], size
                )
            )(m_rois, m_gt_index, gt_boxes)                       # (B, R, S, S)
        else:
            soft = jax.vmap(
                lambda rois_i, gi, gtb, gtm: crop_resize_masks(
                    rois_i, gtb[gi, :4], gtm[gi], size
                )
            )(m_rois, m_gt_index, gt_boxes, gt_masks)
            targets = (soft >= 0.5).astype(jnp.float32)

        cls = jnp.clip(m_labels, 0)                               # (B, R)
        sel = one_hot_select(
            logits, cls[..., None, None]
        )                                                         # (B, R, S, S)
        bce = optax_sigmoid_bce(sel, targets)
        per_roi = bce.mean(axis=(-1, -2))                         # (B, R)
        loss = (per_roi * fg).sum() / jnp.maximum(fg.sum(), 1.0)
        return loss, {"MaskBCELoss": loss}

    def mask_iou_probe(self, images, im_info, gt_boxes, gt_valid, gt_masks):
        """Decoupled mask-quality metric (VERDICT r4 #2): predict masks
        AT the gt boxes with the gt classes — no RPN, no detection
        scoring, no NMS confound — and return per-instance IoU of the
        thresholded 28×28 prediction against the gt polygon bitmap
        resampled onto the same grid.

        → (iou (B, G) f32, gt_valid (B, G) bool).  A rectangle-biased
        head scores ≈ box-occupancy here (ellipse ≈ 0.785, triangle
        ≈ 0.5), so mean IoU ≥ 0.8 on the synthetic ellipse/triangle set
        is evidence of actual shape learning.
        """
        from mx_rcnn_tpu.models.layers import normalize_images
        from mx_rcnn_tpu.ops.mask_targets import crop_resize_masks

        cfg = self.cfg
        size = cfg.TRAIN.MASK_SIZE
        images = normalize_images(images, im_info, cfg)
        pyramid = self._pyramid(images)
        boxes = gt_boxes[..., :4]                                 # (B, G, 4)
        logits = self._mask_forward(pyramid, boxes)               # (B, G, S, S, K)
        cls = jnp.clip(gt_boxes[..., 4].astype(jnp.int32), 0)
        pred = one_hot_select(logits, cls[..., None, None]) > 0.0  # (B, G, S, S)

        # gt bitmap in the same box frame: roi == gt box, so this is a
        # pure M→S bilinear resize of the box-frame bitmap
        target = jax.vmap(
            lambda rois_i, gtb, gtm: crop_resize_masks(
                rois_i, gtb, gtm, size
            )
        )(boxes, boxes, gt_masks) >= 0.5                          # (B, G, S, S)

        inter = (pred & target).sum(axis=(-1, -2)).astype(jnp.float32)
        union = (pred | target).sum(axis=(-1, -2)).astype(jnp.float32)
        iou = inter / jnp.maximum(union, 1.0)
        return iou, gt_valid


def optax_sigmoid_bce(logits, labels):
    return jnp.maximum(logits, 0) - logits * labels + jnp.log1p(
        jnp.exp(-jnp.abs(logits))
    )
