"""Faster R-CNN: one Flax module, one jitted graph, zero host round-trips.

Reference: the train/test Symbol builders ``rcnn/symbol/symbol_vgg.py ::
get_vgg_train/test`` and ``symbol_resnet.py :: get_resnet_train/test``
(SURVEY §4.5) — but where the reference graph hops to Python twice per
step (proposal + proposal_target CustomOps), here the proposal layer,
anchor-target assignment, and roi sampling are all jnp inside the same
XLA program.  Anchors are a trace-time constant derived from the (static,
bucketed) feature shape — the reference needed ``feat_sym.infer_shape``
machinery for the same purpose (``rcnn/core/loader.py :: AnchorLoader``).

Train call returns (losses, aux-for-metrics); test call returns padded
detections inputs (rois, class probs, de-normalized deltas).  Bbox-target
normalization stays in the loss/test-path (never folded into weights —
SURVEY §5.5 explains the reference's checkpoint quirk we deliberately
avoid).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.models.heads import RCNNHead
from mx_rcnn_tpu.models.layers import per_image
from mx_rcnn_tpu.models.resnet import offset_init
from mx_rcnn_tpu.models.rpn import RPNHead
from mx_rcnn_tpu.ops.anchors import shifted_anchors
from mx_rcnn_tpu.ops.deform_roi_pool import deform_roi_pool_batched, empty_bins
from mx_rcnn_tpu.ops.losses import (
    accuracy,
    smooth_l1,
    softmax_cross_entropy,
    weighted_smooth_l1,
)
from mx_rcnn_tpu.ops.proposal import _NEG_INF, anchor_grid_mask, propose
from mx_rcnn_tpu.ops.roi_align import extract_roi_features_batched
from mx_rcnn_tpu.ops.targets import assign_anchor, bbox_denorm_vectors, sample_rois


#: the ``offset`` fc's draw: lecun-normal times this, so that at the
#: initial weights the bins move by about a tenth of the roi (the public
#: initialisation is zero: the first steps would pool on the fixed grid)
ROI_OFFSET_INIT = 0.04


def _dtype_of(cfg: Config):
    return jnp.bfloat16 if cfg.network.COMPUTE_DTYPE == "bfloat16" else jnp.float32


class FasterRCNN(nn.Module):
    """Two-stage detector over a single-level feature map (VGG / ResNet-C4)."""

    cfg: Config

    def setup(self):
        cfg = self.cfg
        if cfg.network.USE_FPN:
            # loud failure until the FPN graph exists — silently training
            # a C4 model with FPN anchor settings was ADVICE r1's top bug
            raise NotImplementedError(
                "USE_FPN: FasterRCNN builds a single-level C4 graph; use the "
                "FPN model once implemented"
            )
        dtype = _dtype_of(cfg)
        from mx_rcnn_tpu.models.stage_models import build_backbone

        self.backbone, self.top_head = build_backbone(cfg, dtype)
        self.rpn = RPNHead(
            num_anchors=cfg.network.NUM_ANCHORS, channels=512, dtype=dtype
        )
        self.rcnn = RCNNHead(num_classes=cfg.dataset.NUM_CLASSES, dtype=dtype)
        if cfg.network.deformable:
            ph, pw = cfg.network.POOLED_SIZE
            # Deformable ConvNets' ``offset`` fc: the first pass's pooled
            # rois → a (dx, dy) for every bin of the second
            self.roi_offset = nn.Dense(
                2 * ph * pw, kernel_init=offset_init(ROI_OFFSET_INIT),
                dtype=dtype, param_dtype=jnp.float32)
        if cfg.network.USE_MASK:
            raise NotImplementedError(
                "USE_MASK: mask targets/loss are not wired into the C4 "
                "graph; the mask path lands with the FPN model"
            )

    def _anchors(self, feat_h: int, feat_w: int) -> jnp.ndarray:
        net = self.cfg.network
        return jnp.asarray(
            shifted_anchors(
                feat_h,
                feat_w,
                net.RPN_FEAT_STRIDE,
                ratios=net.ANCHOR_RATIOS,
                scales=net.ANCHOR_SCALES,
            )
        )

    def _backbone(self, images: jnp.ndarray, pad_mask=None):
        """→ (the RPN's map, the map rois are pooled from, counters for
        ``aux``): one map for both but under Deformable ConvNets, whose
        backbone pools from its conv5 (``DCNBackbone``)."""
        if self.cfg.network.deformable:
            return self.backbone(images, pad_mask=pad_mask)
        feat = self.backbone(images, pad_mask=pad_mask)
        return feat, feat, {}

    def _deform_pool(self, feat: jnp.ndarray, rois: jnp.ndarray,
                     valid_hw=None):
        """Deformable ConvNets' pooling: a pass on the fixed grid, the
        ``roi_offset`` fc on it, then a second pass with each bin moved by
        those offsets (in units of γ × the roi's extent) → ((B, R, ph, pw,
        C), counters for ``aux``: the second pass's bins that kept no
        sample, beside their total)."""
        net = self.cfg.network
        pool = functools.partial(
            deform_roi_pool_batched, feat, rois, pooled=net.POOLED_SIZE,
            spatial_scale=1.0 / net.RCNN_FEAT_STRIDE,
            sample_per_part=net.ROI_SAMPLE_RATIO, valid_hw=valid_hw)
        b, r = rois.shape[0], rois.shape[1]
        first = pool()
        t = self.roi_offset(first.reshape(b * r, -1)).reshape(
            (b, r, 2) + tuple(net.POOLED_SIZE))
        pooled = pool(offsets=t)
        counts = {
            "deform_pool_empty_bins": jax.vmap(
                lambda rr, tt, v: empty_bins(
                    feat.shape[1:3], rr, tt, net.POOLED_SIZE,
                    1.0 / net.RCNN_FEAT_STRIDE, net.ROI_SAMPLE_RATIO,
                    valid_hw=v)
            )(rois, t, valid_hw).sum(),
            "deform_pool_bins": jnp.asarray(
                b * r * net.POOLED_SIZE[0] * net.POOLED_SIZE[1], jnp.int32),
        }
        return pooled, counts

    def _roi_features(
        self, feat: jnp.ndarray, rois: jnp.ndarray, fwd_only: bool = False,
        valid_hw=None, sample_keys=None,
    ):
        """(B, Hf, Wf, C) × (B, R, 4) → ((B*R, D) head trunk features,
        counters for ``aux``).  ``sample_keys`` (B,): the images'
        roi-sampling keys, given in training: a head that drops units
        draws its masks from them."""
        net = self.cfg.network
        counts = {}
        # closes before top_head: the scope's device time is the pooling's.
        # Named after the mode (``roi_align`` | ``roi_pool`` |
        # ``deform_roi_pool``): a trace says which pooling it timed
        with jax.named_scope(net.ROI_MODE):
            if net.deformable:
                pooled, counts = self._deform_pool(feat, rois, valid_hw)
            else:
                pooled = extract_roi_features_batched(
                    feat,
                    rois,
                    net.ROI_MODE,
                    net.POOLED_SIZE,
                    1.0 / net.RCNN_FEAT_STRIDE,
                    net.ROI_SAMPLE_RATIO,
                    fwd_only=fwd_only,
                    valid_hw=valid_hw,
                )
        b, r = pooled.shape[0], pooled.shape[1]
        trunk = self.top_head(
            pooled.reshape((b * r,) + pooled.shape[2:]), sample_keys
        )
        return trunk, counts

    def __call__(
        self,
        images: jnp.ndarray,
        im_info: jnp.ndarray,
        gt_boxes: Optional[jnp.ndarray] = None,
        gt_valid: Optional[jnp.ndarray] = None,
        train: bool = False,
        sample_seeds: Optional[jnp.ndarray] = None,
    ):
        from mx_rcnn_tpu.models.layers import normalize_images

        images = normalize_images(images, im_info, self.cfg)
        if train:
            return self.train_forward(
                images, im_info, gt_boxes, gt_valid, sample_seeds
            )
        return self.test_forward(images, im_info)

    # ------------------------------------------------------------------ train
    def train_forward(
        self,
        images: jnp.ndarray,
        im_info: jnp.ndarray,
        gt_boxes: jnp.ndarray,
        gt_valid: jnp.ndarray,
        sample_seeds: Optional[jnp.ndarray] = None,
    ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        cfg = self.cfg
        t = cfg.TRAIN
        b = images.shape[0]

        feat, roi_feat, counts = self._backbone(images)
        rpn_logits, rpn_deltas = self.rpn(feat)           # (B, N, 2/4)
        anchors = self._anchors(feat.shape[1], feat.shape[2])

        key = self.make_rng("sampling")
        # per-image keys from batch-supplied seeds when available: sampling
        # then depends only on (step rng, image id), so any device topology
        # (1 chip × batch B or B chips × batch 1) draws identical samples —
        # the property the DP-equivalence test asserts exactly
        if sample_seeds is not None:
            keys = jax.vmap(
                lambda s: jax.random.split(jax.random.fold_in(key, s), 2)
            )(sample_seeds)
        else:
            keys = jax.random.split(key, (b, 2))

        # the named scopes below are the stage names a device trace is
        # read by (utils/tracing.py :: TRAIN_SCOPES); flax opens
        # ``backbone``, ``rpn`` and ``rcnn`` itself.  Metadata only.
        # --- RPN anchor targets (reference: rcnn/io/rpn.py :: assign_anchor)
        with jax.named_scope("anchor_targets"):
            atgt = per_image(
                lambda gtb, gtv, info, k: assign_anchor(anchors, gtb[:, :4], gtv, info, k, cfg),
                gt_boxes, gt_valid, im_info, keys[:, 0],
            )

        # --- proposals (stop-gradient: reference proposal op has no backward)
        with jax.named_scope("proposal"):
            fg_scores = jax.nn.softmax(rpn_logits, axis=-1)[..., 1]
            props = jax.vmap(
                lambda s, d, info: propose(
                    s,
                    d,
                    anchors,
                    info,
                    t.RPN_PRE_NMS_TOP_N,
                    t.RPN_POST_NMS_TOP_N,
                    t.RPN_NMS_THRESH,
                    t.RPN_MIN_SIZE,
                )
            )(jax.lax.stop_gradient(fg_scores), jax.lax.stop_gradient(rpn_deltas), im_info)

        # --- sample rois + RCNN targets (reference: proposal_target CustomOp)
        with jax.named_scope("roi_sample"):
            samples = jax.vmap(
                lambda r, rv, gtb, gtv, k: sample_rois(r, rv, gtb, gtv, k, cfg)
            )(props.rois, props.valid, gt_boxes, gt_valid, keys[:, 1])

        # --- second stage (the ROIAlign kernels stay innermost-scoped by
        # flax's ``FasterRCNN._roi_features``: the benchmark finds them so)
        with jax.named_scope("roi_head"):
            trunk, pool_counts = self._roi_features(
                roi_feat, samples.rois, sample_keys=keys[:, 1]
            )                                                   # (B*R, D)
            cls_logits, bbox_pred_out = self.rcnn(trunk)       # (B*R, K), (B*R, 4K)

        labels = samples.labels.reshape(-1)
        bbox_targets = samples.bbox_targets.reshape(bbox_pred_out.shape)
        bbox_weights = samples.bbox_weights.reshape(bbox_pred_out.shape)

        # --- losses, reference normalization semantics (SURVEY §4.5)
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
            # the reference's six metrics (rcnn/core/metric.py), same names
            "RPNAcc": accuracy(rpn_logits.reshape(-1, 2), atgt.labels.reshape(-1)),
            "RPNLogLoss": rpn_cls_loss,
            "RPNL1Loss": rpn_bbox_loss,
            "RCNNAcc": accuracy(cls_logits, labels),
            "RCNNLogLoss": rcnn_cls_loss,
            "RCNNL1Loss": rcnn_bbox_loss,
            "num_fg_rois": (labels > 0).sum(),
            "num_valid_props": props.valid.sum(),
            # zero when the image is smaller than every anchor (RPN loss
            # silently contributes nothing) — watch this on tiny inputs
            "num_fg_anchors": (atgt.labels == 1).sum(),
        }
        # Deformable ConvNets: each deformable layer's sampling points
        # inside the map and the pooled bins that kept no sample, beside
        # their constant totals (``train_net`` sums them into
        # ``report["deform"]``)
        aux.update(counts, **pool_counts)
        return total, aux

    # ------------------------------------------------------------------- test
    def test_forward(self, images: jnp.ndarray, im_info: jnp.ndarray):
        """→ dict with padded per-image rois, class probs, decoded deltas.

        Mirrors ``get_*_test`` + the head of ``rcnn/core/tester.py ::
        im_detect``: proposals from the RPN, class posteriors, and
        *de-normalized* class-specific deltas (the reference baked the
        de-normalization into saved weights; we keep it explicit here).
        """
        cfg = self.cfg
        te = cfg.TEST
        from mx_rcnn_tpu.models.layers import make_pad_mask, pad_feat_to_ladder

        # serving invariance: re-zero bucket padding before every spatial
        # op (frozen BN repaints zeros with its bias, so without this the
        # edge convs read different neighbours on different canvases and
        # detections depend on the bucket).  Inference-only — the train
        # graph keeps its original arithmetic.
        pad_mask = make_pad_mask(im_info, (images.shape[1], images.shape[2]))
        rpn_feat, roi_feat, _counts = self._backbone(images, pad_mask=pad_mask)
        feat = pad_mask(rpn_feat)
        roi_feat = feat if roi_feat is rpn_feat else pad_mask(roi_feat)
        rpn_logits, rpn_deltas = self.rpn(feat)
        anchors = self._anchors(feat.shape[1], feat.shape[2])

        with jax.named_scope("proposal"):
            fg_scores = jax.nn.softmax(rpn_logits, axis=-1)[..., 1]
            # kill anchors sitting on bucket padding: their scores come from
            # zero-padded features, so keeping them would make the pre-NMS
            # top-k set (and thus detections) depend on which bucket the
            # image padded into.  Inference-only — train keeps the full pool
            # (its tuned gate trajectories assume it).
            grid_ok = jax.vmap(
                lambda info: anchor_grid_mask(
                    ((feat.shape[1], feat.shape[2]),),
                    (cfg.network.RPN_FEAT_STRIDE,),
                    cfg.network.NUM_ANCHORS,
                    info,
                )
            )(im_info)
            fg_scores = jnp.where(grid_ok, fg_scores, _NEG_INF)
            props = jax.vmap(
                lambda s, d, info: propose(
                    s,
                    d,
                    anchors,
                    info,
                    te.RPN_PRE_NMS_TOP_N,
                    te.RPN_POST_NMS_TOP_N,
                    te.RPN_NMS_THRESH,
                    te.RPN_MIN_SIZE,
                )
            )(fg_scores, rpn_deltas, im_info)

        # one ladder-wide shape into roi_align so the second stage is the
        # SAME program for every bucket (see layers.pad_feat_to_ladder)
        roi_feat = pad_feat_to_ladder(
            roi_feat, cfg.network.RCNN_FEAT_STRIDE, cfg.SHAPE_BUCKETS
        )
        with jax.named_scope("roi_head"):
            trunk, _counts = self._roi_features(
                roi_feat, props.rois, fwd_only=True, valid_hw=im_info[:, :2]
            )
            cls_logits, bbox_deltas = self.rcnn(trunk)
        b, r = images.shape[0], te.RPN_POST_NMS_TOP_N
        k = cfg.dataset.NUM_CLASSES

        means, stds = bbox_denorm_vectors(cfg, k)
        bbox_deltas = bbox_deltas * stds[None, :] + means[None, :]

        return {
            "rois": props.rois,                                  # (B, R, 4)
            "roi_scores": props.scores,                          # (B, R)
            "roi_valid": props.valid,                            # (B, R)
            "cls_prob": jax.nn.softmax(cls_logits).reshape(b, r, k),
            "bbox_deltas": bbox_deltas.reshape(b, r, 4 * k),
        }
