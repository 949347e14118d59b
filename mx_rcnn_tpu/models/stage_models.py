"""Stage graphs for alternate training: RPN-only and Fast-RCNN-on-proposals.

Reference: the per-stage Symbol builders — ``get_*_rpn``/``get_*_rpn_test``
(RPN-only graphs used by ``rcnn/tools/train_rpn.py``/``test_rpn.py``) and
``get_*_rcnn``/``get_*_rcnn_test`` (Fast R-CNN graphs on precomputed
proposals used by ``rcnn/tools/train_rcnn.py``, fed by
``rcnn/core/loader.py :: ROIIter``).  Same TPU-native stance as
:class:`FasterRCNN`: everything in one jitted graph, fixed shapes,
validity masks.

Both models expose the standard ``(… , train)`` __call__ so the generic
``make_train_step``/``Predictor`` machinery works unchanged; batch dicts
carry exactly the keyword names each signature needs.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.models.heads import RCNNHead
from mx_rcnn_tpu.models.layers import per_image
from mx_rcnn_tpu.models.resnet import (
    RESNET_BLOCK_ORDER,
    DCNBackbone,
    DCNTopHead,
    ResNetBackbone,
    ResNetTopHead,
    frozen_prefix_len,
)
from mx_rcnn_tpu.models.rpn import RPNHead
from mx_rcnn_tpu.models.vgg import VGG_BLOCK_ORDER, VGGBackbone, VGGTopHead
from mx_rcnn_tpu.ops.anchors import shifted_anchors
from mx_rcnn_tpu.ops.losses import accuracy, softmax_cross_entropy, weighted_smooth_l1
from mx_rcnn_tpu.ops.proposal import propose
from mx_rcnn_tpu.ops.roi_align import extract_roi_features_batched
from mx_rcnn_tpu.ops.targets import assign_anchor, bbox_denorm_vectors, sample_rois


def _dtype_of(cfg: Config):
    return jnp.bfloat16 if cfg.network.COMPUTE_DTYPE == "bfloat16" else jnp.float32


def build_backbone(
    cfg: Config, dtype, fixed_params: Optional[Tuple[str, ...]] = None
) -> Tuple[nn.Module, nn.Module]:
    """(backbone, top_head) for the configured network — shared across
    FasterRCNN / RPNOnly / FastRCNN so param trees align for
    ``combine_model``.  Every top head is called ``head(pooled,
    drop_keys=None)``: one key an image in training, for a head that drops
    units.

    The backbone stops gradients at the contiguous-prefix boundary of
    the freeze set: those params get zero updates from the optimizer
    mask either way, so XLA skipping their backward pass is free speed.
    ``fixed_params`` must name the set the optimizer actually freezes
    (stage-2 alternate training passes FIXED_PARAMS_SHARED); defaults to
    ``cfg.network.FIXED_PARAMS``.

    Deformable ConvNets (``cfg.network.deformable``): the backbone runs
    the deformable conv5 on the map and the top head is the 2-fc head."""
    fixed = cfg.network.FIXED_PARAMS if fixed_params is None else fixed_params
    if cfg.network.name == "vgg":
        n = frozen_prefix_len(fixed, VGG_BLOCK_ORDER)
        return VGGBackbone(dtype=dtype, frozen_prefix=n), VGGTopHead(dtype=dtype)
    n = frozen_prefix_len(fixed, RESNET_BLOCK_ORDER, requires=("bn",))
    fold = cfg.network.FOLD_BN
    if cfg.network.deformable:
        return (DCNBackbone(depth=cfg.network.depth, dtype=dtype,
                            frozen_prefix=n, fold_bn=fold),
                DCNTopHead(dtype=dtype))
    return (
        ResNetBackbone(depth=cfg.network.depth, dtype=dtype, frozen_prefix=n,
                       fold_bn=fold),
        ResNetTopHead(depth=cfg.network.depth, dtype=dtype, fold_bn=fold),
    )


def _single_map_only(cfg: Config) -> None:
    """The stage graphs pool rois from the RPN's own map; Deformable
    ConvNets pools from its conv5 and trains end to end only."""
    if cfg.network.deformable:
        raise NotImplementedError(
            "resnet_dcn trains end to end (FasterRCNN): the alternate "
            "training's stage graphs have no deformable conv5")


class RPNOnly(nn.Module):
    """RPN training/inference graph (get_*_rpn / get_*_rpn_test twin).

    Param tree: {backbone, rpn} — name-compatible with FasterRCNN so
    stage checkpoints transfer by subtree copy.

    ``fixed_params``: the freeze set the optimizer will use, when it
    differs from cfg.network.FIXED_PARAMS (stage-4 alternate training
    freezes FIXED_PARAMS_SHARED) — keeps the backbone's backward-skip
    boundary aligned with the actual freeze.
    """

    cfg: Config
    fixed_params: Optional[Tuple[str, ...]] = None

    def setup(self):
        cfg = self.cfg
        _single_map_only(cfg)
        dtype = _dtype_of(cfg)
        self.backbone, _ = build_backbone(cfg, dtype, self.fixed_params)
        self.rpn = RPNHead(
            num_anchors=cfg.network.NUM_ANCHORS, channels=512, dtype=dtype
        )

    def _anchors(self, feat_h: int, feat_w: int) -> jnp.ndarray:
        net = self.cfg.network
        return jnp.asarray(
            shifted_anchors(
                feat_h, feat_w, net.RPN_FEAT_STRIDE,
                ratios=net.ANCHOR_RATIOS, scales=net.ANCHOR_SCALES,
            )
        )

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

        cfg = self.cfg
        t = cfg.TRAIN
        b = images.shape[0]
        feat = self.backbone(normalize_images(images, im_info, cfg))
        rpn_logits, rpn_deltas = self.rpn(feat)
        anchors = self._anchors(feat.shape[1], feat.shape[2])

        if not train:
            te = cfg.TEST
            fg_scores = jax.nn.softmax(rpn_logits, axis=-1)[..., 1]
            props = jax.vmap(
                lambda s, d, info: propose(
                    s, d, anchors, info, te.RPN_PRE_NMS_TOP_N,
                    te.RPN_POST_NMS_TOP_N, te.RPN_NMS_THRESH, te.RPN_MIN_SIZE,
                )
            )(fg_scores, rpn_deltas, im_info)
            return {
                "rois": props.rois,
                "roi_scores": props.scores,
                "roi_valid": props.valid,
            }

        key = self.make_rng("sampling")
        if sample_seeds is not None:
            keys = jax.vmap(lambda s: jax.random.fold_in(key, s))(sample_seeds)
        else:
            keys = jax.random.split(key, b)
        atgt = per_image(
            lambda gtb, gtv, info, k: assign_anchor(
                anchors, gtb[:, :4], gtv, info, k, cfg
            ),
            gt_boxes, gt_valid, im_info, keys,
        )

        rpn_norm = float(t.RPN_BATCH_SIZE * b)
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
        total = rpn_cls_loss + rpn_bbox_loss
        aux = {
            "RPNAcc": accuracy(rpn_logits.reshape(-1, 2), atgt.labels.reshape(-1)),
            "RPNLogLoss": rpn_cls_loss,
            "RPNL1Loss": rpn_bbox_loss,
            # diagnostic: zero here means no anchor fits the image border
            # (image smaller than the smallest anchor) — loss silently 0
            "num_fg_anchors": (atgt.labels == 1).sum(),
        }
        return total, aux


class FastRCNN(nn.Module):
    """Fast-R-CNN-on-proposals graph (get_*_rcnn / get_*_rcnn_test twin;
    TRAIN.HAS_RPN=False mode).  Proposals arrive from the batch (dumped by
    an RPN via ``generate_proposals``) instead of an in-graph RPN.

    Param tree: {backbone, top_head, rcnn} — name-compatible with
    FasterRCNN.  ``fixed_params`` as on :class:`RPNOnly`.
    """

    cfg: Config
    fixed_params: Optional[Tuple[str, ...]] = None

    def setup(self):
        cfg = self.cfg
        _single_map_only(cfg)
        dtype = _dtype_of(cfg)
        self.backbone, self.top_head = build_backbone(cfg, dtype, self.fixed_params)
        self.rcnn = RCNNHead(num_classes=cfg.dataset.NUM_CLASSES, dtype=dtype)

    def _roi_features(
        self, feat: jnp.ndarray, rois: jnp.ndarray, fwd_only: bool = False,
        sample_keys=None,
    ) -> jnp.ndarray:
        """``sample_keys`` (B,): the images' roi-sampling keys, given in
        training: a head that drops units draws its masks from them."""
        net = self.cfg.network
        pooled = extract_roi_features_batched(
            feat, rois, net.ROI_MODE, net.POOLED_SIZE,
            1.0 / net.RCNN_FEAT_STRIDE, net.ROI_SAMPLE_RATIO,
            fwd_only=fwd_only,
        )
        b, r = pooled.shape[0], pooled.shape[1]
        return self.top_head(
            pooled.reshape((b * r,) + pooled.shape[2:]), sample_keys
        )

    def __call__(
        self,
        images: jnp.ndarray,
        im_info: jnp.ndarray,
        proposals: jnp.ndarray = None,
        prop_valid: jnp.ndarray = None,
        gt_boxes: Optional[jnp.ndarray] = None,
        gt_valid: Optional[jnp.ndarray] = None,
        train: bool = False,
        sample_seeds: Optional[jnp.ndarray] = None,
    ):
        cfg = self.cfg
        from mx_rcnn_tpu.models.layers import normalize_images

        t = cfg.TRAIN
        b = images.shape[0]
        k = cfg.dataset.NUM_CLASSES
        feat = self.backbone(normalize_images(images, im_info, cfg))

        if not train:
            trunk = self._roi_features(feat, proposals, fwd_only=True)
            cls_logits, bbox_deltas = self.rcnn(trunk)
            r = proposals.shape[1]
            means, stds = bbox_denorm_vectors(cfg, k)
            bbox_deltas = bbox_deltas * stds[None, :] + means[None, :]
            return {
                "rois": proposals,
                "roi_scores": jnp.zeros(proposals.shape[:2], jnp.float32),
                "roi_valid": prop_valid,
                "cls_prob": jax.nn.softmax(cls_logits).reshape(b, r, k),
                "bbox_deltas": bbox_deltas.reshape(b, r, 4 * k),
            }

        key = self.make_rng("sampling")
        if sample_seeds is not None:
            keys = jax.vmap(lambda s: jax.random.fold_in(key, s))(sample_seeds)
        else:
            keys = jax.random.split(key, b)
        samples = jax.vmap(
            lambda r, rv, gtb, gtv, kk: sample_rois(r, rv, gtb, gtv, kk, cfg)
        )(proposals, prop_valid, gt_boxes, gt_valid, keys)

        trunk = self._roi_features(feat, samples.rois, sample_keys=keys)
        cls_logits, bbox_pred_out = self.rcnn(trunk)
        labels = samples.labels.reshape(-1)
        bbox_targets = samples.bbox_targets.reshape(bbox_pred_out.shape)
        bbox_weights = samples.bbox_weights.reshape(bbox_pred_out.shape)

        rcnn_norm = float(t.BATCH_ROIS * b)
        rcnn_cls_loss = softmax_cross_entropy(cls_logits, labels, -1, rcnn_norm)
        rcnn_bbox_loss = weighted_smooth_l1(
            bbox_pred_out, bbox_targets, bbox_weights, sigma=1.0, norm=rcnn_norm
        )
        total = rcnn_cls_loss + rcnn_bbox_loss
        aux = {
            "RCNNAcc": accuracy(cls_logits, labels),
            "RCNNLogLoss": rcnn_cls_loss,
            "RCNNL1Loss": rcnn_bbox_loss,
            "num_fg_rois": (labels > 0).sum(),
        }
        return total, aux
