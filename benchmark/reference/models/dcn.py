"""``"graph": "dcn"``: Faster R-CNN with Deformable ConvNets on ResNet-101
- the training forward in plain ``jax.numpy``, float32, nothing of the
program.

Dai et al., "Deformable Convolutional Networks" (ICCV 2017,
arXiv:1703.06211) §2-§4, the Faster R-CNN rows, as the public
``msracver/Deformable-ConvNets`` builds them
(``faster_rcnn/symbols/resnet_v1_101_rcnn_dcn.py``, a project built on
mx-rcnn):

- **conv1-conv4**: the ResNet-101 trunk of ``reference/models/resnet.py``
  (its stages and conv-BN pairs, imported and not edited), every BN
  frozen, ``conv0`` and ``stage1`` fixed.
- **conv5** (``stage4``): three bottleneck units of 512 / 2048 on the
  whole stride-16 map, the first at stride 1; each unit's 3×3 is a
  deformable convolution (:func:`deform_conv`, written here from MXNet's
  ``deformable_im2col``): dilation 2, four offset groups of 128 channels,
  its offsets (2 × 9 × 4 = 72 channels) from a 3×3 convolution with bias
  of the same input (``conv2_offset``), no bias of its own.  Then
  ``conv_new_1``: 1×1 2048 → 256, ReLU.
- **RPN**: 3×3 conv 1024 → 512 on conv4, ReLU, 9 anchors a cell.
- **second stage**: deformable ROI pooling (:func:`deform_roi_pool`,
  written here from MXNet's ``DeformablePSROIPooling`` at ``group_size``
  1): 7×7 at 1/16, 4×4 samples a bin, γ 0.1.  A first pass without
  offsets feeds ``roi_offset`` (the symbol's ``offset``: 12544 → 98), a
  second pass pools with those offsets; then ``fc_new_1`` and
  ``fc_new_2`` (1024, ReLU, no dropout), ``cls_score`` and ``bbox_pred``.
- **losses**: softmax over the batch's rois and anchors, smooth-L1 with σ 3
  (RPN) and 1 (head), normalised by 256 and 128 an image.

Departures from the public symbol, each this project's own: NHWC (the
pooled rois reach ``roi_offset`` and ``fc_new_1`` flattened as
(row, column, channel)); images padded into a shape bucket, with
``im_info`` carrying the true extent; the proposal layer (12000 → NMS 0.7
→ 2000), the anchor targets and the roi sampling inside the graph; eight
images a step where the upstream trains one a GPU; class-specific boxes
as the repo's ``RCNNHead`` has them.  **Initialisation**: the public code
starts every offset layer at zero, under which the first steps would be
a plain dilated convolution and a fixed-grid pooling; here the offset
layers are drawn lecun-normal times ``OFFSET_INIT`` (the convolutions)
and ``ROI_OFFSET_INIT`` (``roi_offset``), so that at the seed's weights the
offsets spread about one map cell and the bins move by about a tenth of
the roi; ``conv_new_1``, ``fc_new_1`` and ``fc_new_2`` are drawn
normal(0.01) as the public code draws them.  **One learning rate**: the
public symbol scales ``offset``'s by 0.01 (``lr_mult``); this graph is
trained at one rate for every leaf.

The configuration says ``"network": "resnet"`` (the reference's registry
knows no other ResNet name), so the DCN settings are this module's
constants, and ``cfg``'s ``ROI_MODE``, ``POOLED_SIZE`` and
``ROI_SAMPLE_RATIO`` are not read.  ``harness/check_train.py`` asks the
rest: the program's leaf names (``backbone/stage4/unit<i>/conv2``,
``conv2_offset``, ``backbone/conv_new_1``, ``roi_offset``,
``top_head/fc_new_1``, ``fc_new_2``) and the sampling keys of
``reference/models/faster_rcnn.py``.
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
from reference.models.layers import (
    FrozenBatchNorm,
    make_conv_bn,
    normalize_images,
)
from reference.models.resnet import (
    RESNET_BLOCK_ORDER,
    ResNetStage,
    frozen_prefix_len,
)
from reference.models.rpn import RPNHead
from reference.ops.anchors import shifted_anchors
from reference.ops.losses import (
    accuracy,
    softmax_cross_entropy,
    weighted_smooth_l1,
)
from reference.ops.proposal import propose
from reference.ops.targets import assign_anchor, sample_rois

DEPTH = 101
#: conv5: units, width; every 3×3 dilated and deformable
CONV5_UNITS, CONV5_FILTERS = 3, 512
DILATION = 2
GROUPS = 4
CHANNELS = 256            # conv_new_1
POOLED = (7, 7)
SAMPLE_PER_PART = 4
TRANS_STD = 0.1
HEAD_WIDTH = 1024         # fc_new_1, fc_new_2
OFFSET_INIT = 0.01
ROI_OFFSET_INIT = 0.04


def lecun_times(scale: float):
    return nn.initializers.variance_scaling(
        scale * scale, "fan_in", "truncated_normal")


def deform_conv(x: jnp.ndarray, offsets: jnp.ndarray,
                kernel: jnp.ndarray) -> jnp.ndarray:
    """MXNet's ``DeformableConvolution`` (v1) at kernel 3, stride 1, pad
    and dilation ``DILATION``, ``GROUPS`` offset groups, no bias:
    (B, H, W, C) × (B, H, W, 72) × (3, 3, C, Cout) → (B, H, W, Cout).

    Tap by tap as ``deformable_im2col`` computes a column: tap ``(i, j)``
    of group ``g`` at (h, w) reads ``(h − d + i·d + Δy, w − d + j·d +
    Δx)``, its offsets at channels ``2(9g + 3i + j)`` and the next; a
    point with ``y < 0``, ``y >= H``, ``x < 0`` or ``x >= W`` reads 0,
    and inside, a point whose ``floor`` is the last row (column) reads
    that row itself (``h_high = h_low = H − 1``); elsewhere the four
    corners weigh ``(1 − ly)(1 − lx)`` .. ``ly·lx``.  Each tap's sampled
    map times its ``(C, Cout)`` slice of the kernel, summed over taps.

    The taps are a loop (``lax.scan``, so the check traces and compiles
    one tap, not nine); inside a tap the groups are one indexing of the
    map held as ``(B, H, W, G, C / G)``."""
    b, h, w, c = x.shape
    xg = x.reshape(b, h, w, GROUPS, c // GROUPS)
    off = offsets.reshape(b, h, w, GROUPS, 9, 2)
    taps = kernel.reshape(9, c, kernel.shape[-1])
    rows = jnp.arange(h, dtype=jnp.float32)[None, :, None, None]
    cols = jnp.arange(w, dtype=jnp.float32)[None, None, :, None]
    bi = jnp.arange(b)[:, None, None, None]
    gi = jnp.arange(GROUPS)[None, None, None, :]

    def tap(out, k):
        i, j = (k // 3).astype(jnp.float32), (k % 3).astype(jnp.float32)
        dyx = jnp.take(off, k, axis=4)                     # (B, H, W, G, 2)
        py = rows - DILATION + i * DILATION + dyx[..., 0]
        px = cols - DILATION + j * DILATION + dyx[..., 1]
        inside = (py >= 0) & (py < h) & (px >= 0) & (px < w)
        y_lo = jnp.floor(py)
        x_lo = jnp.floor(px)
        last_y = y_lo >= h - 1
        last_x = x_lo >= w - 1
        py = jnp.where(last_y, h - 1.0, py)
        px = jnp.where(last_x, w - 1.0, px)
        y_lo = jnp.where(last_y, h - 1.0, y_lo)
        x_lo = jnp.where(last_x, w - 1.0, x_lo)
        y_hi = jnp.where(last_y, y_lo, y_lo + 1)
        x_hi = jnp.where(last_x, x_lo, x_lo + 1)
        ly, lx = py - y_lo, px - x_lo

        def at(yy, xx):
            yy = jnp.clip(yy, 0, h - 1).astype(jnp.int32)
            xx = jnp.clip(xx, 0, w - 1).astype(jnp.int32)
            return xg[bi, yy, xx, gi]                      # (B, H, W, G, Cg)

        val = (((1 - ly) * (1 - lx))[..., None] * at(y_lo, x_lo)
               + ((1 - ly) * lx)[..., None] * at(y_lo, x_hi)
               + (ly * (1 - lx))[..., None] * at(y_hi, x_lo)
               + (ly * lx)[..., None] * at(y_hi, x_hi))
        sampled = jnp.where(inside[..., None], val, 0.0).reshape(b, h, w, c)
        return out + jnp.einsum("bhwc,cd->bhwd", sampled, taps[k]), None

    out, _ = jax.lax.scan(
        tap, jnp.zeros((b, h, w, kernel.shape[-1]), jnp.float32),
        jnp.arange(9))
    return out


def deform_roi_pool(fmap: jnp.ndarray, rois: jnp.ndarray,
                    trans: Optional[jnp.ndarray], scale: float) -> jnp.ndarray:
    """MXNet's ``DeformablePSROIPooling`` at ``group_size`` 1,
    ``pooled_size`` = ``part_size`` 7, ``sample_per_part`` 4, γ
    ``TRANS_STD``: (H, W, C) map × (R, 4) rois in image coordinates [×
    (R, 2, 7, 7) offsets, x first] → (R, 7, 7, C).

    As ``deformable_psroi_pooling.cu`` has it for one roi: ``start =
    round(x1)·s − 0.5``, ``end = (round(x2) + 1)·s − 0.5`` (C's ``round``),
    the extent at least 0.1, bins of a seventh of it; bin (ph, pw) starts
    at ``pw·bin + start + γ·trans[0, ph, pw]·width`` (and so in y); its
    4×4 samples lie a quarter of a bin apart; a sample with ``w < −0.5``
    or ``w > W − 0.5`` (or so in h) is skipped, a kept one clamped to
    ``[0, W − 1]`` and read by ``bilinear_interp`` (``x1 = floor``, ``x2 =
    ceil``); the bin is the mean of the kept samples, 0 if none.  One roi
    at a time."""
    hf, wf, _ = fmap.shape
    ph, pw = POOLED
    n = SAMPLE_PER_PART
    if trans is None:
        trans = jnp.zeros((rois.shape[0], 2, ph, pw), jnp.float32)

    @jax.checkpoint   # or the backward pass keeps every roi's samples
    def one_roi(args):
        roi, t = args
        v = jnp.sign(roi) * jnp.floor(jnp.abs(roi) + 0.5)
        start_w, start_h = v[0] * scale - 0.5, v[1] * scale - 0.5
        width = jnp.maximum((v[2] + 1.0) * scale - 0.5 - start_w, 0.1)
        height = jnp.maximum((v[3] + 1.0) * scale - 0.5 - start_h, 0.1)
        bin_w, bin_h = width / pw, height / ph
        p_h = jnp.arange(ph, dtype=jnp.float32)[:, None, None, None]
        p_w = jnp.arange(pw, dtype=jnp.float32)[None, :, None, None]
        i_h = jnp.arange(n, dtype=jnp.float32)[None, None, :, None]
        i_w = jnp.arange(n, dtype=jnp.float32)[None, None, None, :]
        hs = p_h * bin_h + start_h + TRANS_STD * t[1][:, :, None, None] * height
        ws = p_w * bin_w + start_w + TRANS_STD * t[0][:, :, None, None] * width
        y = hs + i_h * (bin_h / n)                          # (7, 7, 4, 4)
        x = ws + i_w * (bin_w / n)
        kept = (x >= -0.5) & (x <= wf - 0.5) & (y >= -0.5) & (y <= hf - 0.5)
        x = jnp.clip(x, 0.0, wf - 1.0)
        y = jnp.clip(y, 0.0, hf - 1.0)
        x1, x2 = jnp.floor(x), jnp.ceil(x)
        y1, y2 = jnp.floor(y), jnp.ceil(y)
        dx, dy = x - x1, y - y1

        def at(yy, xx):
            return fmap[yy.astype(jnp.int32), xx.astype(jnp.int32)]

        val = (((1 - dx) * (1 - dy))[..., None] * at(y1, x1)
               + ((1 - dx) * dy)[..., None] * at(y2, x1)
               + (dx * (1 - dy))[..., None] * at(y1, x2)
               + (dx * dy)[..., None] * at(y2, x2))        # (7, 7, 4, 4, C)
        total = jnp.where(kept[..., None], val, 0.0).sum(axis=(2, 3))
        count = kept.sum(axis=(2, 3))[..., None]
        return jnp.where(count > 0, total / jnp.maximum(count, 1), 0.0)

    return jax.lax.map(one_roi, (rois, trans))


class _Kernel(nn.Module):
    """The deformable convolution's HWIO kernel, lecun-normal."""

    features: int

    @nn.compact
    def __call__(self, cin: int) -> jnp.ndarray:
        return self.param("kernel", nn.initializers.lecun_normal(),
                          (3, 3, cin, self.features), jnp.float32)


class DeformUnit(nn.Module):
    """conv5's bottleneck: 1×1, the deformable 3×3, 1×1 (×4), frozen BN
    after each, the projection shortcut where the width changes."""

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cbn = make_conv_bn(False, jnp.float32)
        y = nn.relu(cbn(x, CONV5_FILTERS, 1, 1, "conv1", "bn1"))
        offsets = nn.Conv(
            2 * 9 * GROUPS, (3, 3), padding=((DILATION, DILATION),) * 2,
            kernel_dilation=(DILATION, DILATION),
            kernel_init=lecun_times(OFFSET_INIT), param_dtype=jnp.float32,
            name="conv2_offset")(y)
        y = deform_conv(y, offsets, _Kernel(CONV5_FILTERS, name="conv2")(
            y.shape[-1]))
        y = nn.relu(FrozenBatchNorm(name="bn2")(y))
        y = cbn(y, CONV5_FILTERS * 4, 1, 1, "conv3", "bn3")
        if x.shape != y.shape:
            x = cbn(x, CONV5_FILTERS * 4, 1, 1, "sc", "sc_bn")
        return nn.relu(y + x)


class Conv5(nn.Module):
    """``stage4``: ``CONV5_UNITS`` deformable units on the whole map."""

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        for u in range(CONV5_UNITS):
            x = DeformUnit(name=f"unit{u + 1}")(x)
        return x


class DCNBackbone(nn.Module):
    """(B, H, W, 3) → (conv4 for the RPN, conv_new_1's map for the
    pooling), both at stride 16.  ``fixed`` leading blocks of ``conv0``,
    ``stage1`` .. take no gradient."""

    fixed: int = 2

    @nn.compact
    def __call__(self, x: jnp.ndarray):
        def boundary(v, idx):
            return jax.lax.stop_gradient(v) if self.fixed == idx else v

        x = nn.relu(make_conv_bn(False, jnp.float32)(
            x, 64, 7, 2, "conv0", "bn0"))
        x = boundary(nn.max_pool(x, (3, 3), strides=(2, 2),
                                 padding=((1, 1), (1, 1))), 1)
        units = {101: (3, 4, 23)}[DEPTH]
        for k, (filters, stride) in enumerate(((64, 1), (128, 2), (256, 2))):
            x = boundary(ResNetStage(filters, units[k], stride,
                                     name=f"stage{k + 1}")(x), k + 2)
        c4 = x
        c5 = Conv5(name="stage4")(c4)
        feat = nn.Conv(CHANNELS, (1, 1),
                       kernel_init=nn.initializers.normal(0.01),
                       param_dtype=jnp.float32, name="conv_new_1")(c5)
        return c4, nn.relu(feat)


class FCHead(nn.Module):
    """One image's pooled rois (R, 7, 7, 256) → (R, 1024)."""

    @nn.compact
    def __call__(self, pooled: jnp.ndarray) -> jnp.ndarray:
        x = pooled.reshape(pooled.shape[0], -1)
        for name in ("fc_new_1", "fc_new_2"):
            x = nn.relu(nn.Dense(
                HEAD_WIDTH, kernel_init=nn.initializers.normal(0.01),
                name=name)(x))
        return x


class DCNFasterRCNN(nn.Module):
    cfg: Config

    def setup(self):
        net = self.cfg.network
        if net.COMPUTE_DTYPE != "float32":
            raise ValueError("the DCN reference computes in float32")
        self.backbone = DCNBackbone(fixed=frozen_prefix_len(
            net.FIXED_PARAMS, RESNET_BLOCK_ORDER, requires=("bn",)))
        self.rpn = RPNHead(num_anchors=net.NUM_ANCHORS, channels=512)
        self.roi_offset = nn.Dense(
            2 * POOLED[0] * POOLED[1], kernel_init=lecun_times(ROI_OFFSET_INIT))
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
            raise NotImplementedError("the DCN reference trains only")
        if self.is_initializing():
            return self._make_params()
        cfg = self.cfg
        net, t = cfg.network, cfg.TRAIN
        b = images.shape[0]
        feat, conv_new = self.backbone(normalize_images(images, im_info, cfg))
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

        # --- second stage, each image's rois on its own map: the fixed
        # grid's pooling, its offsets, the moved bins' pooling, fc_new_1 / _2
        scale = 1.0 / net.RCNN_FEAT_STRIDE
        rois = samples.rois
        n = rois.shape[0] * rois.shape[1]
        first = jax.vmap(deform_roi_pool, in_axes=(0, 0, None, None))(
            conv_new, rois, None, scale)
        trans = self.roi_offset(first.reshape(n, -1)).reshape(
            rois.shape[:2] + (2,) + POOLED)
        pooled = jax.vmap(deform_roi_pool, in_axes=(0, 0, 0, None))(
            conv_new, rois, trans, scale)
        cls_logits, box_out = self.rcnn(self.top_head(
            pooled.reshape((n,) + pooled.shape[2:])))

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

    def _make_params(self):
        """``init``: every parameter, made on a 32×32 canvas and a zero
        pooled roi.  No parameter's shape hangs on the image's size, and
        flax draws each from the seed by its module's path, so these are
        the weights a forward at full size would make; the init program
        then compiles in seconds (the check builds it anew for every
        seed)."""
        c4, _ = self.backbone(jnp.zeros((1, 32, 32, 3), jnp.float32))
        self.rpn(c4)
        pooled = jnp.zeros((1,) + POOLED + (CHANNELS,), jnp.float32)
        self.roi_offset(pooled.reshape(1, -1))
        self.rcnn(self.top_head(pooled))
        return jnp.zeros((), jnp.float32), {}


def build(cfg):
    net = cfg.network
    if net.name != "resnet" or net.depth != DEPTH or net.USE_FPN:
        raise ValueError("graph dcn on a configuration of another network")
    return DCNFasterRCNN(cfg)
