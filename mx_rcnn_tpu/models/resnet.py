"""ResNet-50/101 backbone + conv5 top head, detection-style.

Reference: ``rcnn/symbol/symbol_resnet.py`` — conv1..conv4 (stride 16) as
the shared feature extractor, conv5 applied *after* ROI pooling as the RCNN
head, every BN frozen (``use_global_stats=True``, eps 2e-5), conv1+stage1
parameters frozen during training (``FIXED_PARAMS``).

Architectural stance: post-activation bottleneck (conv-BN-relu) in NHWC.
The reference uses MXNet's pre-activation variant; we keep the classic
post-act form because it is the layout every public ImageNet ResNet
checkpoint family uses, which keeps a future weight importer trivial, and
is numerically equivalent in capacity.  Stage/unit naming (``stage1`` ..
``stage4``) mirrors the reference so FIXED_PARAMS path-prefix freezing
matches both codebases.
"""

from __future__ import annotations

from typing import Any, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from mx_rcnn_tpu.models.layers import (
    FrozenBatchNorm,
    _ConvKernel,
    conv,
    make_conv_bn,
)
from mx_rcnn_tpu.ops.deform_conv import TAPS, deform_conv, inside_count

_BLOCKS = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}

# Deformable ConvNets' conv5 (Dai et al. 2017, the public
# ``resnet_v1_101_rcnn_dcn.py``): first unit at stride 1, every 3×3
# dilated by 2 and deformable with 4 offset groups, then ``conv_new_1``
# 2048 → 256 + ReLU, the map the rois are pooled from
DCN_DILATION = 2
DCN_GROUPS = 4
DCN_CHANNELS = 256
#: the offset convolutions' draw: lecun-normal times this, so that at the
#: initial weights the offsets spread about one map cell (the public
#: initialisation is zero, under which the first steps of training are a
#: plain dilated convolution and nothing deformable would be exercised)
DCN_OFFSET_INIT = 0.01

# leading-block order used for the frozen-prefix stop_gradient boundary;
# must match the module names in ResNetBackbone.__call__
RESNET_BLOCK_ORDER = ("conv0", "stage1", "stage2", "stage3")


def frozen_prefix_len(
    fixed_params: Sequence[str],
    order: Sequence[str],
    requires: Sequence[str] = (),
) -> int:
    """Length of the contiguous leading run of ``order`` whose names are
    frozen under FIXED_PARAMS prefix semantics (core.train.is_frozen_path).
    The backbone stops gradients at that boundary: parameters below it
    get zero updates from the optimizer mask anyway, so skipping their
    backward pass is an exact-semantics compute saving (~25% of the
    ResNet-101 backbone step at the default conv0+stage1 freeze).

    ``requires``: patterns that must also be present in ``fixed_params``
    for any stop to engage.  ResNet callers pass ("bn",): the stop lands
    after each block's FrozenBatchNorm, so the BN affines must be frozen
    too or the stop would silently zero their (trainable) grads.

    Matching delegates to ``core.train.is_frozen_path`` — the optimizer
    mask's own rule — so the stop boundary can never drift from what the
    optimizer actually freezes."""
    from mx_rcnn_tpu.core.train import is_frozen_path

    if any(req not in fixed_params for req in requires):
        return 0
    n = 0
    for name in order:
        if is_frozen_path((name,), fixed_params):
            n += 1
        else:
            break
    return n


def offset_init(scale: float = DCN_OFFSET_INIT):
    """lecun-normal times ``scale``: the offset layers' kernels."""
    return nn.initializers.variance_scaling(
        scale * scale, "fan_in", "truncated_normal")


class Bottleneck(nn.Module):
    """1x1 → 3x3 → 1x1(×4) bottleneck with projection shortcut."""

    filters: int
    stride: int = 1
    dtype: Any = jnp.float32
    fold_bn: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray, pad_mask=None) -> jnp.ndarray:
        pm = pad_mask if pad_mask is not None else (lambda v: v)
        cbn = make_conv_bn(self.fold_bn, self.dtype)
        y = cbn(x, self.filters, 1, self.stride, "conv1", "bn1")
        y = nn.relu(y)
        # the only spatial (3×3) op in the unit: re-zero bucket padding
        # first so edge cells read zeros on every canvas (layers.make_pad_mask)
        y = cbn(pm(y), self.filters, 3, 1, "conv2", "bn2")
        y = nn.relu(y)
        y = cbn(y, self.filters * 4, 1, 1, "conv3", "bn3")
        residual = x
        if residual.shape != y.shape:
            residual = cbn(x, self.filters * 4, 1, self.stride, "sc", "sc_bn")
        return nn.relu(y + residual)


class ResNetStage(nn.Module):
    filters: int
    num_units: int
    stride: int
    dtype: Any = jnp.float32
    fold_bn: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray, pad_mask=None) -> jnp.ndarray:
        for i in range(self.num_units):
            x = Bottleneck(
                self.filters,
                stride=self.stride if i == 0 else 1,
                dtype=self.dtype,
                fold_bn=self.fold_bn,
                name=f"unit{i + 1}",
            )(x, pad_mask=pad_mask)
        return x


class ResNetBackbone(nn.Module):
    """conv1..conv4: (B, H, W, 3) → C4 feature (B, H/16, W/16, 1024).

    When ``return_pyramid`` is set, also returns (C2, C3, C4, C5) for FPN —
    C5 computed convolutionally (the FPN layout; the plain Faster R-CNN
    path instead applies stage4 per-roi via :class:`ResNetTopHead`).
    """

    depth: int = 101
    dtype: Any = jnp.float32
    return_pyramid: bool = False
    # number of leading blocks [conv0, stage1, stage2, stage3] whose output
    # gradient is stopped (their params are frozen via the FIXED_PARAMS
    # optimizer mask; the stop makes XLA skip their backward entirely)
    frozen_prefix: int = 0
    # fold the frozen-BN affines into the conv kernels (exact rewrite;
    # same param tree — see layers.fused_conv_bn)
    fold_bn: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray, pad_mask=None):
        blocks = _BLOCKS[self.depth]
        pm = pad_mask if pad_mask is not None else (lambda v: v)

        def boundary(x, idx):
            return jax.lax.stop_gradient(x) if self.frozen_prefix == idx else x

        x = x.astype(self.dtype)
        x = make_conv_bn(self.fold_bn, self.dtype)(x, 64, 7, 2, "conv0", "bn0")
        x = nn.relu(x)
        # re-zero bucket padding before the 3×3 pool: relu output is ≥ 0,
        # and every valid pool window holds ≥ 1 valid cell, so masked
        # zeros can never win a max that real values would have won
        x = nn.max_pool(pm(x), (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        x = boundary(x, 1)

        def stage(filters, n_units, stride, name):
            return ResNetStage(
                filters, n_units, stride, self.dtype,
                fold_bn=self.fold_bn, name=name,
            )

        c2 = boundary(stage(64, blocks[0], 1, "stage1")(x, pad_mask), 2)
        c3 = boundary(stage(128, blocks[1], 2, "stage2")(c2, pad_mask), 3)
        c4 = boundary(stage(256, blocks[2], 2, "stage3")(c3, pad_mask), 4)
        if not self.return_pyramid:
            return c4
        c5 = stage(512, blocks[3], 2, "stage4")(c4, pad_mask)
        return c2, c3, c4, c5


class ResNetTopHead(nn.Module):
    """conv5 stage on pooled rois: (R, 14, 14, 1024) → (R, 2048) vector.

    Reference: the post-ROIPooling conv5 + global-average-pool tail of
    ``rcnn/symbol/symbol_resnet.py :: get_resnet_train``.

    ``drop_keys``: the top heads' one signature (``build_backbone``); this
    head drops nothing and reads none.
    """

    depth: int = 101
    dtype: Any = jnp.float32
    fold_bn: bool = False

    @nn.compact
    def __call__(self, rois_feat: jnp.ndarray, drop_keys=None) -> jnp.ndarray:
        blocks = _BLOCKS[self.depth]
        x = ResNetStage(512, blocks[3], 2, self.dtype,
                        fold_bn=self.fold_bn, name="stage4")(rois_feat)
        return jnp.mean(x, axis=(1, 2))


class DeformBottleneck(nn.Module):
    """Deformable ConvNets' conv5 unit: a stride-1 :class:`Bottleneck`
    whose 3×3 is deformable (``ops/deform_conv.py``), dilated by
    ``DCN_DILATION``, its offsets from a 3×3 convolution of the same input
    (``conv2_offset``) → ``(y, points of the 3×3 inside the map)``."""

    filters: int
    dtype: Any = jnp.float32
    fold_bn: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray, pad_mask=None):
        pm = pad_mask if pad_mask is not None else (lambda v: v)
        cbn = make_conv_bn(self.fold_bn, self.dtype)
        y = nn.relu(cbn(x, self.filters, 1, 1, "conv1", "bn1"))
        # the offsets, the sampling and the product: one scope a layer
        with jax.named_scope("deform_conv"):
            y = pm(y)
            offsets = conv(
                2 * len(TAPS) * DCN_GROUPS, 3, 1, self.dtype,
                name="conv2_offset", use_bias=True, dilation=DCN_DILATION,
                kernel_init=offset_init())(y)
            kernel = _ConvKernel(self.filters, 3, name="conv2")(y.shape[-1])
            inside = inside_count(offsets, DCN_DILATION, DCN_GROUPS)
            y = deform_conv(y, offsets, kernel.astype(self.dtype),
                            DCN_DILATION, DCN_GROUPS)
        y = nn.relu(FrozenBatchNorm(dtype=self.dtype, name="bn2")(y))
        y = cbn(y, self.filters * 4, 1, 1, "conv3", "bn3")
        residual = x
        if residual.shape != y.shape:
            residual = cbn(x, self.filters * 4, 1, 1, "sc", "sc_bn")
        return nn.relu(y + residual), inside


class DCNConv5(nn.Module):
    """Deformable ConvNets' ``stage4``: :class:`DeformBottleneck` units on
    the whole map → ``(y, [points inside the map, a unit])``."""

    num_units: int
    dtype: Any = jnp.float32
    fold_bn: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray, pad_mask=None):
        inside = []
        for i in range(self.num_units):
            x, n = DeformBottleneck(512, self.dtype, self.fold_bn,
                                    name=f"unit{i + 1}")(x, pad_mask)
            inside.append(n)
        return x, inside


class DCNBackbone(nn.Module):
    """Deformable ConvNets' backbone: :class:`ResNetBackbone`'s conv1..conv4
    (its parameters at this module's own level, as every ResNet's are),
    then :class:`DCNConv5` on the C4 map at stride 1 and ``conv_new_1``
    2048 → ``DCN_CHANNELS`` + ReLU → (C4 for the RPN, that map for the roi
    pooling, counters for ``aux``: each deformable layer's points inside
    the map as ``deform_inside_u<i>``, of ``deform_points`` a layer)."""

    depth: int = 101
    dtype: Any = jnp.float32
    frozen_prefix: int = 0
    fold_bn: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray, pad_mask=None):
        trunk = ResNetBackbone(self.depth, self.dtype,
                               frozen_prefix=self.frozen_prefix,
                               fold_bn=self.fold_bn, name="trunk")
        nn.share_scope(self, trunk)
        c4 = trunk(x, pad_mask)
        c5, inside = DCNConv5(_BLOCKS[self.depth][3], self.dtype,
                              self.fold_bn, name="stage4")(c4, pad_mask)
        feat = conv(DCN_CHANNELS, 1, 1, self.dtype, name="conv_new_1",
                    use_bias=True, kernel_init=nn.initializers.normal(0.01))(c5)
        counts = {f"deform_inside_u{i + 1}": n for i, n in enumerate(inside)}
        # the points a layer samples: the constant beside those counts
        counts["deform_points"] = jnp.asarray(
            c5.shape[0] * c5.shape[1] * c5.shape[2] * len(TAPS) * DCN_GROUPS,
            jnp.int32)
        return c4, nn.relu(feat), counts


class DCNTopHead(nn.Module):
    """Deformable ConvNets' 2-fc head on pooled rois: (R, 7, 7, 256) →
    (R, 1024), ``fc_new_1`` and ``fc_new_2`` with ReLU, no dropout (the
    public symbol has none), drawn normal(0.01) as it initialises them.

    ``drop_keys``: the top heads' one signature (``build_backbone``); this
    head drops nothing and reads none."""

    width: int = 1024
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, rois_feat: jnp.ndarray, drop_keys=None) -> jnp.ndarray:
        x = rois_feat.reshape(rois_feat.shape[0], -1)
        for name in ("fc_new_1", "fc_new_2"):
            x = nn.relu(nn.Dense(
                self.width, kernel_init=nn.initializers.normal(0.01),
                dtype=self.dtype, param_dtype=jnp.float32, name=name)(x))
        return x
