"""Region Proposal Network head.

Reference: the ``rpn_conv_3x3`` → ``rpn_cls_score``/``rpn_bbox_pred`` limb
of ``rcnn/symbol/symbol_vgg.py :: get_vgg_train`` (and the resnet twin).
Emits per-anchor objectness logits and box deltas in the per-pixel
(y, x, anchor) layout that :func:`mx_rcnn_tpu.ops.anchors.shifted_anchors`
uses, so flattening the head output aligns 1:1 with the anchor table —
no reshuffling op needed (the reference needed explicit Reshape/transpose
gymnastics to match its NCHW layout; NHWC makes the layouts agree for
free).
"""

from __future__ import annotations

from typing import Any, Tuple

import flax.linen as nn
import jax.numpy as jnp

from reference.models.layers import conv


class RPNHead(nn.Module):
    num_anchors: int = 9
    channels: int = 512
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, feat: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(B, H, W, C) → logits (B, H*W*A, 2), deltas (B, H*W*A, 4)."""
        b, h, w, _ = feat.shape
        x = conv(self.channels, 3, 1, self.dtype, name="rpn_conv", use_bias=True)(feat)
        x = nn.relu(x)
        logits = conv(
            2 * self.num_anchors, 1, 1, self.dtype, name="rpn_cls_score", use_bias=True
        )(x)
        deltas = conv(
            4 * self.num_anchors, 1, 1, self.dtype, name="rpn_bbox_pred", use_bias=True
        )(x)
        return (
            logits.reshape(b, h * w * self.num_anchors, 2).astype(jnp.float32),
            deltas.reshape(b, h * w * self.num_anchors, 4).astype(jnp.float32),
        )
