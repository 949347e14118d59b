"""VGG-16 backbone + fc6/fc7 top head.

Reference: ``rcnn/symbol/symbol_vgg.py :: get_vgg_conv`` (13 convs, 4
pools → stride 16; conv1/conv2 frozen via FIXED_PARAMS) and the
fc6/fc7(4096) head applied to 7×7 pooled rois in ``get_vgg_train``.
NHWC, biases on (VGG has no BN).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from mx_rcnn_tpu.models.layers import conv

# (number of convs, channels) per block; pool after each of the first 4
_VGG16 = ((2, 64), (2, 128), (3, 256), (3, 512), (3, 512))

# leading-block order for the frozen-prefix stop_gradient boundary; block
# b's convs are named conv{b}_{i} in VGGBackbone.__call__
VGG_BLOCK_ORDER = ("conv1", "conv2", "conv3", "conv4", "conv5")


class VGGBackbone(nn.Module):
    """(B, H, W, 3) → (B, H/16, W/16, 512).

    Block 5 convs run at stride 16 with no trailing pool, matching the
    reference (pool5 is replaced by ROI pooling).
    """

    dtype: Any = jnp.float32
    # number of leading conv blocks whose output gradient is stopped (the
    # FIXED_PARAMS optimizer mask freezes their params; the stop lets XLA
    # skip their backward pass — see resnet.frozen_prefix_len)
    frozen_prefix: int = 0

    @nn.compact
    def __call__(self, x: jnp.ndarray, pad_mask=None) -> jnp.ndarray:
        # pad_mask: re-zero bucket padding before every spatial op so the
        # valid region is canvas-independent (the conv biases repaint the
        # padding nonzero after each layer) — see layers.make_pad_mask
        pm = pad_mask if pad_mask is not None else (lambda v: v)
        x = x.astype(self.dtype)
        for b, (n_convs, ch) in enumerate(_VGG16, start=1):
            for i in range(n_convs):
                x = conv(
                    ch, 3, 1, self.dtype, name=f"conv{b}_{i + 1}", use_bias=True
                )(pm(x))
                x = nn.relu(x)
            if b < 5:
                x = nn.max_pool(pm(x), (2, 2), strides=(2, 2))
            if b == self.frozen_prefix:
                x = jax.lax.stop_gradient(x)
        return x


#: upstream's ``Dropout(p=0.5)`` after relu6 and relu7 (``drop6`` / ``drop7``)
DROPOUT_RATE = 0.5
#: folds the head's dropout stream out of an image's roi-sampling key
DROP_STREAM = 0x64726F70  # "drop"


def dropout_rows(x: jnp.ndarray, keys: jnp.ndarray, rate: float) -> jnp.ndarray:
    """Inverted dropout (MXNet's ``Dropout``: kept units scaled by
    ``1 / (1 - rate)``) on (B*R, D) rows held image by image, image ``i``
    drawing its (R, D) mask from ``keys[i]`` alone: a row's mask then
    depends on its image's key and not on the batch it sits in."""
    b = keys.shape[0]
    keep = jax.vmap(
        lambda k: jax.random.bernoulli(k, 1.0 - rate, (x.shape[0] // b, x.shape[1]))
    )(keys).reshape(x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0).astype(x.dtype)


class VGGTopHead(nn.Module):
    """fc6/fc7 on pooled rois: (R, 7, 7, 512) → (R, 4096).

    ``drop_keys`` (B,) = one key an image (its roi-sampling key: the head
    folds a stream of its own out of it), the rows being B images' rois in
    image order: training applies dropout 0.5 after each ReLU, as
    ``get_vgg_train`` does, and a row's masks depend on (step rng, image
    id) only; without keys (``test_forward``, serving) nothing is dropped.
    """

    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, rois_feat: jnp.ndarray, drop_keys=None) -> jnp.ndarray:
        x = rois_feat.reshape(rois_feat.shape[0], -1)
        if drop_keys is not None:
            drop_keys = jax.vmap(
                lambda k: jax.random.fold_in(k, DROP_STREAM))(drop_keys)
        for i, name in enumerate(("fc6", "fc7")):
            x = nn.Dense(4096, dtype=self.dtype, param_dtype=jnp.float32, name=name)(x)
            x = nn.relu(x)
            if drop_keys is not None:
                keys = jax.vmap(lambda k: jax.random.fold_in(k, i))(drop_keys)
                x = dropout_rows(x, keys, DROPOUT_RATE)
        return x
