"""RCNN classification/regression head (and mask head).

Reference: the ``cls_score``/``bbox_pred`` fully-connected pair appended
after the fc6-fc7 (VGG) or conv5-pool (ResNet) trunk in
``rcnn/symbol/symbol_{vgg,resnet}.py``; initialized Normal(0.01)/
Normal(0.001) respectively (``train_end2end.py :: train_net``).
"""

from __future__ import annotations

from typing import Any, Tuple

import flax.linen as nn
import jax.numpy as jnp

from reference.models.layers import conv


class RCNNHead(nn.Module):
    num_classes: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(R, D) trunk features → cls logits (R, K), box deltas (R, 4K)."""
        cls_score = nn.Dense(
            self.num_classes,
            kernel_init=nn.initializers.normal(0.01),
            dtype=self.dtype,
            param_dtype=jnp.float32,
            name="cls_score",
        )(x)
        bbox_pred = nn.Dense(
            4 * self.num_classes,
            kernel_init=nn.initializers.normal(0.001),
            dtype=self.dtype,
            param_dtype=jnp.float32,
            name="bbox_pred",
        )(x)
        return cls_score.astype(jnp.float32), bbox_pred.astype(jnp.float32)
