"""Box geometry: IoU, encode/decode, clipping.

Reference: ``rcnn/processing/bbox_transform.py`` (``nonlinear_transform``,
``nonlinear_pred``, ``clip_boxes``) and the Cython hot loop
``rcnn/cython/bbox.pyx :: bbox_overlaps_cython``.  The Cython O(N*K) loop
becomes a single broadcast expression — XLA vectorizes it onto the VPU/MXU
with no native code needed.  All functions are jittable, shape-polymorphic
at trace time, and keep the legacy +1 width convention of the reference so
goldens match.
"""

from __future__ import annotations

import jax.numpy as jnp

# guard against exp() overflow on garbage deltas of padded boxes
_BBOX_XFORM_CLIP = 4.135166556742356  # log(1000 / 16)


def bbox_overlaps(boxes: jnp.ndarray, query_boxes: jnp.ndarray) -> jnp.ndarray:
    """IoU matrix between (N, 4) and (K, 4) boxes → (N, K) float32.

    Reference: ``rcnn/cython/bbox.pyx :: bbox_overlaps_cython``.
    """
    boxes = boxes.astype(jnp.float32)
    query_boxes = query_boxes.astype(jnp.float32)
    bx1, by1, bx2, by2 = jnp.split(boxes[:, :4], 4, axis=1)        # (N,1)
    qx1, qy1, qx2, qy2 = (query_boxes[:, i] for i in range(4))     # (K,)

    iw = jnp.minimum(bx2, qx2[None, :]) - jnp.maximum(bx1, qx1[None, :]) + 1.0
    ih = jnp.minimum(by2, qy2[None, :]) - jnp.maximum(by1, qy1[None, :]) + 1.0
    inter = jnp.maximum(iw, 0.0) * jnp.maximum(ih, 0.0)            # (N,K)

    area_b = (bx2 - bx1 + 1.0) * (by2 - by1 + 1.0)                 # (N,1)
    area_q = (qx2 - qx1 + 1.0) * (qy2 - qy1 + 1.0)                 # (K,)
    union = area_b + area_q[None, :] - inter
    return inter / jnp.maximum(union, 1e-12)


def bbox_transform_planes(ex, gt):
    """:func:`bbox_transform` on coordinate planes: ``ex`` and ``gt`` are
    each ``(x1, y1, x2, y2)`` of equal-shaped arrays; → ``(dx, dy, dw, dh)``
    of that shape.  The one place the arithmetic lives: a caller with N on
    the minor axis (``ops/targets.py::assign_anchor``) never forms an
    ``(N, 4)`` operand, which the TPU pads to 128 lanes.
    """
    ex_x1, ex_y1, ex_x2, ex_y2 = ex
    gt_x1, gt_y1, gt_x2, gt_y2 = gt
    ex_w = ex_x2 - ex_x1 + 1.0
    ex_h = ex_y2 - ex_y1 + 1.0
    ex_cx = ex_x1 + 0.5 * (ex_w - 1.0)
    ex_cy = ex_y1 + 0.5 * (ex_h - 1.0)

    gt_w = gt_x2 - gt_x1 + 1.0
    gt_h = gt_y2 - gt_y1 + 1.0
    gt_cx = gt_x1 + 0.5 * (gt_w - 1.0)
    gt_cy = gt_y1 + 0.5 * (gt_h - 1.0)

    dx = (gt_cx - ex_cx) / (ex_w + 1e-14)
    dy = (gt_cy - ex_cy) / (ex_h + 1e-14)
    dw = jnp.log(jnp.maximum(gt_w, 1.0) / jnp.maximum(ex_w, 1e-14))
    dh = jnp.log(jnp.maximum(gt_h, 1.0) / jnp.maximum(ex_h, 1e-14))
    return dx, dy, dw, dh


def bbox_transform(ex_rois: jnp.ndarray, gt_rois: jnp.ndarray) -> jnp.ndarray:
    """Encode gt boxes w.r.t. example rois → (N, 4) [dx, dy, dw, dh].

    Reference: ``rcnn/processing/bbox_transform.py :: nonlinear_transform``.
    """
    return jnp.stack(
        bbox_transform_planes(
            [ex_rois[:, i] for i in range(4)], [gt_rois[:, i] for i in range(4)]
        ),
        axis=1,
    )


def bbox_pred(boxes: jnp.ndarray, box_deltas: jnp.ndarray) -> jnp.ndarray:
    """Decode (N, 4K) deltas against (N, 4) boxes → (N, 4K) predicted boxes.

    Reference: ``rcnn/processing/bbox_transform.py :: nonlinear_pred``.
    Class-agnostic (K=1) and class-specific (K=num_classes) layouts both
    flow through the same reshape.
    """
    n = boxes.shape[0]
    k4 = box_deltas.shape[1]
    widths = boxes[:, 2] - boxes[:, 0] + 1.0
    heights = boxes[:, 3] - boxes[:, 1] + 1.0
    ctr_x = boxes[:, 0] + 0.5 * (widths - 1.0)
    ctr_y = boxes[:, 1] + 0.5 * (heights - 1.0)

    deltas = box_deltas.reshape(n, -1, 4)
    dx, dy = deltas[..., 0], deltas[..., 1]
    dw = jnp.minimum(deltas[..., 2], _BBOX_XFORM_CLIP)
    dh = jnp.minimum(deltas[..., 3], _BBOX_XFORM_CLIP)

    pred_cx = dx * widths[:, None] + ctr_x[:, None]
    pred_cy = dy * heights[:, None] + ctr_y[:, None]
    pred_w = jnp.exp(dw) * widths[:, None]
    pred_h = jnp.exp(dh) * heights[:, None]

    out = jnp.stack(
        [
            pred_cx - 0.5 * (pred_w - 1.0),
            pred_cy - 0.5 * (pred_h - 1.0),
            pred_cx + 0.5 * (pred_w - 1.0),
            pred_cy + 0.5 * (pred_h - 1.0),
        ],
        axis=-1,
    )
    return out.reshape(n, k4)


def clip_boxes(boxes: jnp.ndarray, im_shape) -> jnp.ndarray:
    """Clip (N, 4K) boxes into the image: x∈[0, W-1], y∈[0, H-1].

    Reference: ``rcnn/processing/bbox_transform.py :: clip_boxes``.
    ``im_shape`` is (height, width) — scalars or traced values.
    """
    h, w = im_shape[0], im_shape[1]
    n = boxes.shape[0]
    b = boxes.reshape(n, -1, 4)
    x1 = jnp.clip(b[..., 0], 0.0, w - 1.0)
    y1 = jnp.clip(b[..., 1], 0.0, h - 1.0)
    x2 = jnp.clip(b[..., 2], 0.0, w - 1.0)
    y2 = jnp.clip(b[..., 3], 0.0, h - 1.0)
    return jnp.stack([x1, y1, x2, y2], axis=-1).reshape(boxes.shape)
