"""Proposal generation: RPN outputs → fixed-size roi set, fully in-graph.

Reference: ``rcnn/symbol/proposal.py :: ProposalOperator.forward`` — a
host-side CustomOp that copies RPN outputs to CPU every step, decodes with
numpy, calls the CUDA NMS, and copies rois back (boundary B1 in SURVEY
§4.1).  Here the whole thing is jnp inside the train/test jit: decode →
clip → min-size mask → top-k → masked NMS → pad to POST_NMS_TOP_N.  The
reference already padded its output to a fixed size; we extend that
discipline with an explicit validity mask instead of its zero-row hack.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from reference.ops.boxes import bbox_pred, clip_boxes
from reference.ops.nms import nms

_NEG_INF = -1e10


class Proposals(NamedTuple):
    rois: jnp.ndarray    # (POST_NMS, 4) image-coordinate boxes, padded
    scores: jnp.ndarray  # (POST_NMS,)
    valid: jnp.ndarray   # (POST_NMS,) bool


def anchor_grid_mask(feat_shapes, strides, num_anchors, im_info) -> jnp.ndarray:
    """One image: which anchor slots sit on image content — (N,) bool over
    the concatenated per-level anchor table, row-major (y, x, anchor) per
    level, matching ``shifted_anchors`` + the RPN head emission order.

    An anchor whose grid cell lies in the bucket padding scores zero-image
    features, so its fg score depends on the CANVAS rather than the image:
    two buckets padding the same image would rank different pre-NMS top-k
    sets and detections would drift with the bucket (the serving
    padding-invariance bug).  Cell (y, x) is kept iff its top-left corner
    ``(stride·y, stride·x)`` is inside the unpadded image — a canvas-
    independent criterion, and every kept cell exists (with bit-identical
    features) in every bucket the image fits.
    """
    h, w = im_info[0], im_info[1]
    parts = []
    for (fh, fw), stride in zip(feat_shapes, strides):
        ys = jnp.arange(fh, dtype=jnp.float32) * stride < h
        xs = jnp.arange(fw, dtype=jnp.float32) * stride < w
        m = (ys[:, None] & xs[None, :]).reshape(-1)
        parts.append(jnp.repeat(m, num_anchors))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def propose(
    fg_scores: jnp.ndarray,
    deltas: jnp.ndarray,
    anchors: jnp.ndarray,
    im_info: jnp.ndarray,
    pre_nms_top_n: int,
    post_nms_top_n: int,
    nms_thresh: float,
    min_size: float,
) -> Proposals:
    """One image: (N,) anchor fg scores + (N, 4) deltas → proposals.

    ``im_info`` = (h, w, scale) of the unpadded image; ``min_size`` is
    scaled by ``im_info[2]`` exactly as the reference does.
    """
    h, w, scale = im_info[0], im_info[1], im_info[2]
    boxes = bbox_pred(anchors, deltas)
    boxes = clip_boxes(boxes, (h, w))

    ms = min_size * scale
    ws = boxes[:, 2] - boxes[:, 0] + 1.0
    hs = boxes[:, 3] - boxes[:, 1] + 1.0
    keep = (ws >= ms) & (hs >= ms)

    scores = jnp.where(keep, fg_scores, _NEG_INF)
    k = min(pre_nms_top_n, scores.shape[0])
    top_scores, idx = jax.lax.top_k(scores, k)
    top_boxes = boxes[idx]
    top_valid = top_scores > _NEG_INF / 2

    # top_k output is descending-score: the NMS can skip its own sort
    out_boxes, out_scores, out_valid = nms(
        top_boxes, top_scores, nms_thresh, post_nms_top_n, top_valid,
        sorted_input=True,
    )
    return Proposals(out_boxes, out_scores, out_valid)
