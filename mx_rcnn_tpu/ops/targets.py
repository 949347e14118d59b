"""In-graph target assignment: RPN anchor targets and RCNN roi sampling.

Reference: ``rcnn/io/rpn.py :: assign_anchor`` (host numpy, per image, in
the data loader) and ``rcnn/symbol/proposal_target.py`` +
``rcnn/io/rcnn.py :: sample_rois`` (host numpy via a CustomOp callback
*inside* the GPU graph — the reference's biggest perf wart, SURVEY §4.5).

Here both run inside jit on fixed shapes: gt boxes arrive padded to
``MAX_GT_BOXES`` with a validity mask, subsampling uses ``jax.random``
(reproducible, device-side), and "choose K of M at random" becomes
"rank random priorities, keep the top K" — identical distribution, static
shapes.  Known, documented deviations from the reference:

- the per-gt-argmax fg rule only fires for gts with positive best overlap
  (the reference's ``overlaps == gt_max`` quirk marks *every* anchor fg
  for a gt with zero overlap everywhere);
- when fewer than ``BATCH_ROIS`` fg+bg candidates exist (pathological,
  e.g. tiny unit tests), remaining slots are filled with zero-weight
  ignore rois instead of the reference's sample-with-replacement padding.

Layout rule (``assign_anchor``, ``_random_keep_k``): everything of length N
is a dense ``(N,)`` plane (``(B, N)`` under ``vmap``, N on the TPU's lanes),
and nothing of length N is sorted, scattered or gathered by row.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.ops.boxes import (
    bbox_overlaps,
    bbox_transform,
    bbox_transform_planes,
)
from mx_rcnn_tpu.ops.losses import one_hot_select

_BIG = 1e9


def _random_keep_k(key, candidate_mask: jnp.ndarray, k, k_max: int) -> jnp.ndarray:
    """Keep a uniformly-random size-``min(k, n_candidates)`` subset.

    Returns a bool mask.  ``k`` may be a traced scalar, ``k_max`` is its
    static upper bound.  Ranks candidates by iid uniforms (non-candidates
    rank last) and keeps the first ``k`` of the ``k_max`` best: the set a
    full descending sort would keep, since ``top_k`` like the stable sort
    puts the lower index first among equal priorities.
    """
    n = candidate_mask.shape[0]
    k_max = min(k_max, n)
    priority = jax.random.uniform(key, (n,)) - (~candidate_mask) * 2.0
    _, best = jax.lax.top_k(priority, k_max)
    kept = jnp.zeros((n,), bool).at[best].set(
        jnp.arange(k_max) < k, unique_indices=True
    )
    return candidate_mask & kept


def bbox_denorm_vectors(cfg: Config, num_classes: int):
    """(4K,) de-normalization (means, stds) for test-time delta decode.

    The per-class tables flatten class-major — exactly the 4K
    class-specific layout ``sample_rois`` emits — so test forwards can
    keep their single elementwise multiply-add regardless of whether
    normalization was class-agnostic (end2end convention) or per-class
    (``add_bbox_regression_targets`` precomputed-stats parity).
    """
    t = cfg.TRAIN
    if t.BBOX_STDS_PER_CLASS is not None:
        means = jnp.asarray(t.BBOX_MEANS_PER_CLASS, jnp.float32).reshape(-1)
        stds = jnp.asarray(t.BBOX_STDS_PER_CLASS, jnp.float32).reshape(-1)
        assert means.shape == (4 * num_classes,), (
            f"per-class bbox stats shape {means.shape} != K={num_classes}"
        )
        return means, stds
    return (
        jnp.tile(jnp.asarray(t.BBOX_MEANS, jnp.float32), num_classes),
        jnp.tile(jnp.asarray(t.BBOX_STDS, jnp.float32), num_classes),
    )


class AnchorTargets(NamedTuple):
    labels: jnp.ndarray        # (N,) int32: 1 fg / 0 bg / -1 ignore
    bbox_targets: jnp.ndarray  # (N, 4) float32
    bbox_weights: jnp.ndarray  # (N, 4) float32


def assign_anchor(
    anchors: jnp.ndarray,
    gt_boxes: jnp.ndarray,
    gt_valid: jnp.ndarray,
    im_info: jnp.ndarray,
    key: jax.Array,
    cfg: Config,
    allowed_border: float = 0.0,
) -> AnchorTargets:
    """RPN anchor target assignment for one image, fully in-graph.

    ``anchors`` (N, 4) static table; ``gt_boxes`` (G, 4) padded;
    ``gt_valid`` (G,) mask; ``im_info`` = (h, w, scale) of the *unpadded*
    image.  Semantics follow ``rcnn/io/rpn.py :: assign_anchor``: only
    anchors inside the image participate; fg = per-gt best anchors plus
    IoU ≥ RPN_POSITIVE_OVERLAP; bg = IoU < RPN_NEGATIVE_OVERLAP; subsample
    to RPN_FG_FRACTION·RPN_BATCH_SIZE fg and the remainder bg.
    """
    t = cfg.TRAIN
    h, w = im_info[0], im_info[1]
    ax1, ay1, ax2, ay2 = (anchors[:, i] for i in range(4))       # (N,) planes

    inside = (
        (ax1 >= -allowed_border)
        & (ay1 >= -allowed_border)
        & (ax2 < w + allowed_border)
        & (ay2 < h + allowed_border)
    )

    overlaps = bbox_overlaps(anchors, gt_boxes[:, :4])          # (N, G)
    overlaps = jnp.where(gt_valid[None, :], overlaps, -1.0)
    overlaps = jnp.where(inside[:, None], overlaps, -1.0)
    max_ov = overlaps.max(axis=1)                               # (N,)
    argmax_gt = overlaps.argmax(axis=1)                         # (N,)
    gt_max_ov = overlaps.max(axis=0)                            # (G,)

    # per-gt best anchors (ties included), only for gts that touch anything
    is_gt_best = (
        (overlaps == gt_max_ov[None, :]) & (gt_max_ov[None, :] > 0) & gt_valid[None, :]
    ).any(axis=1)

    fg = inside & (is_gt_best | (max_ov >= t.RPN_POSITIVE_OVERLAP))
    bg = inside & (max_ov < t.RPN_NEGATIVE_OVERLAP) & ~fg
    if t.RPN_CLOBBER_POSITIVES:
        bg = inside & (max_ov < t.RPN_NEGATIVE_OVERLAP)
        fg = fg & ~bg

    k_fg, k_bg = jax.random.split(key)
    num_fg = int(t.RPN_FG_FRACTION * t.RPN_BATCH_SIZE)
    fg = _random_keep_k(k_fg, fg, num_fg, num_fg)
    bg = _random_keep_k(k_bg, bg, t.RPN_BATCH_SIZE - fg.sum(), t.RPN_BATCH_SIZE)

    labels = jnp.where(fg, 1, jnp.where(bg, 0, -1)).astype(jnp.int32)

    # the matched gt box as four (N,) planes, selected over the G axis
    # (exact: one value plus zeros), not a row gather into (N, 4)
    gt_planes = [one_hot_select(gt_boxes[None, :, i], argmax_gt) for i in range(4)]
    deltas = bbox_transform_planes((ax1, ay1, ax2, ay2), gt_planes)
    targets = jnp.stack([jnp.where(fg, d, 0.0) for d in deltas], axis=1)
    weights = jnp.stack(
        [jnp.where(fg, wt, 0.0) for wt in t.RPN_BBOX_WEIGHTS], axis=1
    )
    return AnchorTargets(labels, targets.astype(jnp.float32), weights)


class RoiSamples(NamedTuple):
    rois: jnp.ndarray          # (R, 4) float32, image coords
    labels: jnp.ndarray        # (R,) int32: class id, 0 = bg, -1 = ignore
    bbox_targets: jnp.ndarray  # (R, 4K) class-specific layout
    bbox_weights: jnp.ndarray  # (R, 4K)
    gt_index: jnp.ndarray      # (R,) int32: matched gt slot (the SAME
    #   assignment the label/bbox targets came from — mask targets must
    #   reuse it, not re-derive a fresh best-IoU argmax, or a roi labeled
    #   class A can be trained on a mask cropped from a different gt)


def sample_rois(
    rois: jnp.ndarray,
    rois_valid: jnp.ndarray,
    gt_boxes: jnp.ndarray,
    gt_valid: jnp.ndarray,
    key: jax.Array,
    cfg: Config,
) -> RoiSamples:
    """Sample BATCH_ROIS proposals for the RCNN head, fully in-graph.

    ``rois`` (P, 4) padded proposals; ``gt_boxes`` (G, 5) padded
    [x1, y1, x2, y2, cls].  Follows
    ``rcnn/io/rcnn.py :: sample_rois``: gt boxes are appended to the
    proposal set (so every gt is a candidate roi), fg = IoU ≥ FG_THRESH
    sampled to FG_FRACTION·BATCH_ROIS, bg = IoU ∈ [BG_THRESH_LO,
    BG_THRESH_HI) fills the rest; bbox targets are class-specific 4K
    layout normalized by BBOX_MEANS/STDS
    (``rcnn/processing/bbox_regression.py :: expand_bbox_regression_targets``).
    """
    t = cfg.TRAIN
    num_classes = cfg.dataset.NUM_CLASSES
    r_out = t.BATCH_ROIS

    # append gt boxes to the candidate pool (reference does exactly this)
    cand = jnp.concatenate([rois[:, :4], gt_boxes[:, :4]], axis=0)       # (P+G, 4)
    cand_valid = jnp.concatenate([rois_valid, gt_valid], axis=0)
    p = cand.shape[0]

    overlaps = bbox_overlaps(cand, gt_boxes[:, :4])                       # (P+G, G)
    overlaps = jnp.where(gt_valid[None, :], overlaps, -1.0)
    max_ov = overlaps.max(axis=1)
    argmax_gt = overlaps.argmax(axis=1)
    cls_of = gt_boxes[argmax_gt, 4].astype(jnp.int32)

    fg_cand = cand_valid & (max_ov >= t.FG_THRESH)
    bg_cand = (
        cand_valid & (max_ov < t.BG_THRESH_HI) & (max_ov >= t.BG_THRESH_LO) & ~fg_cand
    )

    k_fg, k_bg, k_tie = jax.random.split(key, 3)
    num_fg = int(round(t.FG_FRACTION * r_out))
    fg_sel = _random_keep_k(k_fg, fg_cand, num_fg, num_fg)
    bg_sel = _random_keep_k(k_bg, bg_cand, r_out - fg_sel.sum(), r_out)

    # pack: fg first, then bg, then ignore padding — fixed R_out rows.
    # LOAD-BEARING ordering: the Mask R-CNN branch (models/fpn.py::
    # _mask_loss) runs on only the first FG_FRACTION·BATCH_ROIS slots,
    # relying on every fg roi landing in that prefix
    sel_priority = jnp.where(fg_sel, 2.0 * _BIG, 0.0) + jnp.where(bg_sel, _BIG, 0.0)
    sel_priority = sel_priority + jax.random.uniform(k_tie, (p,))
    if p < r_out:  # static: fewer candidates than the roi budget (tiny tests)
        pad = r_out - p
        sel_priority = jnp.concatenate([sel_priority, jnp.full((pad,), -_BIG)])
        cand = jnp.concatenate([cand, jnp.zeros((pad, 4), cand.dtype)])
        fg_sel = jnp.concatenate([fg_sel, jnp.zeros((pad,), bool)])
        bg_sel = jnp.concatenate([bg_sel, jnp.zeros((pad,), bool)])
        cls_of = jnp.concatenate([cls_of, jnp.zeros((pad,), jnp.int32)])
        argmax_gt = jnp.concatenate([argmax_gt, jnp.zeros((pad,), argmax_gt.dtype)])
    _, idx = jax.lax.top_k(sel_priority, r_out)
    picked_fg = fg_sel[idx]
    picked_bg = bg_sel[idx]

    out_rois = cand[idx]
    labels = jnp.where(
        picked_fg, cls_of[idx], jnp.where(picked_bg, 0, -1)
    ).astype(jnp.int32)

    # bbox regression targets, normalized then expanded to 4K layout;
    # per-class tables (the reference's precomputed-normalization path)
    # override the class-agnostic vectors when present
    raw = bbox_transform(out_rois, gt_boxes[argmax_gt[idx], :4])
    if t.BBOX_STDS_PER_CLASS is not None:
        means_t = jnp.asarray(t.BBOX_MEANS_PER_CLASS, jnp.float32)   # (K, 4)
        stds_t = jnp.asarray(t.BBOX_STDS_PER_CLASS, jnp.float32)
        means = means_t[jnp.clip(labels, 0)]                         # (R, 4)
        stds = stds_t[jnp.clip(labels, 0)]
        raw = (raw - means) / stds
    else:
        means = jnp.asarray(t.BBOX_MEANS, jnp.float32)
        stds = jnp.asarray(t.BBOX_STDS, jnp.float32)
        raw = (raw - means[None, :]) / stds[None, :]
    raw = jnp.where(picked_fg[:, None], raw, 0.0)

    cls_onehot = jax.nn.one_hot(
        jnp.clip(labels, 0), num_classes, dtype=jnp.float32
    ) * picked_fg[:, None]                                                # (R, K)
    bbox_targets = (cls_onehot[:, :, None] * raw[:, None, :]).reshape(r_out, -1)
    bbox_weights = jnp.repeat(cls_onehot, 4, axis=1)
    return RoiSamples(
        out_rois, labels, bbox_targets, bbox_weights,
        argmax_gt[idx].astype(jnp.int32),
    )
