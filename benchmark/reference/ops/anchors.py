"""Anchor generation.

Reference: ``rcnn/processing/generate_anchor.py :: generate_anchors`` (the
classic py-faster-rcnn enumeration via ``_whctrs/_mkanchors/_ratio_enum/
_scale_enum``).  Behaviorally identical output; implemented as one
vectorized numpy routine because anchors are a compile-time constant on
TPU — they're baked into the jitted graph, never computed on device.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np


def generate_anchors(
    base_size: int = 16,
    ratios: Sequence[float] = (0.5, 1.0, 2.0),
    scales: Sequence[int] = (8, 16, 32),
) -> np.ndarray:
    """Return (A, 4) anchor windows [x1, y1, x2, y2] around (0, 0).

    Matches the classic algorithm: start from the [0, 0, 15, 15] base box,
    enumerate aspect ratios preserving (rounded) area, then scale each.
    Uses the legacy +1 width/height convention throughout.
    """
    ratios = np.asarray(ratios, dtype=np.float64)
    scales = np.asarray(scales, dtype=np.float64)

    w = h = float(base_size)
    x_ctr = 0.5 * (w - 1.0)
    y_ctr = 0.5 * (h - 1.0)

    # ratio enumeration: round(sqrt(area / ratio)) widths
    size = w * h
    size_ratios = size / ratios
    ws = np.round(np.sqrt(size_ratios))            # (R,)
    hs = np.round(ws * ratios)                     # (R,)

    # scale enumeration applied to every ratio anchor
    ws = (ws[:, None] * scales[None, :]).reshape(-1)   # (R*S,)
    hs = (hs[:, None] * scales[None, :]).reshape(-1)

    anchors = np.stack(
        [
            x_ctr - 0.5 * (ws - 1.0),
            y_ctr - 0.5 * (hs - 1.0),
            x_ctr + 0.5 * (ws - 1.0),
            y_ctr + 0.5 * (hs - 1.0),
        ],
        axis=1,
    )
    return anchors.astype(np.float32)


def shifted_anchors(
    feat_height: int,
    feat_width: int,
    feat_stride: int = 16,
    base_anchors: np.ndarray | None = None,
    ratios: Sequence[float] = (0.5, 1.0, 2.0),
    scales: Sequence[int] = (8, 16, 32),
) -> np.ndarray:
    """All anchors on an H×W feature grid: (H*W*A, 4), row-major over
    (y, x, anchor) — the per-pixel layout the RPN head emits.

    Reference: the shift-enumeration prologue of
    ``rcnn/symbol/proposal.py :: ProposalOperator.forward`` and
    ``rcnn/io/rpn.py :: assign_anchor``.
    """
    if base_anchors is None:
        base_anchors = generate_anchors(feat_stride, ratios, scales)
    shift_x = np.arange(feat_width, dtype=np.float32) * feat_stride
    shift_y = np.arange(feat_height, dtype=np.float32) * feat_stride
    sx, sy = np.meshgrid(shift_x, shift_y)  # (H, W)
    shifts = np.stack([sx, sy, sx, sy], axis=-1).reshape(-1, 1, 4)  # (H*W,1,4)
    all_anchors = shifts + base_anchors[None, :, :]                 # (H*W,A,4)
    return all_anchors.reshape(-1, 4).astype(np.float32)
