"""Host-side gt mask rasterization into box-frame bitmaps.

Reference: the mask plumbing of the ``rcnn/pycocotools`` lineage
(``maskApi.c`` / ``_mask.pyx`` — SURVEY N5): upstream descendants decode
COCO polygons/RLE to full-image bitmaps and crop per roi on device.  The
TPU-first rework avoids full-image mask tensors entirely: each gt is
rasterized ONCE, at roidb-load/batch time, into a small M×M bitmap over
its own gt box ("box frame"), and the in-graph target op
(``ops/mask_targets.py::crop_resize_masks``) bilinearly resamples that
bitmap under each matched roi's S×S grid.  A (B, G, M, M) uint8 tensor
replaces (B, G, H, W) — ~100× less HBM and host→device traffic at M=64 — and the
device-side crop is two matmuls per roi instead of gathers.

Supported ``segmentation`` record formats (the COCO instance formats):
- list of polygons ``[[x1, y1, x2, y2, ...], ...]`` (continuous image
  coordinates, pixel p covering [p, p+1));
- an RLE dict ``{"size": [h, w], "counts": [...]}`` (crowd regions —
  excluded from training by ``data/coco.py``, handled here anyway for
  completeness).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np

from mx_rcnn_tpu.native import rle as rlelib


def polygons_to_box_frame(
    segm, box: Sequence[float], m: int
) -> np.ndarray:
    """One gt's ``segmentation`` → (m, m) uint8 bitmap over its own box.

    ``box`` = [x1, y1, x2, y2] inclusive pixel indices (+1 widths).  The
    bitmap's cell (r, c) covers the continuous region
    [x1 + c/m·w, x1 + (c+1)/m·w) × [y1 + r/m·h, ...): polygon vertices
    are affinely mapped into that frame and filled by the native even-odd
    scanline rasterizer on cell centers — the same convention
    ``crop_resize_masks`` samples under.
    """
    x1, y1, x2, y2 = (float(v) for v in box[:4])
    w = max(x2 - x1 + 1.0, 1.0)
    h = max(y2 - y1 + 1.0, 1.0)
    if isinstance(segm, dict):  # RLE: decode, crop, nearest-resize
        full = rle_to_bitmap(segm)
        return _crop_resize_bitmap(full, (x1, y1, x2, y2), m)
    polys = []
    for poly in segm:
        p = np.asarray(poly, np.float64).reshape(-1, 2)
        if len(p) < 3:
            continue
        q = np.empty_like(p)
        q[:, 0] = (p[:, 0] - x1) / w * m
        q[:, 1] = (p[:, 1] - y1) / h * m
        polys.append(q.reshape(-1))
    if not polys:
        return np.ones((m, m), np.uint8)  # degenerate → rectangle fallback
    return rlelib.decode(rlelib.from_polygons(polys, m, m))


def rle_to_bitmap(segm: Dict) -> np.ndarray:
    """RLE dict → (h, w) uint8 bitmap.  Handles compressed string counts
    (``ensure_list_counts``) and the lazy ``hflip`` tag
    ``flip_segmentations`` sets instead of eagerly re-encoding."""
    norm = rlelib.ensure_list_counts(
        {"size": segm["size"], "counts": segm["counts"]}
    )
    full = rlelib.decode(norm)
    if segm.get("hflip"):
        full = full[:, ::-1]
    return full


def _crop_resize_bitmap(full: np.ndarray, box, m: int) -> np.ndarray:
    """Nearest-neighbor crop-resize of a full-image bitmap to the box
    frame (the RLE-crowd path; polygons never take this)."""
    x1, y1, x2, y2 = box
    hh, ww = full.shape
    w = max(x2 - x1 + 1.0, 1.0)
    h = max(y2 - y1 + 1.0, 1.0)
    cols = np.clip((x1 + (np.arange(m) + 0.5) / m * w).astype(int), 0, ww - 1)
    rows = np.clip((y1 + (np.arange(m) + 0.5) / m * h).astype(int), 0, hh - 1)
    return full[np.ix_(rows, cols)].astype(np.uint8)


def record_gt_masks(
    rec: Dict, max_gt: int, m: int
) -> Optional[np.ndarray]:
    """roidb record → (max_gt, m, m) uint8 box-frame bitmaps, or None
    when the record carries no ``segmentation`` (box-only dataset — the
    model then falls back to rectangle targets).

    Boxes and polygons are both stored pre-flipped by
    ``append_flipped_images``, so no flip handling is needed here; the
    bitmaps are resolution-independent (the box frame is relative), so
    the loader's resize scale does not touch them.

    Rasterization runs once per batch assembly (not cached on the
    record): the native scanline fill costs a few µs per gt at M=64,
    ~1000× less than the JPEG decode sharing the same prefetch path,
    while caching bitmaps across a COCO-scale roidb would pin GBs of
    host RAM.
    """
    segms = rec.get("segmentation")
    if segms is None:
        return None
    out = np.zeros((max_gt, m, m), np.uint8)
    for i, (segm, box) in enumerate(zip(segms, rec["boxes"])):
        if i >= max_gt:
            break
        if segm is None:
            out[i] = 1  # this gt has no mask → rectangle
        else:
            out[i] = polygons_to_box_frame(segm, box, m)
    return out


def flip_segmentations(segms, width: int):
    """x-flip a record's segmentation list.  Polygons flip eagerly
    (x ↦ width − x in continuous coordinates — an array op); RLE dicts
    flip LAZILY via an ``hflip`` tag consumed by :func:`rle_to_bitmap`,
    so flip-time roidb preparation never pays a full-image decode +
    re-encode per annotation.  The even-odd fill is winding-insensitive,
    so reversed polygon orientation after flipping is harmless."""
    if segms is None:
        return None
    out = []
    for segm in segms:
        if segm is None:
            out.append(None)
        elif isinstance(segm, dict):
            out.append(
                {
                    "size": segm["size"],
                    "counts": segm["counts"],
                    "hflip": not segm.get("hflip", False),
                }
            )
        else:
            flipped = []
            for poly in segm:
                p = np.asarray(poly, np.float64).copy()
                p[0::2] = width - p[0::2]
                flipped.append(p)
            out.append(flipped)
    return out
