"""The stated synthetic data and preprocessing, as the reference makes
them itself: the roidb of ``--synthetic N`` (records drawn from a fixed
seed, x-flipped copies appended), the render of one record, the resize to
the configuration's scale, normalisation, padding into the bucket, and
the gt arrays scaled with the image.  A copy of what
``mx_rcnn_tpu/data/{synthetic,imdb,image,loader}.py`` state, importing
nothing of the program, so that a train check can rebuild the batch a
step was fed from the record numbers alone and hold the loader to it.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np


def synthetic_roidb(num_images: int, num_classes: int, flip: bool,
                    image_size: Tuple[int, int] = (480, 640),
                    max_boxes: int = 4, seed: int = 0) -> List[Dict]:
    rng = np.random.RandomState(seed)
    h, w = image_size
    roidb = []
    for i in range(num_images):
        n = rng.randint(1, max_boxes + 1)
        boxes, classes = [], []
        for _ in range(n):
            bw = rng.randint(60, w // 2)
            bh = rng.randint(60, h // 2)
            x1 = rng.randint(0, w - bw)
            y1 = rng.randint(0, h - bh)
            boxes.append([x1, y1, x1 + bw - 1, y1 + bh - 1])
            classes.append(rng.randint(1, num_classes))
        roidb.append({
            "height": h, "width": w,
            "boxes": np.asarray(boxes, np.float32),
            "gt_classes": np.asarray(classes, np.int32),
            "flipped": False, "synthetic_seed": seed + 1000 + i,
        })
    if flip:
        flipped = []
        for rec in roidb:
            boxes = rec["boxes"].copy()
            boxes[:, 0] = rec["width"] - rec["boxes"][:, 2] - 1
            boxes[:, 2] = rec["width"] - rec["boxes"][:, 0] - 1
            flipped.append(dict(rec, boxes=boxes, flipped=True))
        roidb = roidb + flipped
    return roidb


def class_color(cls: int) -> np.ndarray:
    """Golden-ratio hue spacing at value 235, saturation 0.85."""
    hue = ((cls - 1) * 0.61803398875) % 1.0
    i = int(hue * 6.0)
    f = hue * 6.0 - i
    v, s = 235.0, 0.85
    p, q, t = v * (1 - s), v * (1 - s * f), v * (1 - s * (1 - f))
    rgb = [(v, t, p), (q, v, p), (p, v, t), (p, q, v), (t, p, v), (v, p, q)][i % 6]
    return np.asarray(rgb, np.float32)


def render(rec: Dict) -> np.ndarray:
    """Noise background 90..150, each box filled with its class's colour
    plus noise, from the record's own (possibly flipped) geometry."""
    rng = np.random.RandomState(rec["synthetic_seed"])
    h, w = rec["height"], rec["width"]
    im = rng.rand(h, w, 3).astype(np.float32) * 60.0 + 90.0
    for box, cls in zip(rec["boxes"], rec["gt_classes"]):
        x1, y1, x2, y2 = box.astype(int)
        im[y1:y2 + 1, x1:x2 + 1] = class_color(int(cls)) + rng.rand(
            y2 - y1 + 1, x2 - x1 + 1, 3).astype(np.float32) * 10.0
    return im


def prepare(im: np.ndarray, cfg, bucket: Tuple[int, int]):
    """Short side to the scale's target, long side capped; normalised;
    zero-padded bottom and right.  → (canvas, im_info)."""
    import cv2

    target, max_size = cfg.dataset.SCALES[0]
    h, w = im.shape[:2]
    scale = float(target) / min(h, w)
    if round(scale * max(h, w)) > max_size:
        scale = float(max_size) / max(h, w)
    im = cv2.resize(im, None, fx=scale, fy=scale,
                    interpolation=cv2.INTER_LINEAR)
    im = (im - np.asarray(cfg.network.PIXEL_MEANS, np.float32)) / np.asarray(
        cfg.network.PIXEL_STDS, np.float32)
    h, w = im.shape[:2]
    canvas = np.zeros(tuple(bucket) + (3,), np.float32)
    canvas[:h, :w] = im
    return canvas, np.array([h, w, scale], np.float32)


def make_batch(roidb: List[Dict], rows: Sequence[int], cfg,
               bucket: Tuple[int, int]) -> Dict[str, np.ndarray]:
    """The train batch of records ``rows``; each row's sampling seed is
    its record number."""
    g = cfg.dataset.MAX_GT_BOXES
    n = len(rows)
    out = {
        "images": np.zeros((n,) + tuple(bucket) + (3,), np.float32),
        "im_info": np.zeros((n, 3), np.float32),
        "gt_boxes": np.zeros((n, g, 5), np.float32),
        "gt_valid": np.zeros((n, g), bool),
        "sample_seeds": np.asarray(rows, np.int32),
    }
    for i, r in enumerate(rows):
        rec = roidb[int(r)]
        out["images"][i], out["im_info"][i] = prepare(render(rec), cfg, bucket)
        k = min(len(rec["boxes"]), g)
        out["gt_boxes"][i, :k, :4] = rec["boxes"][:k] * out["im_info"][i, 2]
        out["gt_boxes"][i, :k, 4] = rec["gt_classes"][:k]
        out["gt_valid"][i, :k] = True
    return out


def batch_gap(fed: Dict[str, np.ndarray], own: Dict[str, np.ndarray]) -> float:
    """The largest difference between a batch as the step was fed it and
    the same rows rebuilt here; infinite where the two do not line up or
    two rows are the same record."""
    if set(fed) != set(own):
        return float("inf")
    if len(set(np.asarray(fed["sample_seeds"]).tolist())) != len(fed["sample_seeds"]):
        return float("inf")
    worst = 0.0
    for k, v in own.items():
        f = np.asarray(fed[k])
        if f.shape != v.shape:
            return float("inf")
        worst = max(worst, float(np.max(np.abs(
            f.astype(np.float64) - v.astype(np.float64)), initial=0.0)))
    return worst
