"""What decides ``correct`` for a serve cell.

Once the window has closed: a sample of the requests it answered (drawn
from the seed, the largest image among them), each with the detections
that came back through the engine.  The plain reference (``benchmark/
reference``: float32 at ``highest``, gather ROIAlign, sequential jnp NMS)
makes its own weights from the seed, prepares each image itself (resize,
uint8 quantisation, padding: a copy of the stated preprocessing) and
detects once per image.

Each served detection is held against the reference's candidates of its
class (every roi's decoded box and score before threshold, NMS and cap):
the forward pass, box and score.  The served list is held against the
reference's final list both ways and against itself: per-class NMS,
threshold, cap and count.  Greedy NMS and the cap turn a rounding-sized
change of one score into another kept set, so the lists are compared by
what accounts for each detection, not one to one; see :func:`readings`
for the six numbers.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np


# ---------------------------------------------------- stated preprocessing
def resize_scale(h: int, w: int, target: int, max_size: int) -> float:
    """Short side to ``target``, capped so the long side stays ≤ ``max_size``."""
    scale = float(target) / min(h, w)
    if round(scale * max(h, w)) > max_size:
        scale = float(max_size) / max(h, w)
    return scale


def resize_to_scale(im: np.ndarray, target: int, max_size: int):
    import cv2

    scale = resize_scale(im.shape[0], im.shape[1], target, max_size)
    im = cv2.resize(im, None, fx=scale, fy=scale,
                    interpolation=cv2.INTER_LINEAR)
    return im, scale


def prepare(im: np.ndarray, cfg, ladder):
    """Original RGB → (padded uint8 canvas, im_info, orig_hw)."""
    im = np.asarray(im, np.float32)
    orig_hw = im.shape[:2]
    target, max_size = cfg.dataset.SCALES[0]
    im, scale = resize_to_scale(im, target, max_size)
    h, w = im.shape[:2]
    fit = [b for b in ladder if b[0] >= h and b[1] >= w]
    bh, bw = min(fit, key=lambda b: b[0] * b[1])
    if cfg.TEST.UINT8_TRANSFER:
        im = np.clip(np.rint(im), 0, 255).astype(np.uint8)
    else:
        im = (im - np.asarray(cfg.network.PIXEL_MEANS, np.float32)) / \
            np.asarray(cfg.network.PIXEL_STDS, np.float32)
    canvas = np.zeros((bh, bw, 3), im.dtype)
    canvas[:h, :w] = im
    return canvas, np.array([h, w, scale], np.float32), \
        np.array(orig_hw, np.float32)


# ------------------------------------------------------------- reference
def reference_config(config: Dict[str, Any], overrides=None):
    from harness.check_train import apply_overrides
    from reference.config import generate_config

    return apply_overrides(
        generate_config(config["network"], config["dataset"]), overrides)


class ReferenceDetector:
    """The reference's detections for one image at a time (batch 1, one
    compile per ladder rung)."""

    def __init__(self, cfg, graph: str, seed: int, ladder,
                 round_to: Optional[str] = None, recipe=None):
        import jax
        import jax.numpy as jnp

        from reference.models import build_model
        from reference.ops.postprocess import make_test_postprocess
        from reference.precision import rounded_operands

        self.cfg, self.ladder = cfg, [tuple(b) for b in ladder]
        self._ctx = lambda: rounded_operands(round_to)
        self.model = build_model(cfg, graph)
        k = cfg.dataset.NUM_CLASSES
        te = cfg.TEST
        post = make_test_postprocess(cfg, k, te.SCORE_THRESH,
                                     max_out=te.DET_PER_CLASS)
        h, w = cfg.SHAPE_BUCKETS[0]
        with jax.default_matmul_precision("highest"):
            self.params = jax.jit(lambda: self.model.init(
                {"params": jax.random.key(seed)},
                jnp.zeros((1, h, w, 3), jnp.float32),
                jnp.array([[h, w, 1.0]], jnp.float32), train=False,
            )["params"])()
        from harness.weights import condition

        self.params = condition(self.params, recipe)

        def forward(params, images, im_info, orig_hw):
            from reference.ops.boxes import bbox_pred, clip_boxes

            out = self.model.apply({"params": params}, images, im_info,
                                   train=False)
            res = post(out, im_info, orig_hw)
            # every candidate before threshold, NMS and cap: (R, K, 4)
            # boxes in original coordinates, (R, K) scores
            info, ohw = im_info[0], orig_hw[0]
            boxes = clip_boxes(bbox_pred(out["rois"][0], out["bbox_deltas"][0]),
                               (info[0], info[1]))
            boxes = clip_boxes(boxes / info[2], (ohw[0], ohw[1]))
            res["cand_boxes"] = boxes.reshape(boxes.shape[0], -1, 4)
            res["cand_scores"] = out["cls_prob"][0]
            res["cand_valid"] = out["roi_valid"][0].astype(bool)
            return res

        self._forward = jax.jit(forward)

    def detect(self, im: np.ndarray) -> List[Optional[np.ndarray]]:
        import jax

        canvas, info, ohw = prepare(im, self.cfg, self.ladder)
        with jax.default_matmul_precision("highest"), self._ctx():
            res = jax.device_get(self._forward(
                self.params, canvas[None], info[None], ohw[None]))
        v = res["cand_valid"]
        self.last_candidates = (res["cand_boxes"][v], res["cand_scores"][v])
        k = self.cfg.dataset.NUM_CLASSES
        dets: List[Optional[np.ndarray]] = [None] * k
        for j in range(1, k):
            m = res["det_valid"][0][j - 1].astype(bool)
            dets[j] = np.hstack([
                res["det_boxes"][0][j - 1][m],
                res["det_scores"][0][j - 1][m][:, None],
            ]).astype(np.float32)
        self.last_uncapped = list(dets)
        return _cap(dets, self.cfg.TEST.MAX_PER_IMAGE)


# ------------------------------------------------------------ comparison
def iou_matrix(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """(n, 4) × (m, 4) → (n, m), the +1 pixel convention of the boxes."""
    x1 = np.maximum(a[:, None, 0], b[None, :, 0])
    y1 = np.maximum(a[:, None, 1], b[None, :, 1])
    x2 = np.minimum(a[:, None, 2], b[None, :, 2])
    y2 = np.minimum(a[:, None, 3], b[None, :, 3])
    inter = np.clip(x2 - x1 + 1, 0, None) * np.clip(y2 - y1 + 1, 0, None)
    area = lambda x: (x[:, 2] - x[:, 0] + 1) * (x[:, 3] - x[:, 1] + 1)
    return inter / (area(a)[:, None] + area(b)[None, :] - inter)


#: how two lists are held against each other; a cell's limits file may
#: state its own under ``match``
MATCH = {"iou": 0.5, "iou_margin": 0.05, "score_margin": 0.05}


def _classes(dets):
    """[(class, (n, 5) array)] of the classes that hold a detection."""
    return [(j, d) for j, d in enumerate(dets)
            if j and d is not None and len(d)]


def _all_scores(dets) -> np.ndarray:
    return np.concatenate([d[:, 4] for _j, d in _classes(dets)] + [np.zeros(0)])


def _unexplained(a, b, sign: float, nms: float, match):
    """→ (flags, scores) over the detections of list ``a``: is there no
    detection of its class in list ``b`` that accounts for it?  One
    accounts for it by being the same box (IoU ≥ ``match.iou``), or by
    standing in its way under greedy NMS: overlapping it by the NMS
    threshold less a margin, with a score that - give or take a margin -
    decides between the two the way it came out (``sign`` +1: the one in
    ``b`` won against ``a``'s; −1: it lost to it)."""
    flags, scores = [], []
    for j, d in _classes(a):
        o = b[j] if j < len(b) and b[j] is not None else np.zeros((0, 5))
        scores.append(d[:, 4])
        if not len(o):
            flags.append(np.ones(len(d), bool))
            continue
        iou = iou_matrix(d[:, :4], o[:, :4])
        same = (iou >= match["iou"]).any(axis=1)
        lead = sign * (o[None, :, 4] - d[:, None, 4])
        blocked = ((iou > nms - match["iou_margin"])
                   & (lead >= -match["score_margin"])).any(axis=1)
        flags.append(~(same | blocked))
    return (np.concatenate(flags + [np.zeros(0, bool)]),
            np.concatenate(scores + [np.zeros(0)]))


def readings(sample_dets, ref_dets, ref_candidates, rules: Dict[str, float],
             match: Optional[Dict[str, float]] = None) -> Dict[str, float]:
    """Every number compared, over the sample's images together.

    ``rules``: what the configuration states of the postprocess: ``nms``
    (per-class IoU threshold), ``score_thresh``, ``cap`` (detections an
    image).  ``ref_dets`` is the reference's final list of each image,
    ``ref_candidates`` its (boxes (R, K, 4), scores (R, K)) before
    threshold, NMS and cap.

    - ``cand_box_gap``    mean over served detections of 1 − the best IoU
                          with a reference candidate of the same class
    - ``cand_score_gap``  mean |score − that candidate's score|
    - ``nms_overlap``     the largest IoU between two served detections of
                          one class in one image, over the NMS threshold
    - ``missed_share``    share of the reference's firm detections (clear
                          of threshold and cap by the score margin) that
                          nothing served accounts for
    - ``extra_share``     share of the served detections clear of the
                          reference's cap cut that nothing in the
                          reference's list accounts for
    - ``count_gap``       |served − reference| detections over the
                          reference's: a cap that is skipped shows here
                          even where what it cuts scores next to nothing

    A detection is accounted for by the same box in the other list or by
    one that stands in its way under greedy NMS (see ``_unexplained``), so
    a near-tie that flips which of two boxes survives reads as nothing,
    and a skipped NMS, a missing cap, dropped or duplicated answers read
    as what they are.  Replies that are empty where the reference has
    detections read 1 in every gap."""
    match = dict(MATCH, **(match or {}))
    m_s = match["score_margin"]
    cand_box, cand_score, missed, extra = [], [], [], []
    overlap = 0.0
    n_served = n_ref = 0
    for got, ref, (boxes, scores) in zip(sample_dets, ref_dets, ref_candidates):
        n_ref += len(_all_scores(ref))
        n_served += len(_all_scores(got))
        for j, g in _classes(got):
            if len(boxes):
                iou = iou_matrix(g[:, :4], boxes[:, j])
                best = iou.argmax(axis=1)
                cand_box += list(1.0 - iou[np.arange(len(g)), best])
                cand_score += list(np.abs(g[:, 4] - scores[best, j]))
            else:
                cand_box += [1.0] * len(g)
                cand_score += [1.0] * len(g)
            if len(g) > 1:
                pair = iou_matrix(g[:, :4], g[:, :4])
                overlap = max(overlap, float(np.triu(pair, 1).max()))
        # the score of the last detection the reference's cap let through;
        # a detection within the margin of it (or of the threshold) stands
        # or falls with a rounding, and counts on neither side
        ref_scores = np.sort(_all_scores(ref))[::-1]
        capped = rules["cap"] > 0 and len(ref_scores) >= rules["cap"]
        cut = max(float(ref_scores[-1]) if capped else 0.0,
                  rules["score_thresh"])
        flags, scores = _unexplained(ref, got, 1.0, rules["nms"], match)
        missed += list(flags[scores >= cut + m_s])
        flags, scores = _unexplained(got, ref, -1.0, rules["nms"], match)
        extra += list(flags[np.abs(scores - cut) > m_s])
    mean = lambda v, empty: float(np.mean(v)) if len(v) else empty
    nothing = 1.0 if n_ref and not n_served else 0.0
    return {
        "cand_box_gap": mean(cand_box, nothing),
        "cand_score_gap": mean(cand_score, nothing),
        "nms_overlap": max(0.0, overlap - rules["nms"]),
        "missed_share": mean(missed, nothing),
        "extra_share": mean(extra, nothing),
        "count_gap": abs(n_served - n_ref) / max(n_ref, 1),
        "n_served": n_served, "n_reference": n_ref,
        "n_firm": len(missed),
    }


def rules_of(cfg) -> Dict[str, float]:
    """What the configuration states of the test-time postprocess."""
    te = cfg.TEST
    return {"nms": float(te.NMS), "score_thresh": float(te.SCORE_THRESH),
            "cap": int(te.MAX_PER_IMAGE)}


# -------------------------------------------------------- planted faults
def _cap(dets, n: int):
    scores = _all_scores(dets)
    if n <= 0 or len(scores) <= n:
        return dets
    cut = np.sort(scores)[-n]
    return [None] + [d if d is None else d[d[:, 4] >= cut] for d in dets[1:]]


def _greedy(cands, rules, thresh: Optional[float]):
    """The reference's candidates through threshold, greedy per-class NMS
    at ``thresh`` (None: skipped) and the cap."""
    boxes, scores = cands
    dets: List[Optional[np.ndarray]] = [None]
    for j in range(1, scores.shape[1]):
        m = scores[:, j] >= rules["score_thresh"]
        b, sc = boxes[m, j], scores[m, j]
        order = np.argsort(-sc)
        b, sc = b[order], sc[order]
        keep: List[int] = []
        for k in range(len(b)):
            if (thresh is None or not keep
                    or iou_matrix(b[k:k + 1], b[keep]).max() <= thresh):
                keep.append(k)
        dets.append(np.hstack([b[keep], sc[keep][:, None]]).astype(np.float32))
    return _cap(dets, rules["cap"])


def _each(dets, f):
    return [None] + [d if d is None else f(d) for d in dets[1:]]


#: faults of the postprocess and of the answer, each put in the engine's
#: place: ``f(served, uncapped, candidates, rules)`` → a reply.  The first
#: three rebuild the reply from the reference's own candidates (its NMS
#: skipped, at 0.5, its cap skipped); the others alter what was served.
FAULTS = {
    "nms_off": lambda s, u, c, r: _greedy(c, r, None),
    "nms_at_0.5": lambda s, u, c, r: _greedy(c, r, 0.5),
    "cap_off": lambda s, u, c, r: u,
    "half_dropped": lambda s, u, c, r: _each(s, lambda d: d[::2]),
    "duplicated": lambda s, u, c, r: _each(s, lambda d: np.vstack([d, d])),
    "scores_x0.8": lambda s, u, c, r: _each(
        s, lambda d: d * np.array([1, 1, 1, 1, 0.8], np.float32)),
    "boxes_moved_30px": lambda s, u, c, r: _each(
        s, lambda d: d + np.array([30, 0, 30, 0, 0], np.float32)),
    "empty": lambda s, u, c, r: _each(s, lambda d: d[:0]),
}


def all_readings(raw: Dict[str, Any], rules, match=None) -> Dict[str, Any]:
    """One seed's served answers, the control and every planted fault
    through :func:`readings`.  ``raw``: what ``calibrate`` keeps."""
    ref, cands = raw["reference"], raw["candidates"]
    out = {"program": readings(raw["served"], ref, cands, rules, match)}
    if "control" in raw:
        out["control"] = readings(raw["control"], ref, cands, rules, match)
    for name, plant in FAULTS.items():
        bad = [plant(s, u, c, rules) for s, u, c in zip(
            raw["served"], raw["reference_uncapped"], cands)]
        out[f"fault.{name}"] = readings(bad, ref, cands, rules, match)
    return out


def check(cell, check_input: Dict[str, Any], overrides=None):
    from harness.check_train import compare

    cfg = reference_config(cell.config, overrides)
    det = ReferenceDetector(
        cfg, cell.config["model"]["graph"], check_input["seed"],
        check_input["ladder"], recipe=cell.traffic.get("weights"))
    ref, cands = [], []
    for s in check_input["sample"]:
        ref.append(det.detect(s["image"]))
        cands.append(det.last_candidates)
    read = readings([s["dets"] for s in check_input["sample"]], ref, cands,
                    rules_of(cfg), cell.limits.get("match"))
    detail = {k: read[k] for k in ("n_served", "n_reference", "n_firm")}
    detail["requests"] = [s["i"] for s in check_input["sample"]]
    return compare(read, cell.limits["limits"]), detail


def calibrate(cell, run, with_control: bool, overrides=None) -> Dict[str, Any]:
    """Readings of one seed for ``tools/calibrate.py``: the engine's
    answers, the control (on the seeds that ask for it) and every planted
    fault.  ``raw`` holds what was compared (served, reference,
    candidates, control), so the readings can be worked out again from the
    chip's own answers without the chip (``calibrate.py --replay``)."""
    import time

    ci = run["check_input"]
    cfg = reference_config(cell.config, overrides)
    graph = cell.config["model"]["graph"]
    recipe = cell.traffic.get("weights")
    t = time.monotonic()
    det = ReferenceDetector(cfg, graph, ci["seed"], ci["ladder"], recipe=recipe)
    raw = {"seed": ci["seed"], "served": [s["dets"] for s in ci["sample"]],
           "reference": [], "reference_uncapped": [], "candidates": [],
           "image_hw": [s["image"].shape[:2] for s in ci["sample"]]}
    for s in ci["sample"]:
        raw["reference"].append(det.detect(s["image"]))
        raw["candidates"].append(det.last_candidates)
        raw["reference_uncapped"].append(det.last_uncapped)
    reference_s = time.monotonic() - t
    if with_control:
        ctl = ReferenceDetector(cfg, graph, ci["seed"], ci["ladder"],
                                round_to=cell.limits["control"], recipe=recipe)
        raw["control"] = [ctl.detect(s["image"]) for s in ci["sample"]]
    out = all_readings(raw, rules_of(cfg), cell.limits.get("match"))
    out.update(reference_s=reference_s, raw=raw)
    return out
