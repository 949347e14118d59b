"""Device-side eval post-processing: per-class decode + NMS in one jit.

Reference: the HOST loop in ``rcnn/core/tester.py :: pred_eval`` — per
class: threshold, stack [boxes|score], ``cpu_nms``.  On a weak-host TPU
deployment that loop is the eval bottleneck twice over: the full
``(B, R, K)`` + ``(B, R, 4K)`` head outputs cross the device→host link
(76 MB/batch at flagship shapes), and the per-class C NMS runs K−1 times per image on
one core.  Here the whole thing is a batched device program — decode →
clip → per-class NMS (vmap over classes × images, the Pallas kernel on
TPU) — and only the per-class keep lists (≈0.5 MB/batch) come back.

Equivalence with the host path (asserted in
``tests/test_postprocess.py``): below-threshold and padding rows are
excluded BEFORE suppression (they neither survive nor suppress — same
as the host's pre-filter), and the decode → resized-clip → /scale →
original-extent-clip chain runs ON DEVICE before NMS.  The last step
matters: under the +1 pixel convention IoU is NOT scale-invariant
(areas pick up +1 at whichever scale they're measured), so suppressing
in resized coordinates would flip borderline keep decisions vs the
reference host loop — NMS must see original-space boxes, which is why
eval batches carry ``orig_hw``.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.ops.boxes import bbox_pred, clip_boxes
from mx_rcnn_tpu.ops.nms import batched_class_nms

_NEG_INF = -1e10


def make_test_postprocess(
    cfg: Config, num_classes: int, thresh: float, max_out: int = 100,
    paste: bool = False,
):
    """→ jittable ``fn(out, im_info, orig_hw) -> {det_boxes, det_scores,
    det_valid}`` with shapes (B, K−1, max_out, ·); class j's detections
    live at row j−1 (background has none).  Boxes are in ORIGINAL image
    coordinates (``orig_hw`` (B, 2) = pre-resize heights/widths, shipped
    by TestLoader).

    Mask models: when ``out`` carries ``mask_logits`` (B, R, S, S, K),
    the same program additionally gathers — still on device — each
    surviving detection's S×S grid for its predicted class, for the
    cross-class top ``max_det = TEST.MAX_PER_IMAGE`` survivors by score
    (the per-image cap the host applies anyway in ``cap_detections``).
    Three fixed-shape outputs ride along: ``det_masks`` (B, max_det,
    S, S) float32 LOGITS (sigmoid stays host so the bits match the
    reference ``im_detect`` numpy expression exactly), ``det_mask_idx``
    (B, max_det) int32 flat index ``(class_row)*max_out + slot`` into
    the det grid (−1 on padding), and ``det_mask_valid`` (B, max_det).
    Only these come over the wire — the raw ``(R, S, S, K)`` stack never
    leaves the device.  ``max_det`` is static, so the CompileCache
    bucket ladder stays zero-recompile.

    ``paste=True`` (streaming mask serving): the program ADDITIONALLY
    pastes each survivor's grid into its box footprint on a fixed
    ``det_canvas`` (B, max_det, Hc, Wc) uint8 binary canvas, where
    (Hc, Wc) is the padded bucket extent (``batched`` gains a trailing
    ``canvas_hw`` argument, supplied by the Predictor from the traced
    image shape — one canvas shape per `(model, bucket)` rung, so the
    zero-recompile ladder is untouched).  Boxes are mapped to CANVAS
    (= resized-image) coordinates by ``im_info[2]`` and the grid is
    bilinearly resized to the box's pixel extent (floor/ceil +1
    convention of ``eval/segm.py::paste_mask``) then thresholded at
    probability 0.5 — i.e. logit 0: interpolation runs in logit space,
    where prob 0.5 is exactly the zero crossing.  All paste arithmetic
    is INTEGER fixed point (8 fractional bits on the quantized logits,
    7 on the interpolation weights — int32 throughout, no overflow by
    construction), so the device canvas is bitwise identical to the
    numpy mirror ``eval/segm.py::paste_mask_canvas`` on every backend:
    the RLE byte-identity of tests/test_streaming.py::TestCanvasParity
    is structural, not float luck."""
    te = cfg.TEST
    max_det = te.MAX_PER_IMAGE if te.MAX_PER_IMAGE > 0 \
        else (num_classes - 1) * max_out
    # the det grid only holds (K-1)*max_out candidates — a larger cap
    # would make top_k's k exceed its operand
    max_det = min(max_det, (num_classes - 1) * max_out)

    def one_image(rois, valid, scores, deltas, info, ohw):
        r, k = scores.shape
        with jax.named_scope("decode"):
            boxes = bbox_pred(rois, deltas)                      # (R, 4K)
            boxes = clip_boxes(boxes, (info[0], info[1]))
            boxes = clip_boxes(boxes / info[2], (ohw[0], ohw[1]))
            # foreground classes on the leading axis for the shared
            # batched per-class NMS helper
            boxes_k = boxes.reshape(r, k, 4).transpose(1, 0, 2)[1:]   # (K-1, R, 4)
            scores_k = scores.T[1:]                                   # (K-1, R)
            valid_k = valid[None, :] & (scores_k > thresh)
        with jax.named_scope("class_nms"):
            return batched_class_nms(
                boxes_k, scores_k, te.NMS, max_out, valid_k, with_idx=True
            )

    def one_image_masks(ob, os_, ov, oi, mask_logits):
        # (K-1, max_out) det grid → flat cross-class top-max_det by
        # score; ties break toward the lower flat index (top_k), which
        # only diverges from the host cap on exact float score ties.
        r = mask_logits.shape[0]
        with jax.named_scope("cap"):
            flat_scores = jnp.where(ov, os_, _NEG_INF).reshape(-1)
            top_s, top_flat = jax.lax.top_k(flat_scores, max_det)
            mvalid = top_s > _NEG_INF / 2
        with jax.named_scope("mask_select"):
            # survivor's source roi (per-class nms idx may exceed R on
            # padding slots — clamp before the gather) and class channel
            roi_idx = jnp.clip(oi.reshape(-1)[top_flat], 0, r - 1)
            roi_idx = jnp.where(mvalid, roi_idx, 0)
            cls = jnp.where(mvalid, top_flat // ov.shape[1] + 1, 1)
            grids = jax.vmap(lambda ri, c: mask_logits[ri, :, :, c])(
                roi_idx, cls
            )
            # large-negative logits on padding rows: padding-count invariant
            # AND safe if one ever leaks to paste (sigmoid ≈ 0, empty mask,
            # no exp overflow on host)
            grids = jnp.where(
                mvalid[:, None, None], grids, jnp.float32(-80.0)
            ).astype(jnp.float32)
            midx = jnp.where(mvalid, top_flat, -1).astype(jnp.int32)
        return grids, midx, mvalid

    def one_image_paste(ob, oi_flat, grids, mvalid, info, canvas_hw):
        # fixed-size-canvas device paste: each survivor's S×S logit
        # grid → binary mask in its box footprint on the (Hc, Wc)
        # bucket canvas.  Every arithmetic step below is mirrored
        # op-for-op by eval/segm.py::paste_mask_canvas; the bilinear
        # blend itself is int32 fixed point, so the two are bitwise
        # equal by construction (see make_test_postprocess docstring).
        hc, wc = canvas_hw
        s = grids.shape[1]
        # survivor boxes in canvas (= resized-image) coordinates:
        # original coords × im_info scale, clipped to the canvas — the
        # clip guarantees the floor/ceil footprint stays inside it
        bf = ob.reshape(-1, 4)
        box = bf[jnp.clip(oi_flat, 0, bf.shape[0] - 1)] * info[2]
        x1 = jnp.clip(box[:, 0], 0.0, wc - 1.0)
        y1 = jnp.clip(box[:, 1], 0.0, hc - 1.0)
        x2 = jnp.clip(box[:, 2], 0.0, wc - 1.0)
        y2 = jnp.clip(box[:, 3], 0.0, hc - 1.0)
        x1i = jnp.floor(x1).astype(jnp.int32)
        y1i = jnp.floor(y1).astype(jnp.int32)
        x2i = jnp.ceil(x2).astype(jnp.int32)
        y2i = jnp.ceil(y2).astype(jnp.int32)
        bw = jnp.maximum(x2i - x1i + 1, 1)
        bh = jnp.maximum(y2i - y1i + 1, 1)
        # quantize logits once: 8 fractional bits, |logit| capped at 60
        # (sigmoid there is 1 to float precision anyway) → |q| ≤ 2^14
        q = jnp.round(
            jnp.clip(grids, -60.0, 60.0) * jnp.float32(256.0)
        ).astype(jnp.int32)

        def paste_one(qd, bx1, by1, bx2, by2, bwd, bhd, ok):
            xs = jnp.arange(wc, dtype=jnp.int32)
            ys = jnp.arange(hc, dtype=jnp.int32)

            def axis(coords, lo, extent):
                # cv2-convention half-pixel source mapping dst → src,
                # border-replicate clamped; weights quantized to 7 bits
                d = (coords - lo).astype(jnp.float32)
                t = (d + jnp.float32(0.5)) * jnp.float32(s) \
                    / extent.astype(jnp.float32) - jnp.float32(0.5)
                sc = jnp.clip(t, 0.0, s - 1.0)
                i0 = jnp.floor(sc).astype(jnp.int32)
                i1 = jnp.minimum(i0 + 1, s - 1)
                w = jnp.round(
                    (sc - i0.astype(jnp.float32)) * jnp.float32(128.0)
                ).astype(jnp.int32)
                return i0, i1, w

            x0, x1b, wx = axis(xs, bx1, bwd)
            y0, y1b, wy = axis(ys, by1, bhd)
            q00 = qd[y0][:, x0]
            q01 = qd[y0][:, x1b]
            q10 = qd[y1b][:, x0]
            q11 = qd[y1b][:, x1b]
            val = (128 - wy)[:, None] * (
                (128 - wx)[None, :] * q00 + wx[None, :] * q01
            ) + wy[:, None] * (
                (128 - wx)[None, :] * q10 + wx[None, :] * q11
            )
            inside = (
                (xs >= bx1) & (xs <= bx2)
            )[None, :] & ((ys >= by1) & (ys <= by2))[:, None]
            return ((val >= 0) & inside & ok).astype(jnp.uint8)

        return jax.vmap(paste_one)(q, x1i, y1i, x2i, y2i, bw, bh, mvalid)

    def batched(out: Dict, im_info, orig_hw, canvas_hw=None):
        # stage scopes of the serve graph's tail, as a device trace is
        # read by them (utils/tracing.py :: SERVE_SCOPES): metadata only
        with jax.named_scope("postprocess"):
            return _batched(out, im_info, orig_hw, canvas_hw)

    def _batched(out: Dict, im_info, orig_hw, canvas_hw):
        ob, os_, ov, oi = jax.vmap(one_image)(
            out["rois"],
            out["roi_valid"].astype(bool),
            out["cls_prob"],
            out["bbox_deltas"],
            im_info,
            orig_hw,
        )
        res = {"det_boxes": ob, "det_scores": os_, "det_valid": ov}
        if "mask_logits" in out:
            grids, midx, mvalid = jax.vmap(one_image_masks)(
                ob, os_, ov, oi, out["mask_logits"]
            )
            res["det_masks"] = grids
            res["det_mask_idx"] = midx
            res["det_mask_valid"] = mvalid
            if paste and canvas_hw is not None:
                with jax.named_scope("mask_paste"):
                    res["det_canvas"] = jax.vmap(
                        lambda b, i, g, m, info: one_image_paste(
                            b, i, g, m, info, tuple(canvas_hw)
                        )
                    )(ob, midx, grids, mvalid, im_info)
        return res

    # the Predictor passes the traced image extent as canvas_hw only to
    # postprocess closures that declare they want it
    batched.wants_canvas = bool(paste)
    return batched
