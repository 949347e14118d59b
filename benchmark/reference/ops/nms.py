"""Non-maximum suppression, TPU-style: fixed shapes, validity masks.

Reference: ``rcnn/cython/nms_kernel.cu`` (bitmask GPU NMS),
``rcnn/cython/cpu_nms.pyx`` and ``rcnn/processing/nms.py`` (dispatch +
pure-python fallback).  TPU/XLA has no dynamic output shapes, so instead of
a variable-length keep list every routine here returns values padded to a
static size with an explicit validity mask — callers thread the mask, never
the length.

Three implementations, one contract:

- :func:`nms_mask` — in-graph greedy NMS via ``lax.fori_loop`` over
  score-sorted boxes.  O(N) memory (IoU rows computed on the fly), exact
  greedy semantics.  This is the interim/debug path; the Pallas blocked
  kernel (``mx_rcnn_tpu.ops.pallas.nms``) is the fast path behind the same
  contract.
- :func:`nms` — mask + top-k selection → fixed ``max_out`` boxes.
- :func:`nms_numpy` — host-side greedy NMS for the per-class filtering in
  ``pred_eval`` (reference: ``rcnn/processing/nms.py :: nms``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from reference.ops.boxes import bbox_overlaps

_NEG_INF = -1e10


def _iou_row(box: jnp.ndarray, boxes: jnp.ndarray) -> jnp.ndarray:
    """IoU of one (4,) box against (N, 4) boxes → (N,)."""
    return bbox_overlaps(box[None, :], boxes)[0]


def nms_mask(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    thresh: float,
    valid: jnp.ndarray | None = None,
    sorted_input: bool = False,
    max_keep: int = 0,
) -> jnp.ndarray:
    """Greedy NMS → bool keep mask aligned with the *input* order.

    Exactly the sequential greedy algorithm of the reference CPU/GPU
    kernels: walk boxes in descending score; a box survives iff no
    higher-scoring *surviving* box overlaps it above ``thresh``.
    Invalid (padding) entries never survive and never suppress.

    ``sorted_input``: promise that ``boxes``/``valid`` are already in
    descending-score order (e.g. straight out of ``lax.top_k``) — skips
    an argsort + scatter round-trip.

    ``max_keep``: with ``sorted_input``, stop the sweep once that many
    survivors exist — exact iff the caller keeps only the top
    ``max_keep`` survivors by score (``nms`` does).
    """
    n = boxes.shape[0]
    if valid is None:
        valid = jnp.ones((n,), dtype=bool)
    if sorted_input:
        b, v, order = boxes.astype(jnp.float32), valid, None
    else:
        scores = jnp.where(valid, scores, _NEG_INF)
        order = jnp.argsort(-scores)
        b = boxes[order].astype(jnp.float32)
        v = valid[order]

    def body(i, alive):
        row = _iou_row(b[i], b)
        suppress = (row > thresh) & (jnp.arange(n) > i) & alive[i]
        return alive & ~suppress

    alive = jax.lax.fori_loop(0, n, body, v)
    if order is None:
        return alive
    # scatter back to input order
    keep = jnp.zeros((n,), dtype=bool).at[order].set(alive)
    return keep


def nms(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    thresh: float,
    max_out: int,
    valid: jnp.ndarray | None = None,
    sorted_input: bool = False,
    with_idx: bool = False,
):
    """NMS + select top ``max_out`` survivors by score (fixed shape).

    Returns ``(boxes (max_out, 4), scores (max_out,), valid (max_out,))``;
    padding rows are zero boxes with score ``-1e10`` and ``valid=False``.
    This is the in-graph replacement for the keep-list interface of
    ``gpu_nms`` — the pad-to-``post_nms_top_n`` discipline the reference
    already applied in ``rcnn/symbol/proposal.py`` generalized.

    ``with_idx`` appends the top-k source indices ``idx (max_out,)`` —
    each survivor's position in the INPUT order, which downstream gathers
    (device mask selection) use to index back into per-roi head outputs.
    ``idx`` is only meaningful where ``valid``; when ``N < max_out`` the
    scores are padded before ``top_k``, so invalid slots may carry
    indices ≥ N — callers must clamp or mask before gathering.
    """
    # with a sorted input the kernel may stop once max_out survivors
    # exist — the top_k below only ever reads that prefix
    keep = nms_mask(
        boxes, scores, thresh, valid, sorted_input=sorted_input,
        max_keep=max_out if sorted_input else 0,
    )
    masked = jnp.where(keep, scores, _NEG_INF)
    if masked.shape[0] < max_out:  # static: pad so top_k(k) is well-formed
        pad = max_out - masked.shape[0]
        masked = jnp.concatenate([masked, jnp.full((pad,), _NEG_INF)])
        boxes = jnp.concatenate([boxes, jnp.zeros((pad, 4), boxes.dtype)])
    top_scores, idx = jax.lax.top_k(masked, max_out)
    out_valid = top_scores > _NEG_INF / 2
    out_boxes = jnp.where(out_valid[:, None], boxes[idx], 0.0)
    if with_idx:
        return out_boxes, top_scores, out_valid, idx
    return out_boxes, top_scores, out_valid


def batched_class_nms(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    thresh: float,
    max_out: int,
    valid: jnp.ndarray | None = None,
    with_idx: bool = False,
):
    """Per-class NMS, vmapped over a leading class axis.

    ``boxes`` (C, N, 4), ``scores`` (C, N) → (C, max_out, ·) padded.
    Replaces the per-class python loop in
    ``rcnn/core/tester.py :: pred_eval`` with one in-graph batched op.
    ``with_idx`` threads the per-class survivor source indices through
    (see :func:`nms`) for device-side mask gathering.
    """
    if valid is None:
        valid = jnp.ones(scores.shape, dtype=bool)
    return jax.vmap(
        lambda b, s, v: nms(b, s, thresh, max_out, v, with_idx=with_idx)
    )(boxes, scores, valid)


def nms_numpy(dets: np.ndarray, thresh: float) -> list:
    """Host greedy NMS on (N, 5) [x1, y1, x2, y2, score] → kept indices.

    Reference: ``rcnn/processing/nms.py :: nms`` (the pure-python
    fallback); used by host-side eval tooling and as the golden oracle in
    kernel tests.
    """
    if dets.size == 0:
        return []
    x1, y1, x2, y2, scores = dets[:, 0], dets[:, 1], dets[:, 2], dets[:, 3], dets[:, 4]
    areas = (x2 - x1 + 1) * (y2 - y1 + 1)
    # stable sort pins the equal-score visit order (descending index
    # after the reversal) so the native C path (hostops.c) can match it
    # exactly; numpy's default introsort leaves tie order unspecified
    order = scores.argsort(kind="stable")[::-1]
    keep = []
    while order.size > 0:
        i = order[0]
        keep.append(int(i))
        xx1 = np.maximum(x1[i], x1[order[1:]])
        yy1 = np.maximum(y1[i], y1[order[1:]])
        xx2 = np.minimum(x2[i], x2[order[1:]])
        yy2 = np.minimum(y2[i], y2[order[1:]])
        w = np.maximum(0.0, xx2 - xx1 + 1)
        h = np.maximum(0.0, yy2 - yy1 + 1)
        inter = w * h
        ovr = inter / (areas[i] + areas[order[1:]] - inter)
        inds = np.where(ovr <= thresh)[0]
        order = order[inds + 1]
    return keep
