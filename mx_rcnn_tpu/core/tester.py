"""Inference: Predictor, im_detect, pred_eval, generate_proposals.

Reference: ``rcnn/core/tester.py`` — ``Predictor`` (bound forward-only
module), ``im_detect`` (decode + clip + unscale), ``pred_eval`` (dataset
loop → per-class NMS → ``imdb.evaluate_detections``), and
``generate_proposals`` (dump RPN proposals for alternate training).

The device side is one jitted test forward per shape bucket; the host
side (per-class thresholding/NMS, detection accumulation) stays on the
host exactly like the reference, with the NMS inner loop in native C
(``native/hostops.c`` — the reference's ``cpu_nms.pyx`` role).
"""

from __future__ import annotations

import logging
import pickle
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.utils.bbox_stats import np_bbox_pred, np_clip_boxes

logger = logging.getLogger(__name__)


class Predictor:
    """Jitted forward-only wrapper (Predictor twin).  One compile per
    shape bucket — the TPU replacement for MutableModule max-shape
    binding.

    ``postprocess`` (ops/postprocess.py): fuses per-class decode+NMS
    into the same jit, so only keep lists cross the device→host link
    instead of the full (B, R, K)+(B, R, 4K) head outputs.  Mask models
    get the same treatment: the postprocess gathers each survivor's
    class-channel S×S grid on device (``det_masks``), so the raw
    ``(B, R, S, S, K)`` stack never crosses the link — host workers
    only sigmoid + paste + RLE-encode."""

    def __init__(self, model, params, postprocess=None, donate: bool = False,
                 params_transform=None):
        self.model = model
        self.params = params

        # batch keys match the model __call__ kwargs (gt keys are accepted
        # and ignored by test forwards; FastRCNN additionally consumes
        # proposals/prop_valid)
        #
        # params_transform (int8 rung, core/quantize.py): a jit-traceable
        # tree→tree map applied to the params argument INSIDE the jit —
        # the bound tree can then be a compressed form (int8 q + scale
        # leaves) that dequantizes on use, with XLA fusing the broadcast
        # multiply into each weight's consumer.  Params stay a traced
        # argument, so hot-swap pointer flips still reuse the executable.
        def fwd(p, batch):
            if params_transform is not None:
                p = params_transform(p)
            batch = dict(batch)
            orig_hw = batch.pop("orig_hw", None)
            out = model.apply({"params": p}, train=False, **batch)
            if postprocess is not None and orig_hw is not None:
                if getattr(postprocess, "wants_canvas", False):
                    # canvas-paste postprocess (streaming mask serving):
                    # the paste canvas is the padded bucket extent —
                    # static under the trace, so one canvas shape per
                    # (model, bucket) rung and the compile ladder is
                    # untouched
                    return postprocess(
                        out, batch["im_info"], orig_hw,
                        tuple(batch["images"].shape[1:3]),
                    )
                return postprocess(out, batch["im_info"], orig_hw)
            return out

        # donate=True hands the input batch buffers to XLA (serving: the
        # engine never reuses a dispatched batch, so the device can write
        # outputs in place).  Off by default — the CPU runtime can't use
        # donations and would log a warning per compile.
        jit_kwargs = {}
        if donate:
            jit_kwargs["donate_argnums"] = (1,)
        self._fn = jax.jit(fwd, **jit_kwargs)

    def predict(self, batch: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        return jax.device_get(self.predict_async(batch))

    def predict_async(self, batch: Dict[str, np.ndarray]):
        """Dispatch the forward and return the ON-DEVICE outputs without
        materializing them (``jax.device_get`` forces) — the
        dispatch/force split point ``ServeRunner`` and ``pipelined``'s
        ``"async"`` mode build on."""
        return self._fn(self.params, batch)

    def predict_with(
        self, params, batch: Dict[str, np.ndarray]
    ) -> Dict[str, np.ndarray]:
        """Blocking forward with CALLER-supplied params instead of the
        bound ones.  Params are a traced jit argument, so a same-
        structure/shape/dtype tree reuses the compiled executable — this
        is what makes a hot-swap warmup (ISSUE 7) a validation pass, not
        a recompile: the registry drives a candidate version through
        every warmed bucket off the live path, then the swap itself is a
        pointer assignment to :attr:`params` between batches."""
        return jax.device_get(self._fn(params, batch))

    def input_layouts(self, batch: Dict[str, np.ndarray]):
        """Compiled formats of the batch argument for this batch's
        shapes, usable as a ``jax.device_put`` target so the transfer
        lands device-native and XLA inserts no input relayout copy."""
        from mx_rcnn_tpu.core.pipeline import input_layouts_for, shape_structs

        return input_layouts_for(
            self._fn, (shape_structs(self.params), shape_structs(batch)),
            argnum=1,
        )


def pipelined(
    predictor: Predictor,
    batches,
    in_flight: int = 2,
    feed_depth: int = 2,
    stats_out: Optional[Dict] = None,
    mode: str = "auto",
):
    """Overlapped eval pipeline shared by pred_eval and
    generate_proposals: keeps ``in_flight`` forwards in motion and yields
    ``(payload, batch, outputs)`` in input order.

    Two dispatch modes, selected by ``mode`` (``"auto"`` picks per
    backend):

    * ``"threads"`` (non-CPU default): ``in_flight`` blocking
      :meth:`Predictor.predict` calls in a small thread pool, so one
      batch's upload and fetch overlap another's compute (the GIL drops
      inside the runtime).  Which mode is faster on a local chip is not
      measured; ROADMAP D9 settles it and drops the loser.
    * ``"async"`` (CPU default): :meth:`Predictor.predict_async` from
      the dispatch thread with a bounded in-flight window, forcing
      (``jax.device_get``) only when a result is consumed — no predict
      threads, so the dispatch thread stays free to run the completion
      pool's backpressure and local runtimes queue the window natively.

    Either way results are consumed in submission order, so downstream
    accumulation is order-identical to the serial loop
    (``tests/test_postprocess.py`` equivalence).

    Eval draws device-feed from the same pipeline stage as training:
    ``feed_depth`` > 0 stacks a :class:`~mx_rcnn_tpu.core.pipeline
    .DeviceFeed` between the host batches and the predict stage, so
    batch N+1's H2D transfer overlaps batch N's forward (0 disables —
    the batches then reach jit as host numpy).  ``stats_out``, if given,
    receives the feed's occupancy counters plus the resolved mode on
    exit.
    """
    from collections import deque
    from concurrent.futures import ThreadPoolExecutor

    from mx_rcnn_tpu.core.pipeline import DeviceFeed

    if mode == "auto":
        mode = "async" if jax.default_backend() == "cpu" else "threads"
    if mode not in ("async", "threads"):
        raise ValueError(f"unknown pipelined mode {mode!r}")
    feed = None
    source = batches
    if feed_depth > 0:
        feed = DeviceFeed(
            batches,
            # stage only the batch; the payload (indices/records) is host
            # bookkeeping
            place_fn=lambda pair: (pair[0], jax.device_put(pair[1])),
            depth=feed_depth,
            name="eval-device-feed",
        )
        source = feed
    window = max(in_flight, 1)
    q: deque = deque()
    ex = None
    try:
        if mode == "async":
            for payload, batch in source:
                q.append((payload, batch, predictor.predict_async(batch)))
                while len(q) > window:
                    p, b, o = q.popleft()
                    yield p, b, jax.device_get(o)
            while q:
                p, b, o = q.popleft()
                yield p, b, jax.device_get(o)
        else:
            ex = ThreadPoolExecutor(max_workers=window)
            for payload, batch in source:
                q.append(
                    (payload, batch, ex.submit(predictor.predict, batch))
                )
                while len(q) > window:
                    p, b, f = q.popleft()
                    yield p, b, f.result()
            while q:
                p, b, f = q.popleft()
                yield p, b, f.result()
    finally:
        if ex is not None:
            # wait=True: on early abandonment (consumer raised/broke
            # out), drain the in-flight predicts (~one batch chain)
            # rather than leaving orphan threads driving the device under
            # whatever the caller does next; queued-but-unstarted work
            # is cancelled
            ex.shutdown(wait=True, cancel_futures=True)
        if stats_out is not None:
            stats_out["mode"] = mode
            stats_out["in_flight"] = window
        if feed is not None:
            if stats_out is not None:
                stats_out.update(feed.stats())
            feed.close()


def im_detect(
    output: Dict[str, np.ndarray], im_info: np.ndarray, orig_hw, index: int = 0
) -> Dict[str, np.ndarray]:
    """Decode one image's raw head outputs into image-space detections.

    Reference: ``rcnn/core/tester.py :: im_detect`` — class-specific
    delta decode, clip to the *resized* image, then divide by scale back
    to original coordinates.  ``index`` selects the image within a
    batched forward's outputs.
    """
    rois = output["rois"][index]
    valid = output["roi_valid"][index].astype(bool)
    scores = output["cls_prob"][index]
    deltas = output["bbox_deltas"][index]
    scale = float(im_info[2])

    # host numpy decode, like the reference's nonlinear_pred: a jnp call
    # here would pay a device dispatch per image during the eval loop
    boxes = np_bbox_pred(np.asarray(rois), np.asarray(deltas))
    boxes = np_clip_boxes(boxes, (float(im_info[0]), float(im_info[1])))
    boxes = boxes / scale
    # final clip to the original image extent
    h, w = orig_hw
    boxes = np_clip_boxes(boxes, (float(h), float(w)))
    det = {"scores": scores[valid], "boxes": boxes[valid]}
    if "mask_logits" in output:  # Mask R-CNN branch: per-roi (S, S, K)
        det["mask_probs"] = 1.0 / (
            1.0 + np.exp(-np.asarray(output["mask_logits"][index][valid]))
        )
    return det


def pred_eval(
    predictor: Predictor,
    loader,
    imdb,
    cfg: Config,
    thresh: Optional[float] = None,
    vis: Optional[str] = None,
    dump_path: Optional[str] = None,
    vis_thresh: float = 0.7,
    postprocess_workers: Optional[int] = None,
    assembly_workers: Optional[int] = None,
    stats_out: Optional[Dict] = None,
):
    """Full-dataset evaluation loop (pred_eval twin).

    Returns (all_boxes, eval_results) where
    ``all_boxes[cls][img] = (n, 5)``.  ``dump_path`` writes the all_boxes
    pickle that ``tools/reeval.py`` re-scores (the reference's
    detections.pkl); ``vis`` names a directory that receives per-image
    detection overlays (vis_all_detection twin).

    Host data plane (ISSUE 5): assembly can run in a worker pool
    (``assembly_workers``, batched loaders only) and the per-image
    postprocess — detections, capping, mask RLE encoding — runs in a
    :class:`~mx_rcnn_tpu.data.assembler.CompletionPool`
    (``postprocess_workers``; None → ``MX_RCNN_POSTPROCESS_WORKERS``,
    default 0 = inline on the dispatch thread).  Accumulation is
    index-addressed (``all_boxes[cls][img]``), so the result is
    identical no matter which worker finishes first; worker errors
    re-raise at the final ``drain``.  ``stats_out`` receives the
    completion-pool counters.
    """
    import os as _os
    import threading

    from mx_rcnn_tpu.data.assembler import CompletionPool

    te = cfg.TEST
    thresh = te.SCORE_THRESH if thresh is None else thresh
    num_classes = imdb.num_classes
    num_images = len(loader)
    if te.DEVICE_POSTPROCESS:
        from mx_rcnn_tpu.ops.postprocess import make_test_postprocess

        predictor = Predictor(
            predictor.model,
            predictor.params,
            postprocess=make_test_postprocess(
                cfg, num_classes, thresh, max_out=te.DET_PER_CLASS
            ),
        )
    all_boxes: List[List[np.ndarray]] = [
        [np.zeros((0, 5), np.float32) for _ in range(num_images)]
        for _ in range(num_classes)
    ]
    all_masks: Optional[List[List[list]]] = None
    t0 = time.time()
    done = 0
    # all_boxes/all_masks slot writes are disjoint per image index; the
    # lock covers the only cross-image state (lazy all_masks creation
    # and the progress counter)
    acc_lock = threading.Lock()

    def process_image(i: int, rec: Dict, out, batch, k: int = 0):
        """Accumulate detections for dataset image ``i`` from the
        ``k``-th slot of a (possibly batched) forward's outputs.  Pure
        per image except the index-addressed slot writes — safe from
        any completion worker."""
        nonlocal all_masks, done
        # the canonical per-image postprocess lives in serve/runner.py
        # (one decode path shared by eval, demo, and the serving engine);
        # function-level import: serve imports this module at top level
        from mx_rcnn_tpu.serve.runner import (
            cap_detections,
            detections_from_output,
        )

        cls_dets, mask_probs = detections_from_output(
            out, batch["im_info"][k], (rec["height"], rec["width"]),
            cfg, num_classes, index=k, thresh=thresh,
        )
        # cap detections per image across classes (COCO: 100) BEFORE mask
        # encoding — full-image mask work for detections the cap then
        # discards dominated segm eval cost
        cls_dets, mask_probs = cap_detections(
            cls_dets, te.MAX_PER_IMAGE, mask_probs
        )
        rles = None
        if mask_probs is not None:
            from mx_rcnn_tpu.eval.segm import rles_for_detections

            rles = {
                j: rles_for_detections(
                    mask_probs[j], cls_dets[j], rec["height"], rec["width"]
                )
                for j in range(1, num_classes)
            }
        for j in range(1, num_classes):
            all_boxes[j][i] = cls_dets[j]
        if rles is not None:
            with acc_lock:
                if all_masks is None:
                    all_masks = [
                        [[] for _ in range(num_images)]
                        for _ in range(num_classes)
                    ]
            for j in range(1, num_classes):
                all_masks[j][i] = rles[j]
        if vis:
            from mx_rcnn_tpu.data.loader import _load_record_image
            from mx_rcnn_tpu.utils.visualize import draw_detections, save_image

            _os.makedirs(vis, exist_ok=True)
            dets_by_class = {
                imdb.classes[j]: all_boxes[j][i] for j in range(1, num_classes)
            }
            im = draw_detections(_load_record_image(rec), dets_by_class, vis_thresh)
            save_image(_os.path.join(vis, f"det_{i:06d}.png"), im)
        with acc_lock:
            done += 1
            n_done = done
        if n_done % 100 == 0:
            logger.info(
                "im_detect %d/%d %.3fs/im", n_done, num_images,
                (time.time() - t0) / n_done,
            )

    workers = (
        max(0, int(_os.environ.get("MX_RCNN_POSTPROCESS_WORKERS", "0")))
        if postprocess_workers is None
        else max(0, int(postprocess_workers))
    )
    completion = CompletionPool(workers, name="eval-complete")
    try:
        if getattr(loader, "batch_size", 1) > 1:
            # batched device forwards (beyond-reference: the reference
            # tester is batch=1); dataset order is restored through the
            # indices, so completion can run out of order
            for (idxs, recs), batch, out in pipelined(
                predictor,
                (
                    ((idxs, recs), batch)
                    for idxs, recs, batch in loader.iter_batched(
                        assembly_workers=assembly_workers
                    )
                ),
            ):
                for k, (i, rec) in enumerate(zip(idxs, recs)):
                    completion.submit(process_image, i, rec, out, batch, k)
        else:
            for (i, rec), batch, out in pipelined(
                predictor,
                (((i, rec), batch) for i, (rec, batch) in enumerate(loader)),
            ):
                completion.submit(process_image, i, rec, out, batch)
        completion.drain()
    finally:
        completion.close()
        if stats_out is not None:
            stats_out["completion"] = completion.stats()
    if dump_path:
        with open(dump_path, "wb") as f:
            pickle.dump(all_boxes, f, pickle.HIGHEST_PROTOCOL)
    if all_masks is not None:
        import inspect

        sig = inspect.signature(imdb.evaluate_detections)
        if "all_masks" in sig.parameters:
            results = imdb.evaluate_detections(all_boxes, all_masks=all_masks)
        else:  # dataset without segm support: bbox-only
            logger.warning(
                "%s.evaluate_detections has no all_masks support — "
                "dropping segm results", type(imdb).__name__
            )
            results = imdb.evaluate_detections(all_boxes)
    else:
        results = imdb.evaluate_detections(all_boxes)
    return all_boxes, results


def generate_proposals(
    predictor: Predictor, loader, cfg: Config, dump_path: Optional[str] = None
) -> List[np.ndarray]:
    """Run the RPN over a dataset and keep proposals per image, for the
    alternate-training pipeline and proposal-recall eval.

    Reference: ``rcnn/core/tester.py :: generate_proposals`` (+ the
    ``.pkl`` dump consumed by ``load_proposal_roidb``).
    """
    proposals: List[Optional[np.ndarray]] = [None] * len(loader)
    for idxs, batch, out in pipelined(
        predictor, ((idxs, batch) for idxs, recs, batch in loader.iter_batched())
    ):
        for k, i in enumerate(idxs):
            rois = out["rois"][k]
            valid = out["roi_valid"][k].astype(bool)
            scale = float(batch["im_info"][k][2])
            boxes = rois[valid] / scale
            scores = np.asarray(out["roi_scores"][k])[valid]
            dets = np.hstack([boxes, scores[:, None]]).astype(np.float32)
            proposals[i] = dets
    if dump_path:
        with open(dump_path, "wb") as f:
            pickle.dump(proposals, f, pickle.HIGHEST_PROTOCOL)
    return proposals
