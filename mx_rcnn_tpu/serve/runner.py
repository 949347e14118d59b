"""The one canonical predict path: prepare → batch → forward → detections.

Before this module, the repo had three copies of "raw head outputs →
per-class detections" (``core/tester.py :: pred_eval.process_image``'s
device and host branches, and ``tools/demo.py :: demo_net``); they have
been collapsed onto :func:`detections_from_output` /
:func:`cap_detections` here, and both callers now delegate.  The online
engine (``serve/engine.py``) uses the same functions, so offline eval,
the demo, and the serving endpoint are bit-identical per image by
construction.

:class:`ServeRunner` is the device-facing half: it owns the jitted
:class:`~mx_rcnn_tpu.core.tester.Predictor` (with device postprocess
when configured, and donated input buffers on accelerator backends),
enforces the serving bucket ladder on the prepare path (oversize →
:class:`~mx_rcnn_tpu.serve.buckets.BucketOverflow`, never a fresh
compile), pads every batch to ``max_batch`` so each bucket has exactly
ONE jit signature, and accounts signatures in a
:class:`~mx_rcnn_tpu.serve.buckets.CompileCache` — ``warmup`` walks the
ladder once, after which ``misses`` must stay 0.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, List, Optional, Tuple, Union

import jax
import numpy as np

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.core.resilience import host_copy
from mx_rcnn_tpu.core.tester import Predictor, im_detect
from mx_rcnn_tpu.data.image import (
    normalize,
    pad_to_bucket,
    quantize_uint8,
    resize_im,
)
from mx_rcnn_tpu.native.hostops import nms_host
from mx_rcnn_tpu.analysis.lockcheck import make_lock
from mx_rcnn_tpu.serve.batcher import Request
from mx_rcnn_tpu.serve.buckets import BucketLadder, CompileCache
from mx_rcnn_tpu.utils import tracing

ClsDets = List[Optional[np.ndarray]]  # [None, (n1, 5), ..., (nK-1, 5)]

#: compile-cache precision tags — part of every jit signature, so the
#: f32, bf16, and int8 serve graphs can never collide on one cache key
_PRECISION_TAGS = {
    None: "f32", "float32": "f32", "f32": "f32",
    "bfloat16": "bf16", "bf16": "bf16",
    "int8": "int8",
}


class PrecisionParityError(RuntimeError):
    """A reduced-precision serve graph's detections (bf16 compute or
    int8 weight rung) drifted outside the documented tolerance vs the
    f32 reference — the precision mode refuses to serve (fail at
    warmup, not in production results)."""


def _box_iou(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """(n, 4) × (m, 4) [x1 y1 x2 y2] → (n, m) IoU matrix."""
    ax1, ay1, ax2, ay2 = [a[:, k, None] for k in range(4)]
    bx1, by1, bx2, by2 = [b[None, :, k] for k in range(4)]
    iw = np.maximum(np.minimum(ax2, bx2) - np.maximum(ax1, bx1) + 1.0, 0.0)
    ih = np.maximum(np.minimum(ay2, by2) - np.maximum(ay1, by1) + 1.0, 0.0)
    inter = iw * ih
    area_a = (ax2 - ax1 + 1.0) * (ay2 - ay1 + 1.0)
    area_b = (bx2 - bx1 + 1.0) * (by2 - by1 + 1.0)
    return inter / np.maximum(area_a + area_b - inter, 1e-9)


def detection_parity(
    ref: ClsDets,
    test: ClsDets,
    thresh: float,
    margin: float = 0.1,
    match_iou: float = 0.5,
) -> Dict:
    """Compare two detection sets for reduced-precision parity.

    Detections scoring within ``margin`` of ``thresh`` are exempt —
    threshold flips are the expected (and harmless) failure mode of a
    lower-precision graph.  Every CONFIDENT detection (score ≥ thresh +
    margin) on either side must have a counterpart on the other with
    IoU ≥ ``match_iou``; for matched pairs the max absolute box-corner
    delta (px) and score delta are reported.  Symmetric by construction.
    """
    max_box = 0.0
    max_score = 0.0
    unmatched = 0
    for j in range(1, max(len(ref), len(test))):
        a = ref[j] if j < len(ref) else None
        b = test[j] if j < len(test) else None
        a = np.zeros((0, 5), np.float32) if a is None else np.asarray(a)
        b = np.zeros((0, 5), np.float32) if b is None else np.asarray(b)
        for src, dst in ((a, b), (b, a)):
            conf = src[src[:, 4] >= thresh + margin]
            if not len(conf):
                continue
            if not len(dst):
                unmatched += len(conf)
                continue
            iou = _box_iou(conf[:, :4], dst[:, :4])
            best = iou.argmax(axis=1)
            for i, k in enumerate(best):
                if iou[i, k] < match_iou:
                    unmatched += 1
                    continue
                max_box = max(
                    max_box,
                    float(np.abs(conf[i, :4] - dst[k, :4]).max()),
                )
                max_score = max(
                    max_score, float(abs(conf[i, 4] - dst[k, 4]))
                )
    return {
        "max_box_delta_px": round(max_box, 4),
        "max_score_delta": round(max_score, 5),
        "unmatched_confident": unmatched,
        "margin": margin,
        "match_iou": match_iou,
    }


def mask_parity(
    ref_dets: ClsDets,
    ref_masks: Dict[int, np.ndarray],
    test_dets: ClsDets,
    test_masks: Dict[int, np.ndarray],
    thresh: float,
    margin: float = 0.1,
    match_iou: float = 0.5,
) -> Dict:
    """Mask-grid parity companion to :func:`detection_parity`: for every
    confident reference detection with an IoU-matched counterpart, the
    max absolute per-pixel probability delta between the two S×S grids.
    This is what lets the bf16 gate cover mask models — without it a
    reduced-precision graph could pass on boxes while shipping drifted
    masks."""
    max_delta = 0.0
    pairs = 0
    for j in range(1, len(ref_dets)):
        a = ref_dets[j]
        b = test_dets[j] if j < len(test_dets) else None
        ma = ref_masks.get(j) if ref_masks else None
        mb = test_masks.get(j) if test_masks else None
        if a is None or b is None or ma is None or mb is None \
                or not len(a) or not len(b):
            continue
        conf = np.where(np.asarray(a)[:, 4] >= thresh + margin)[0]
        if not len(conf):
            continue
        iou = _box_iou(np.asarray(a)[conf, :4], np.asarray(b)[:, :4])
        best = iou.argmax(axis=1)
        for t, i in enumerate(conf):
            k = int(best[t])
            if iou[t, k] < match_iou:
                continue
            pairs += 1
            max_delta = max(
                max_delta, float(np.abs(ma[i] - mb[k]).max())
            )
    return {"max_mask_prob_delta": round(max_delta, 5), "mask_pairs": pairs}


# --------------------------------------------------------------- detections
def detections_from_output(
    out: Dict[str, np.ndarray],
    im_info_row: np.ndarray,
    orig_hw: Tuple[float, float],
    cfg: Config,
    num_classes: int,
    index: int = 0,
    thresh: Optional[float] = None,
    with_rows: bool = False,
):
    """One image's forward outputs → per-class (n, 5) [x1 y1 x2 y2 score].

    Handles both output flavors: the fused device-postprocess dict
    (``det_boxes``/``det_scores``/``det_valid`` — decode, unscale, clip,
    and per-class NMS already ran inside the jit) and raw head outputs
    (host decode via :func:`~mx_rcnn_tpu.core.tester.im_detect`, then
    per-class threshold + native NMS, the reference ``pred_eval`` inner
    loop).  Returns ``(cls_dets, mask_probs)``; ``cls_dets[0]`` is None
    (background), ``mask_probs`` is None unless the model is a mask
    family.  On the device path a mask model ships already-selected
    per-survivor grids (``det_masks`` LOGITS + ``det_mask_idx`` flat
    det-grid indices, ops/postprocess.py) — the sigmoid happens here,
    with the exact numpy expression of the reference ``im_detect``, so
    the resulting probabilities are bit-identical to the raw-head path.

    ``with_rows=True`` additionally returns, as a third element, the
    per-class det-grid row indices each kept detection came from (device
    path only; None on the host path) — the alignment the streaming
    canvas path (:meth:`ServeRunner.mask_rles_for`) needs to map capped
    detections back onto their ``det_canvas`` / ``det_masks`` slots.
    """
    te = cfg.TEST
    thresh = te.SCORE_THRESH if thresh is None else thresh
    cls_dets: ClsDets = [None] * num_classes
    mask_probs: Optional[Dict[int, np.ndarray]] = None
    det_rows: Optional[Dict[int, np.ndarray]] = None
    if "det_boxes" in out:
        det_rows = {}
        lut = None
        if "det_masks" in out:
            mask_probs = {}
            midx = np.asarray(out["det_mask_idx"][index])
            grids = np.asarray(out["det_masks"][index])
            lut = {int(f): p for p, f in enumerate(midx) if f >= 0}
        max_out = out["det_boxes"].shape[2]
        for j in range(1, num_classes):
            m = np.asarray(out["det_valid"][index][j - 1]).astype(bool)
            b = np.asarray(out["det_boxes"][index][j - 1][m])
            s = np.asarray(out["det_scores"][index][j - 1][m])
            cls_dets[j] = np.hstack([b, s[:, None]]).astype(np.float32)
            det_rows[j] = np.where(m)[0]
            if lut is not None:
                rows = det_rows[j]
                # rows beyond the device's max_det mask budget only
                # exist past the MAX_PER_IMAGE cut — cap_detections
                # drops them; the large-negative logit fill (sigmoid ≈ 0
                # → empty mask, no exp overflow) keeps any
                # exact-score-tie leak safe, not wrong
                g = np.full(
                    (len(rows),) + grids.shape[1:], -80.0, np.float32
                )
                for t, rr in enumerate(rows):
                    p = lut.get((j - 1) * max_out + int(rr))
                    if p is not None:
                        g[t] = grids[p]
                mask_probs[j] = 1.0 / (1.0 + np.exp(-g))
    else:
        det = im_detect(out, im_info_row, orig_hw, index=index)
        scores, boxes = det["scores"], det["boxes"]
        if "mask_probs" in det:
            mask_probs = {}
        for j in range(1, num_classes):
            keep = np.where(scores[:, j] > thresh)[0]
            cd = np.hstack(
                [boxes[keep, j * 4 : (j + 1) * 4], scores[keep, j : j + 1]]
            ).astype(np.float32)
            keep_nms = nms_host(cd, te.NMS)
            cls_dets[j] = cd[keep_nms]
            if mask_probs is not None:
                mask_probs[j] = det["mask_probs"][keep][keep_nms, :, :, j]
    if with_rows:
        return cls_dets, mask_probs, det_rows
    return cls_dets, mask_probs


def cap_detections(
    cls_dets: ClsDets,
    max_per_image: int,
    mask_probs: Optional[Dict[int, np.ndarray]] = None,
    rows: Optional[Dict[int, np.ndarray]] = None,
):
    """Cross-class per-image detection cap (COCO-style, reference
    ``max_per_image``): keep the globally top-scoring ``max_per_image``
    detections across classes.  No-op when ``max_per_image <= 0``.
    ``rows`` (the ``with_rows`` side-channel of
    :func:`detections_from_output`) is filtered in lockstep and returned
    as a third element when given."""
    num_classes = len(cls_dets)
    if max_per_image > 0:
        all_scores = np.concatenate(
            [cls_dets[j][:, 4] for j in range(1, num_classes)]
        )
        if len(all_scores) > max_per_image:
            cut = np.sort(all_scores)[-max_per_image]
            for j in range(1, num_classes):
                keep = cls_dets[j][:, 4] >= cut
                cls_dets[j] = cls_dets[j][keep]
                if mask_probs is not None:
                    mask_probs[j] = mask_probs[j][keep]
                if rows is not None and rows.get(j) is not None:
                    rows[j] = rows[j][keep]
    if rows is not None:
        return cls_dets, mask_probs, rows
    return cls_dets, mask_probs


# ----------------------------------------------------------------- prepare
def prepare_request(
    im: np.ndarray,
    cfg: Config,
    ladder: BucketLadder,
    deadline: Optional[float] = None,
    model: Optional[str] = None,
) -> Request:
    """Original RGB image → bucket-padded :class:`Request`.

    Same math as the offline ``data/image.py :: prepare_image`` (resize
    to dataset SCALES, optional uint8 quantize per TEST.UINT8_TRANSFER,
    zero-pad), but bucket choice goes through the serving ladder:
    smallest fit, oversize REJECTED (:class:`BucketOverflow`) instead of
    the offline largest-bucket fallback.  Runs in the submitting thread
    so host preprocessing overlaps device execution of earlier batches.
    """
    im = np.asarray(im, np.float32)
    orig_hw = (int(im.shape[0]), int(im.shape[1]))
    target, max_size = cfg.dataset.SCALES[0]
    im, scale = resize_im(im, target, max_size)
    h, w = im.shape[:2]
    bucket = ladder.select(h, w)  # raises BucketOverflow
    if cfg.TEST.UINT8_TRANSFER:
        im = quantize_uint8(im)
    else:
        im = normalize(im, cfg.network.PIXEL_MEANS, cfg.network.PIXEL_STDS)
    return Request(
        image=pad_to_bucket(im, bucket),
        im_info=np.array([h, w, scale], np.float32),
        orig_hw=orig_hw,
        bucket=bucket,
        enqueue_t=time.monotonic(),
        deadline=deadline,
        model=model,
    )


# ------------------------------------------------------------------ runner
@dataclasses.dataclass
class ServeHandle:
    """Device-resident result of :meth:`ServeRunner.dispatch`.

    ``outputs`` is the UN-FORCED output tree of the async jitted forward
    (:meth:`Predictor.predict_async`): the device is still computing (or
    has the result parked in device memory) when the handle is returned,
    so the host is free to stage and dispatch the next batch.
    :meth:`ServeRunner.complete` is the only sanctioned way to force it —
    it fetches through the ``host_copy`` owning-copy discipline (a bare
    ``device_get`` on CPU yields zero-copy views that a donating runner
    mutates under the caller; graftlint R1 polices exactly this escape).
    """

    outputs: Dict
    model: str
    signature: Tuple
    bucket: Tuple[int, int]
    dispatch_t: float


class _ModelSlot:
    """One model family's device-facing state on one runner: the jitted
    :class:`Predictor` bound to whatever version this runner last synced
    to.  ``lock`` serializes the params pointer swap against concurrent
    sync attempts; predict itself reads the pointer once, so a swap
    lands cleanly BETWEEN batches."""

    def __init__(self, model_id, predictor, version, cfg, num_classes,
                 uint8: bool, precision: str = "f32"):
        self.model_id = model_id
        self.predictor = predictor
        self.version = int(version)
        self.cfg = cfg
        self.num_classes = int(num_classes)
        self.uint8 = bool(uint8)
        self.precision = precision  # compile-cache tag: "f32" | "bf16"
        self.lock = make_lock("_ModelSlot.lock")


class ServeRunner:
    """Device-facing predict path shared by the engine and tests.

    Since ISSUE 7 the runner holds NO params of its own: every model's
    params are a versioned resource in a
    :class:`~mx_rcnn_tpu.serve.registry.ModelRegistry`, resolved per
    batch.  Two construction modes:

    * legacy single-model — ``ServeRunner(model, params, cfg, ...)``
      builds a private one-entry registry under
      :data:`~mx_rcnn_tpu.serve.registry.DEFAULT_MODEL` (every pre-ISSUE-7
      call site works unchanged);
    * tenancy — ``ServeRunner(registry=reg, ...)`` serves every family
      in a shared registry; requests carry ``model=`` and each family
      gets its own :class:`_ModelSlot` (own jit, own postprocess, own
      uint8/num_classes), all accounted in ONE compile cache keyed
      ``(model, shape, dtype)``.

    Hot-swap contract: ``run`` compares its slot's version against the
    registry's live pointer and, on mismatch, swaps the predictor's
    params pointer under the slot lock — params are a traced jit
    argument, so a same-structure swap reuses the compiled executable
    (zero recompiles) and takes effect between batches.  ``warm_version``
    stages a candidate's device placement ahead of the commit;
    ``canary`` probes the live path after it.
    """

    def __init__(
        self,
        model=None,
        params=None,
        cfg: Optional[Config] = None,
        num_classes: Optional[int] = None,
        ladder: Optional[BucketLadder] = None,
        max_batch: int = 4,
        donate: Optional[bool] = None,
        device_postprocess: Optional[bool] = None,
        layout_feed: Optional[bool] = None,
        registry=None,
        device=None,
        mask_canvas: Optional[bool] = None,
        precision: Optional[Union[str, Dict[str, str]]] = None,
        parity_check: bool = True,
        parity_box_tol: float = 4.0,
        parity_score_tol: float = 0.1,
        parity_margin: float = 0.1,
        parity_mask_tol: float = 0.25,
    ):
        from mx_rcnn_tpu.serve.registry import DEFAULT_MODEL, ModelRegistry

        if registry is None:
            if model is None or params is None or cfg is None:
                raise ValueError(
                    "ServeRunner needs (model, params, cfg) or registry="
                )
            registry = ModelRegistry()
            registry.register(DEFAULT_MODEL, model, cfg, params)
        self.registry = registry
        self.device = device
        self.default_model = registry.default_model
        self.cfg = cfg if cfg is not None else registry.entry(
            self.default_model
        ).cfg
        self._num_classes_override = num_classes
        self.num_classes = (
            self.cfg.dataset.NUM_CLASSES if num_classes is None else num_classes
        )
        self.ladder = ladder if ladder is not None else BucketLadder(
            self.cfg.SHAPE_BUCKETS
        )
        self.max_batch = int(max_batch)
        self.uint8 = bool(self.cfg.TEST.UINT8_TRANSFER)
        self.compile_cache = CompileCache()
        on_tpu = jax.default_backend() == "tpu"
        if donate is None:
            # donation only pays (and only works) on the TPU; the CPU
            # runtime would log an unused-donation warning per jit
            donate = on_tpu
        if layout_feed is None:
            # layout-matched staging (core/pipeline.py): device_put each
            # batch directly into the compiled forward's input layouts so
            # XLA inserts no input relayout copy.  Off on CPU — layouts
            # are trivial there and the probe would double every compile
            layout_feed = on_tpu
        self.layout_feed = bool(layout_feed)
        self._donate = bool(donate)
        self._device_postprocess = device_postprocess
        self._layouts: Dict[Tuple, object] = {}  # warmup-captured, per sig
        self.staged_batches = 0
        self.layout_staged = 0
        # serve-graph precision (opt-in bf16, see _slot): a global
        # string applies to every model, a dict assigns per model
        self._precision = precision
        self._parity_check = bool(parity_check)
        self._parity_box_tol = float(parity_box_tol)
        self._parity_score_tol = float(parity_score_tol)
        self._parity_margin = float(parity_margin)
        self._parity_mask_tol = float(parity_mask_tol)
        # "model:precision" → last gate report.  Precision is part of
        # the key so one family's int8 report can never overwrite its
        # bf16 one when snapshots from differently-rung runners merge.
        self.parity: Dict[str, Dict] = {}
        # registry-resolution state
        self._slots: Dict[str, _ModelSlot] = {}
        self._slots_lock = make_lock("ServeRunner._slots_lock")
        self._staged: Dict[Tuple[str, int], object] = {}  # (model, ver) → tree
        self.served_buckets: Dict[str, set] = {}
        self.swaps_applied = 0
        # split-path counters (ISSUE 13 overlap accounting; cumulative,
        # read unlocked by snapshots like the staging counters above)
        self.split_dispatches = 0
        self.fetch_stall_s = 0.0  # wall time blocked in complete()'s fetch
        # fetch-byte accounting (ISSUE 14): every complete() sums the
        # nbytes of the host-copied output tree — the measured evidence
        # for the device-postprocess fetch reduction, per model and in
        # total.  last_fetch_bytes is the most recent complete()'s size
        # (read by Replica._finish right after the call, same thread).
        self.fetch_bytes_total = 0
        self.fetch_bytes_by_model: Dict[str, int] = {}
        self.last_fetch_bytes = 0
        # per-request cost accounting (ISSUE 18): dispatch→complete wall
        # per batch (device compute + fetch), attributed to the serving
        # model.  last_device_ms is read by Replica._finish like
        # last_fetch_bytes.
        self.device_ms_by_model: Dict[str, float] = {}
        self.last_device_ms = 0.0
        # mask canvas paste (ISSUE 20): None defers to each model cfg's
        # TEST.MASK_CANVAS; True/False overrides for every mask family
        self._mask_canvas = mask_canvas
        # paste accounting (ISSUE 20): host wall ms and mask payload
        # bytes consumed by the paste+RLE stage (mask_rles_for), per
        # model and in total.  ``overlap`` is the owning Replica's OverlapStats
        # hook (set by Replica.__init__/_recover) so the same numbers
        # pool-merge through the router snapshot alongside fetch_bytes.
        self.pastes = 0
        self.paste_ms_total = 0.0
        self.paste_bytes_total = 0
        self.paste_ms_by_model: Dict[str, float] = {}
        self.paste_bytes_by_model: Dict[str, int] = {}
        self.last_paste_ms = 0.0
        self.last_paste_bytes = 0
        self.overlap = None
        # build the default slot eagerly: construction fails fast on a
        # bad config, and legacy callers read .predictor immediately
        self._slot(self.default_model)

    # ---- registry resolution
    def _place(self, tree):
        """Stage a params tree onto this runner's pinned device (replica
        pinning via ``device=``); unpinned runners let jit place it."""
        if self.device is None:
            return tree
        return jax.device_put(tree, self.device)

    def _precision_for(self, model_id: str) -> str:
        """Compile-cache precision tag for ``model_id``
        ("f32"/"bf16"/"int8")."""
        p = self._precision
        if isinstance(p, dict):
            p = p.get(model_id)
        tag = _PRECISION_TAGS.get(p)
        if tag is None:
            raise ValueError(f"unknown serve precision {p!r}")
        return tag

    def _parity_key(self, model_id: str, precision: str) -> str:
        """Key of :attr:`parity` reports: ``"model:precision"``."""
        return f"{model_id}:{precision}"

    def _slot(self, model_id: str) -> _ModelSlot:
        s = self._slots.get(model_id)
        if s is not None:
            return s
        with self._slots_lock:
            s = self._slots.get(model_id)
            if s is not None:
                return s
            e = self.registry.entry(model_id)
            live = self.registry.live(model_id)
            cfg = e.cfg
            serve_model = e.model
            precision = self._precision_for(model_id)
            if precision == "bf16":
                # the inference-optimized serve graph: compute dtype is
                # baked into the flax module at build time, so the slot
                # gets a REBUILT module at bf16 with the BN affine
                # folded into conv weights (fused_conv_bn — param paths
                # identical, so the registry's f32 params apply as-is
                # and hot-swap structure checks stay valid)
                from mx_rcnn_tpu.models import build_model

                cfg = cfg.replace(
                    network=dataclasses.replace(
                        cfg.network,
                        COMPUTE_DTYPE="bfloat16",
                        FOLD_BN=True,
                    )
                )
                serve_model = build_model(cfg)
            if (
                model_id == self.default_model
                and self._num_classes_override is not None
            ):
                n_cls = self._num_classes_override
            else:
                n_cls = cfg.dataset.NUM_CLASSES
            post = None
            use_post = (
                cfg.TEST.DEVICE_POSTPROCESS
                if self._device_postprocess is None
                else self._device_postprocess
            )
            if precision in ("bf16", "int8") and cfg.network.USE_MASK \
                    and not self._parity_check:
                # a reduced-precision mask graph without the warmup
                # parity gate would serve unverified mask grids — the
                # gate is what checks them (check_parity compares grids
                # of matched pairs)
                raise ValueError(
                    f"precision={precision!r} for mask model {model_id!r} "
                    f"requires parity_check=True (the warmup gate is "
                    f"what verifies the mask grids against f32)"
                )
            if use_post:
                from mx_rcnn_tpu.ops.postprocess import make_test_postprocess

                use_canvas = (
                    getattr(cfg.TEST, "MASK_CANVAS", False)
                    if self._mask_canvas is None
                    else self._mask_canvas
                )
                post = make_test_postprocess(
                    cfg, n_cls, cfg.TEST.SCORE_THRESH,
                    max_out=cfg.TEST.DET_PER_CLASS,
                    paste=bool(use_canvas and cfg.network.USE_MASK),
                )
            if precision == "int8":
                # int8 weight rung: the bound tree is the registry's
                # per-channel quantized form (scales folded once at
                # registry load, shared across runners/replicas), and
                # the serve graph dequantizes on use — params stay a
                # traced jit argument, so swaps remain pointer flips
                from mx_rcnn_tpu.core.quantize import dequantize_tree

                self.registry.enable_quantization(model_id)
                qtree = self.registry.quantized_tree(model_id, live.version)
                predictor = Predictor(
                    serve_model, self._place(qtree), postprocess=post,
                    donate=self._donate, params_transform=dequantize_tree,
                )
            else:
                predictor = Predictor(
                    serve_model, self._place(live.params), postprocess=post,
                    donate=self._donate,
                )
            s = _ModelSlot(
                model_id, predictor, live.version, cfg, n_cls,
                bool(cfg.TEST.UINT8_TRANSFER), precision=precision,
            )
            self._slots[model_id] = s
            return s

    def _sync(self, slot: _ModelSlot) -> None:
        """Apply a committed (or rolled-back) version flip: pointer-swap
        the slot predictor's params to the registry's live version.
        Same structure/shape/dtype tree → the compiled executable is
        reused, so the swap costs one pointer write between batches."""
        live = self.registry.live(slot.model_id)
        if live.version == slot.version:
            return
        with slot.lock:
            live = self.registry.live(slot.model_id)
            if live.version == slot.version:
                return
            staged = self._staged.pop((slot.model_id, live.version), None)
            # any other staged tree for this model is a candidate that
            # lost (rolled back / cancelled): drop its buffers now
            for k in [k for k in self._staged if k[0] == slot.model_id]:
                self._staged.pop(k, None)
            # int8 slots adopt the registry's cached quantized form of
            # the new version (folded on the swap restore path); staged
            # trees for such slots were quantized at warm_version time
            if staged is not None:
                slot.predictor.params = staged
            elif slot.precision == "int8":
                slot.predictor.params = self._place(
                    self.registry.quantized_tree(slot.model_id, live.version)
                )
            else:
                slot.predictor.params = self._place(live.params)
            slot.version = live.version
            self.swaps_applied += 1

    @property
    def predictor(self) -> Predictor:
        """The default model's predictor (legacy single-model surface)."""
        return self._slot(self.default_model).predictor

    # ---- request/batch plumbing
    def make_request(
        self,
        im: np.ndarray,
        deadline: Optional[float] = None,
        model: Optional[str] = None,
    ) -> Request:
        if model is None:
            return prepare_request(im, self.cfg, self.ladder, deadline)
        return prepare_request(
            im, self.registry.entry(model).cfg, self.ladder, deadline,
            model=model,
        )

    def assemble(self, requests: List[Request]) -> Dict[str, np.ndarray]:
        """(model, bucket)-homogeneous requests → device batch padded to
        ``max_batch`` (pad slots replicate slot 0 so every bucket keeps a
        single jit signature and pad work is never a fresh codepath)."""
        with tracing.span(tracing.SERVE_ASSEMBLE,
                          batch=tracing.current_batch(),
                          bucket=requests[0].bucket if requests else None):
            return self._assemble(requests)

    def _assemble(self, requests: List[Request]) -> Dict[str, np.ndarray]:
        n = len(requests)
        if not 0 < n <= self.max_batch:
            raise ValueError(f"batch of {n} vs max_batch={self.max_batch}")
        bh, bw = requests[0].bucket
        if any(r.bucket != (bh, bw) for r in requests):
            raise ValueError("mixed buckets in one batch")
        mid = requests[0].model
        if any(r.model != mid for r in requests):
            raise ValueError("mixed models in one batch")
        uint8 = self._slot(
            self.default_model if mid is None else mid
        ).uint8
        images = np.zeros(
            (self.max_batch, bh, bw, 3), np.uint8 if uint8 else np.float32
        )
        im_info = np.zeros((self.max_batch, 3), np.float32)
        orig_hw = np.zeros((self.max_batch, 2), np.float32)
        for i, r in enumerate(requests):
            images[i] = r.image
            im_info[i] = r.im_info
            orig_hw[i] = r.orig_hw
        for i in range(n, self.max_batch):
            images[i] = images[0]
            im_info[i] = im_info[0]
            orig_hw[i] = orig_hw[0]
        return {"images": images, "im_info": im_info, "orig_hw": orig_hw}

    def _signature(
        self, batch: Dict[str, np.ndarray], model: Optional[str] = None
    ) -> Tuple:
        mid = self.default_model if model is None else model
        return (
            mid,
            batch["images"].shape,
            str(batch["images"].dtype),
            # precision is part of the key: an f32 and a bf16 serve
            # graph for the same (model, shape) are different programs
            self._precision_for(mid),
        )

    def stage(
        self, batch: Dict[str, np.ndarray], model: Optional[str] = None
    ) -> Dict[str, np.ndarray]:
        """Host batch → device batch in the compiled forward's input
        layouts (captured at :meth:`warmup`), so the transfer lands
        device-native and XLA inserts no relayout copy on dispatch.
        Signatures never warmed (lazy first dispatch) take a plain
        ``device_put``; ``layout_staged == staged_batches`` says every
        batch went the layout way."""
        self.staged_batches += 1
        layouts = self._layouts.get(self._signature(batch, model))
        if layouts is None:
            return jax.device_put(batch)
        self.layout_staged += 1
        return jax.device_put(batch, layouts)

    def dispatch(
        self,
        batch: Dict[str, np.ndarray],
        model: Optional[str] = None,
    ) -> ServeHandle:
        """First half of the predict path: sync the slot to the live
        version, account the jit signature, stage the batch (layout-aware
        H2D when ``layout_feed``), and fire the ASYNC jitted forward.
        Returns a device-resident :class:`ServeHandle` without forcing
        the outputs — the caller can keep staging/dispatching further
        batches while the device computes, then :meth:`complete` this
        one.  Adds no jit signatures beyond :meth:`run`'s: same bucket
        pad, same ``max_batch``, same compiled program."""
        mid = self.default_model if model is None else model
        with tracing.span(tracing.SERVE_DISPATCH,
                          batch=tracing.current_batch()):
            slot = self._slot(mid)
            self._sync(slot)
            sig = self._signature(batch, mid)
            self.compile_cache.record(sig)
            if self.layout_feed:
                batch = self.stage(batch, mid)
            bucket = tuple(batch["images"].shape[1:3])
            outputs = slot.predictor.predict_async(batch)
        self.served_buckets.setdefault(mid, set()).add(bucket)
        self.split_dispatches += 1
        return ServeHandle(
            outputs=outputs, model=mid, signature=sig, bucket=bucket,
            dispatch_t=time.monotonic(),
        )

    def complete(self, handle: ServeHandle) -> Dict[str, np.ndarray]:
        """Second half: force the handle's device outputs to host memory
        via the ``host_copy`` owning-copy discipline (blocks until the
        device finishes).  Per-image postprocess stays downstream
        (:meth:`detections_for` on the returned tree), unchanged from the
        blocking path."""
        t0 = time.monotonic()
        with tracing.span(tracing.SERVE_FETCH, batch=tracing.current_batch()):
            out = host_copy(handle.outputs)
        self.fetch_stall_s += time.monotonic() - t0
        nbytes = sum(
            int(getattr(leaf, "nbytes", 0))
            for leaf in jax.tree_util.tree_leaves(out)
        )
        self.last_fetch_bytes = nbytes
        self.fetch_bytes_total += nbytes
        self.fetch_bytes_by_model[handle.model] = (
            self.fetch_bytes_by_model.get(handle.model, 0) + nbytes
        )
        # cost accounting: dispatch→complete wall, attributed to the
        # serving model
        dt_ms = (time.monotonic() - handle.dispatch_t) * 1000.0
        self.last_device_ms = dt_ms
        self.device_ms_by_model[handle.model] = (
            self.device_ms_by_model.get(handle.model, 0.0) + dt_ms
        )
        return out

    def run(
        self,
        batch: Dict[str, np.ndarray],
        model: Optional[str] = None,
    ) -> Dict[str, np.ndarray]:
        """Blocking forward through ``model``'s slot (default model when
        None): exactly :meth:`complete` ∘ :meth:`dispatch`, kept as the
        composition so every pre-split caller and test is untouched.
        The engine overlaps batches with threads; the replica pool
        overlaps through the split halves directly (``Replica`` with
        ``inflight_depth > 1``)."""
        return self.complete(self.dispatch(batch, model=model))

    def _probe_request(self, model_id: str, bucket: Tuple[int, int]) -> Request:
        bh, bw = bucket
        uint8 = self._slot(model_id).uint8
        return Request(
            image=np.zeros((bh, bw, 3), np.uint8 if uint8 else np.float32),
            im_info=np.array([bh, bw, 1.0], np.float32),
            orig_hw=(bh, bw),
            bucket=(bh, bw),
            model=None if model_id == self.default_model else model_id,
        )

    def warmup(self, buckets=None, models=None) -> int:
        """Precompile serving signatures; returns total compile misses.

        Default: every registered model × every ladder rung (the cold
        start).  ``buckets`` partitions the warm set (ISSUE 7 satellite):
        a dict ``{model: iterable-of-(H, W)}`` warms exactly those rungs
        (a recovering replica passes the buckets it actually served —
        models/rungs it never saw are warmed lazily on first dispatch);
        a plain iterable applies to ``models`` (default model only when
        unset).  After warmup, ``compile_cache.misses`` must not grow.
        With ``layout_feed``, also captures each signature's compiled
        input layouts for :meth:`stage`."""
        if isinstance(buckets, dict):
            per = {m: sorted(bs) for m, bs in buckets.items() if bs}
            if not per:  # empty partition: fall back to the full cold start
                per = {m: list(self.ladder)
                       for m in self.registry.model_ids()}
        elif buckets is not None:
            per = {
                m: sorted(buckets)
                for m in (models if models else [self.default_model])
            }
        else:
            per = {
                m: list(self.ladder)
                for m in (models if models else self.registry.model_ids())
            }
        for mid, rungs in per.items():
            slot = self._slot(mid)
            self._sync(slot)
            for bucket in rungs:
                batch = self.assemble(
                    [self._probe_request(mid, tuple(bucket))]
                )
                if self.layout_feed:
                    # before the warm run, so it already stages the way
                    # traffic will (layout_staged == staged_batches)
                    self._layouts[self._signature(batch, mid)] = (
                        slot.predictor.input_layouts(batch)
                    )
                self.run(batch, model=mid)
            if (
                slot.precision in ("bf16", "int8")
                and self._parity_check
                and self._parity_key(mid, slot.precision) not in self.parity
            ):
                self.check_parity(mid)
        return self.compile_cache.misses

    # ---- serve-graph precision parity gate
    def _parity_batch(self, mid: str, bucket: Tuple[int, int]) -> Dict:
        """Deterministic noise probe batch (zeros would make the parity
        comparison vacuous — no proposals clear the score threshold)."""
        bh, bw = bucket
        slot = self._slot(mid)
        rng = np.random.RandomState(0)
        im = rng.randint(0, 256, (bh, bw, 3)).astype(
            np.uint8 if slot.uint8 else np.float32
        )
        req = Request(
            image=im,
            im_info=np.array([bh, bw, 1.0], np.float32),
            orig_hw=(bh, bw),
            bucket=(bh, bw),
            model=None if mid == self.default_model else mid,
        )
        return self.assemble([req])

    def check_parity(
        self,
        model: Optional[str] = None,
        bucket: Optional[Tuple[int, int]] = None,
    ) -> Dict:
        """Gate a reduced-precision serve graph (bf16 compute or int8
        weight rung) on detection parity vs the f32 path.

        Runs one deterministic probe batch (smallest ladder rung unless
        ``bucket`` overrides) through the model's reduced-precision slot
        AND a transient f32 reference predictor built from the
        registered module + live params, then compares detections with
        :func:`detection_parity`.  Outside the documented tolerance →
        :class:`PrecisionParityError`, so a drifting precision config —
        including a corrupted int8 scale fold — fails at warmup, never
        in production results.  The f32 reference is a one-shot compile
        OFF the serving path — it is deliberately not recorded in the
        compile cache, whose signatures account the programs that serve
        traffic.  The report lands in ``self.parity["model:precision"]``
        and engine snapshots."""
        mid = self.default_model if model is None else model
        slot = self._slot(mid)
        if slot.precision not in ("bf16", "int8"):
            report = {"precision": slot.precision, "checked": False}
            self.parity[self._parity_key(mid, slot.precision)] = report
            return report
        bucket = tuple(bucket) if bucket else next(iter(self.ladder))
        batch = self._parity_batch(mid, bucket)
        e = self.registry.entry(mid)
        live = self.registry.live(mid)
        self._sync(slot)
        out_rp = slot.predictor.predict(batch)
        # mirror the slot's postprocess flavor (visible in its output
        # keys) so parity measures PRECISION, not device-vs-host NMS
        post = None
        if "det_boxes" in out_rp:
            from mx_rcnn_tpu.ops.postprocess import make_test_postprocess

            post = make_test_postprocess(
                e.cfg, slot.num_classes, e.cfg.TEST.SCORE_THRESH,
                max_out=e.cfg.TEST.DET_PER_CLASS,
                paste="det_canvas" in out_rp,
            )
        ref_predictor = Predictor(
            e.model, self._place(live.params), postprocess=post,
            donate=False,
        )
        out_f32 = ref_predictor.predict(batch)
        thresh = float(slot.cfg.TEST.SCORE_THRESH)
        dets_rp, masks_rp = self.detections_for(
            out_rp, batch, 0, model=model, with_masks=True
        )
        ref_dets, ref_masks = detections_from_output(
            out_f32, batch["im_info"][0], tuple(batch["orig_hw"][0]),
            e.cfg, slot.num_classes,
        )
        ref_dets, ref_masks = cap_detections(
            ref_dets, e.cfg.TEST.MAX_PER_IMAGE, ref_masks
        )
        report = detection_parity(
            ref_dets, dets_rp, thresh, margin=self._parity_margin
        )
        report.update(
            precision=slot.precision, checked=True, bucket=list(bucket),
            box_tol_px=self._parity_box_tol,
            score_tol=self._parity_score_tol,
        )
        mask_ok = True
        if e.cfg.network.USE_MASK:
            # mask families must not pass the gate on boxes alone —
            # compare the matched pairs' S×S probability grids too
            report.update(mask_parity(
                ref_dets, ref_masks or {}, dets_rp, masks_rp or {},
                thresh, margin=self._parity_margin,
            ))
            report["mask_tol"] = self._parity_mask_tol
            mask_ok = report["max_mask_prob_delta"] <= self._parity_mask_tol
        ok = (
            report["unmatched_confident"] == 0
            and report["max_box_delta_px"] <= self._parity_box_tol
            and report["max_score_delta"] <= self._parity_score_tol
            and mask_ok
        )
        report["ok"] = ok
        self.parity[self._parity_key(mid, slot.precision)] = report
        if not ok:
            raise PrecisionParityError(
                f"{slot.precision} serve graph for model {mid!r} outside "
                f"parity tolerance vs f32: {report}"
            )
        return report

    # ---- hot-swap (SwapController target surface)
    def warm_version(
        self,
        model: Optional[str],
        version: int,
        params,
        buckets=None,
        abort=None,
    ) -> int:
        """Drive CANDIDATE params through this runner's served
        signatures for ``model``, off the live path
        (:meth:`Predictor.predict_with` — params are a jit argument, so
        the compiled executables are reused: zero new compile misses).
        The device-placed tree is staged under ``(model, version)`` for
        :meth:`_sync` to adopt at commit.  ``abort`` (the controller's
        cancel hook) is called before the device placement and between
        rungs — a cancelled swap raises there, before any further
        device work.  Returns the number of rungs warmed."""
        mid = self.default_model if model is None else model
        slot = self._slot(mid)
        if abort is not None:
            abort()
        if slot.precision == "int8":
            # stage the candidate in the slot's own form: quantized via
            # the registry's per-version cache (folded once on the
            # restore path) so N replicas warming the same candidate
            # share one fold; local fallback covers registries that
            # stage versions outside the swap path
            try:
                tree = self.registry.quantized_tree(mid, int(version))
            except Exception:  # noqa: BLE001 — e.g. version not in registry
                from mx_rcnn_tpu.core.quantize import quantize_tree

                tree = quantize_tree(params)
            placed = self._place(tree)
        else:
            placed = self._place(params)
        if buckets is None:
            buckets = sorted(self.served_buckets.get(mid, ())) or list(
                self.ladder
            )
        warmed = 0
        for bucket in buckets:
            if abort is not None:
                abort()
            batch = self.assemble([self._probe_request(mid, tuple(bucket))])
            slot.predictor.predict_with(placed, batch)
            warmed += 1
        self._staged[(mid, int(version))] = placed
        return warmed

    def canary(self, model: Optional[str] = None) -> int:
        """One probe batch through the LIVE path (smallest served rung):
        forces :meth:`_sync` onto the just-committed version and proves
        the swapped predictor actually serves.  Raising here is the
        rollback trigger."""
        mid = self.default_model if model is None else model
        served = sorted(self.served_buckets.get(mid, ()))
        bucket = served[0] if served else next(iter(self.ladder))
        batch = self.assemble([self._probe_request(mid, bucket)])
        self.run(batch, model=mid)
        return 1

    def discard_version(self, model: Optional[str], version: int) -> None:
        """Drop a losing candidate's staged device tree (rollback or
        cancel cleanup)."""
        mid = self.default_model if model is None else model
        self._staged.pop((mid, int(version)), None)

    def run_version(
        self,
        batch: Dict[str, np.ndarray],
        model: Optional[str] = None,
        version: Optional[int] = None,
    ) -> Dict[str, np.ndarray]:
        """Blocking forward through an EXPLICIT version: the live slot
        when ``version`` is the live one (or None), else the staged
        candidate tree parked by :meth:`warm_version` — the rollout
        split/shadow predict path.  Params are a jit argument
        (:meth:`Predictor.predict_with`), so a candidate with the same
        tree structure reuses the live compiled executables: a split
        adds zero jit signatures.  Raises
        :class:`~mx_rcnn_tpu.serve.registry.UnknownVersion` when the
        version is neither live nor staged (a rolled-back arm) — the
        engine's cue to fall back to the incumbent."""
        from mx_rcnn_tpu.serve.registry import UnknownVersion

        mid = self.default_model if model is None else model
        slot = self._slot(mid)
        self._sync(slot)
        if version is None or int(version) == slot.version:
            return self.run(batch, model=mid)
        placed = self._staged.get((mid, int(version)))
        if placed is None:
            raise UnknownVersion(
                f"model {mid!r} v{int(version)} is neither live "
                f"(v{slot.version}) nor staged on this runner"
            )
        sig = self._signature(batch, mid)
        self.compile_cache.record(sig)
        if self.layout_feed:
            batch = self.stage(batch, mid)
        self.served_buckets.setdefault(mid, set()).add(
            tuple(batch["images"].shape[1:3])
        )
        return slot.predictor.predict_with(placed, batch)

    # ---- per-image postprocess
    def detections_for(
        self,
        out: Dict[str, np.ndarray],
        batch: Dict[str, np.ndarray],
        index: int,
        orig_hw: Optional[Tuple[float, float]] = None,
        thresh: Optional[float] = None,
        model: Optional[str] = None,
        with_masks: bool = False,
    ) -> ClsDets:
        """Per-image capped detections; ``with_masks=True`` returns
        ``(cls_dets, mask_probs)`` instead (mask_probs None for box
        families) — the capped per-class grids ready for
        ``eval/segm.py::rles_for_detections``."""
        slot = self._slot(self.default_model if model is None else model)
        if orig_hw is None:
            orig_hw = tuple(batch["orig_hw"][index])
        cls_dets, mask_probs = detections_from_output(
            out, batch["im_info"][index], orig_hw, slot.cfg,
            slot.num_classes, index=index, thresh=thresh,
        )
        cls_dets, mask_probs = cap_detections(
            cls_dets, slot.cfg.TEST.MAX_PER_IMAGE, mask_probs
        )
        if with_masks:
            return cls_dets, mask_probs
        return cls_dets

    def mask_rles_for(
        self,
        out: Dict[str, np.ndarray],
        batch: Dict[str, np.ndarray],
        index: int,
        orig_hw: Optional[Tuple[float, float]] = None,
        thresh: Optional[float] = None,
        model: Optional[str] = None,
    ):
        """Per-image capped detections + CANVAS-space mask RLEs — the
        streaming mask serve path.  Returns ``(cls_dets, rles)`` with
        ``rles[j]`` aligned row-for-row with ``cls_dets[j]``; RLEs are
        in the fixed (bucket-extent) canvas the image was padded to.

        Two paths, identical bytes by construction:

        * device canvas (``det_canvas`` in ``out``, paste ran in the
          jit): the host keeps only RLE encoding;
        * host paste (device postprocess without paste): each
          survivor's fetched LOGIT grid goes through the numpy
          fixed-point mirror (``eval/segm.py::paste_mask_canvas``).

        Accounts ``paste_ms`` (host wall in the paste+RLE stage) and
        ``paste_bytes`` (mask payload consumed: canvas bytes vs grid
        bytes) per model, and mirrors both into the owning replica's
        :class:`~mx_rcnn_tpu.serve.metrics.OverlapStats` when attached."""
        from mx_rcnn_tpu.eval.segm import canvas_rles
        from mx_rcnn_tpu.native import rle as rle_mod

        if "det_masks" not in out:
            raise ValueError(
                "mask_rles_for needs the fused device-postprocess mask "
                "outputs (det_masks); raw-head batches have no canvas "
                "contract"
            )
        mid = self.default_model if model is None else model
        slot = self._slot(mid)
        if orig_hw is None:
            orig_hw = tuple(batch["orig_hw"][index])
        cls_dets, _probs, rows = detections_from_output(
            out, batch["im_info"][index], orig_hw, slot.cfg,
            slot.num_classes, index=index, thresh=thresh, with_rows=True,
        )
        cls_dets, _probs, rows = cap_detections(
            cls_dets, slot.cfg.TEST.MAX_PER_IMAGE, _probs, rows=rows
        )
        midx = np.asarray(out["det_mask_idx"][index])
        lut = {int(f): p for p, f in enumerate(midx) if f >= 0}
        max_out_dim = out["det_boxes"].shape[2]
        hc = int(batch["images"].shape[1])
        wc = int(batch["images"].shape[2])
        scale = float(batch["im_info"][index][2])
        canvas = out.get("det_canvas")
        rles: Dict[int, list] = {}
        t0 = time.monotonic()
        if canvas is not None:
            cv = np.asarray(canvas[index])
            nbytes = int(cv.nbytes)
            empty = np.zeros((hc, wc), np.uint8)
            for j in range(1, slot.num_classes):
                out_j = []
                for rr in rows[j]:
                    p = lut.get((j - 1) * max_out_dim + int(rr))
                    # an unmapped row only exists past the device's
                    # max_det budget; its device canvas would have been
                    # all zeros too (the -80-logit fill story)
                    out_j.append(rle_mod.encode(
                        np.ascontiguousarray(cv[p]) if p is not None
                        else empty
                    ))
                rles[j] = out_j
        else:
            grids_all = np.asarray(out["det_masks"][index])
            nbytes = int(grids_all.nbytes)
            fill = np.full(grids_all.shape[1:], -80.0, np.float32)
            for j in range(1, slot.num_classes):
                grids = [
                    grids_all[lut[(j - 1) * max_out_dim + int(rr)]]
                    if (j - 1) * max_out_dim + int(rr) in lut else fill
                    for rr in rows[j]
                ]
                rles[j] = canvas_rles(grids, cls_dets[j], scale, hc, wc)
        dt = time.monotonic() - t0
        self.pastes += 1
        self.last_paste_ms = dt * 1000.0
        self.last_paste_bytes = nbytes
        self.paste_ms_total += dt * 1000.0
        self.paste_bytes_total += nbytes
        self.paste_ms_by_model[mid] = (
            self.paste_ms_by_model.get(mid, 0.0) + dt * 1000.0
        )
        self.paste_bytes_by_model[mid] = (
            self.paste_bytes_by_model.get(mid, 0) + nbytes
        )
        if self.overlap is not None:
            self.overlap.note_paste(dt, nbytes=nbytes, model=mid)
        return cls_dets, rles

    # ---- synchronous single image (demo path)
    def detect(self, im: np.ndarray, thresh: Optional[float] = None) -> ClsDets:
        req = self.make_request(im)
        batch = self.assemble([req])
        out = self.run(batch)
        return self.detections_for(out, batch, 0, thresh=thresh)


def detect_single(
    predictor: Predictor,
    im: np.ndarray,
    cfg: Config,
    num_classes: int,
    thresh: Optional[float] = None,
) -> ClsDets:
    """One-shot detection with a caller-owned :class:`Predictor` (the
    demo path: checkpoint already loaded, no engine).  Batch of 1, no
    cross-class cap — identical semantics to the historical
    ``demo_net`` inner loop, now routed through the shared
    :func:`detections_from_output`."""
    ladder = BucketLadder(cfg.SHAPE_BUCKETS)
    req = prepare_request(im, cfg, ladder)
    batch = {
        "images": req.image[None],
        "im_info": req.im_info[None],
        "orig_hw": np.asarray([req.orig_hw], np.float32),
    }
    out = predictor.predict(batch)
    cls_dets, _ = detections_from_output(
        out, batch["im_info"][0], req.orig_hw, cfg, num_classes, thresh=thresh
    )
    return cls_dets
