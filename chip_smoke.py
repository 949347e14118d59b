#!/usr/bin/env python3
"""Chip smoke: the flagship trainer and the serving engine, through their
normal entry points, once, on the TPU.

    python chip_smoke.py               # one chip: kernels, train (C4 bf16 b8, f32 b1; pyramid bf16 b8, f32 b1; VGG-16 bf16 b8; Deformable ConvNets bf16 b8), serve
    python chip_smoke.py --multichip   # four chips: DP train + DP-vs-single check

Full width (ResNet-101 C4, the ResNet-50 pyramid, VGG-16 and the
Deformable ConvNets detector on the (608, 1024) bucket, the default serve
ladder), random weights from a seed, synthetic data from a seed; depth of the RUN is cut (a handful of steps, 16 requests), not the model.  Every
phase checks its own output by the repo's means — the kernels against
their jnp/numpy references on a small input, the trainer's guard counters,
the engine's snapshot, the compile-cache audit — and raises on the first
thing that is off; nothing catches it, so any failed phase is a
non-zero exit.  Refuses to run (non-zero, no result line) unless JAX's
first device is a TPU.  One process, no children: a chip belongs to one
process at a time.

The last line of stdout is the result the driver reads:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

_TRAIN_COMMON = [
    "--network", "resnet", "--dataset", "PascalVOC", "--synthetic", "64",
    "--epochs", "1", "--frequent", "1",
]
#: the bench configuration: flagship C4, bf16, 8 images on the chip
TRAIN_BF16_ARGV = _TRAIN_COMMON + [
    "--batch_images", "8", "--compute_dtype", "bfloat16", "--max_steps", "6",
]
#: the repo's DEFAULT configuration (README quickstart without
#: --compute_dtype; what PARITY.md's gate evidence is for)
TRAIN_F32_ARGV = _TRAIN_COMMON + ["--batch_images", "1", "--max_steps", "2"]
#: the pyramid (Faster R-CNN ResNet-50-FPN, COCO's 81 classes) as the cell
#: fpn_train_b8 runs it.  --lr as the benchmark's mix: the default spikes
#: the loss of a random network under frozen BN (PERF.md, PR 21)
_TRAIN_FPN_COMMON = [
    "--network", "resnet_fpn", "--dataset", "coco", "--synthetic", "64",
    "--epochs", "1", "--frequent", "1", "--lr", "1e-05",
]
TRAIN_FPN_BF16_ARGV = _TRAIN_FPN_COMMON + [
    "--batch_images", "8", "--compute_dtype", "bfloat16", "--max_steps", "6",
]
#: the family's DEFAULT per-chip batch, the shape whose per-level top-k
#: used to abort the chip's compiler (ROADMAP R1)
TRAIN_FPN_F32_ARGV = _TRAIN_FPN_COMMON + [
    "--batch_images", "1", "--max_steps", "2",
]
#: Pallas kernels the compiled pyramid step must hold: P2 and P3 stream,
#: P4 and P5 stay resident (fits_vmem at bf16, 14x14), one proposal NMS
FPN_STEP_KERNELS = (
    "pallas_roi_features_stream_fwd", "pallas_roi_features_stream_bwd",
    "pallas_roi_features_fwd", "pallas_roi_features_bwd", "pallas_nms_mask",
)
#: the upstream's default recipe (Faster R-CNN VGG-16 on VOC) as the cell
#: vgg_train_b8 runs it: ROI max pooling forward and differentiated, the
#: fc6 / fc7 head with dropout
TRAIN_VGG_BF16_ARGV = [
    "--network", "vgg", "--dataset", "PascalVOC", "--synthetic", "64",
    "--epochs", "1", "--frequent", "1", "--lr", "1e-05",
    "--batch_images", "8", "--compute_dtype", "bfloat16", "--max_steps", "6",
]
#: Pallas kernels the compiled VGG step must hold: the proposal NMS and
#: the ROI max pooling pair (no ``while`` sweep in their place)
VGG_STEP_KERNELS = (
    "pallas_nms_mask", "pallas_roi_pool_fwd", "pallas_roi_pool_bwd",
)
#: Deformable ConvNets (Faster R-CNN ResNet-101 on VOC) as the cell
#: dcn_train_b8 runs it: the deformable conv5 and the deformable ROI
#: pooling, forward and differentiated, in plain jnp
TRAIN_DCN_BF16_ARGV = [
    "--network", "resnet_dcn", "--dataset", "PascalVOC", "--synthetic", "64",
    "--epochs", "1", "--frequent", "1", "--lr", "1e-05",
    "--batch_images", "8", "--compute_dtype", "bfloat16", "--max_steps", "6",
]
#: tools/serve.py without --small: flagship, default ladder, f32
SERVE_ARGV = [
    "--network", "resnet", "--max_batch", "4", "--requests", "16",
    "--concurrency", "8", "--seed", "0",
]
#: four chips, two images each: global batch 8, the program known to fit
MULTICHIP_TRAIN_ARGV = _TRAIN_COMMON + [
    "--batch_images", "2", "--compute_dtype", "bfloat16", "--max_steps", "4",
]
#: DP-vs-single-device first-step loss, relative.  bf16 activations round
#: at 2^-8 ≈ 0.4%; the two sides run the same per-image math under
#: different batch tilings (2 vs 8 per program), so a few roi/anchor
#: sampling ties may also flip.  The f32 CPU twin
#: (tests/test_parallel.py::test_dp_grads_match_single_device) holds 1e-5.
DP_LOSS_RTOL = 2e-2


def say(**fields) -> None:
    """One JSON line per fact worth keeping from the run."""
    print(json.dumps(fields, sort_keys=True, default=str), flush=True)


def result_line(devices) -> str:
    """The contract's last line, from the devices as JAX reports them."""
    d = devices[0]
    return json.dumps({
        "ok": True,
        "device": {
            "platform": d.platform, "kind": d.device_kind,
            "count": len(devices),
        },
    })


def require_tpu(n_chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r} "
            f"({len(devices)} device(s)) — not running on it"
        )
    if len(devices) != n_chips:
        raise SystemExit(
            f"chip_smoke: this mode needs {n_chips} chip(s), JAX found "
            f"{len(devices)}"
        )
    return devices


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, by listening to
    its own duration events; ``lap()`` returns the seconds since the
    previous lap."""

    def __init__(self):
        import jax

        self._total = 0.0
        self._mark = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self._total += secs

    def lap(self) -> float:
        out, self._mark = self._total - self._mark, self._total
        return round(out, 1)


def check_native() -> None:
    """The C host libraries must have BUILT here: ``native/_build.py``
    degrades to numpy with a warning, which on a machine with a C
    compiler is a fault, not a fallback."""
    from mx_rcnn_tpu.native import hostops, rle

    for mod in (hostops, rle):
        if mod._lib() is None:
            raise RuntimeError(f"{mod.__name__}: C library did not build")


# ------------------------------------------------------------------- kernels
#: |kernel − reference| over the reference's scale.  f32 kernels run
#: HIGHEST-precision MXU passes (interpret mode holds 1e-5 against the
#: gather reference; a wrong block or layout is off by O(1)); bf16 is the
#: interpret tests' own bound.
KERNEL_F32_RTOL = 1e-3
KERNEL_BF16_RTOL = 5e-2


def _random_rois(rng, b, r, h_img, w_img):
    """(B, R, 4) image-coordinate boxes, border and degenerate ones in."""
    import numpy as np

    x1 = rng.rand(b, r) * w_img * 0.8
    y1 = rng.rand(b, r) * h_img * 0.8
    x2 = x1 + rng.rand(b, r) * (w_img - x1)
    y2 = y1 + rng.rand(b, r) * (h_img - y1)
    rois = np.stack([x1, y1, x2, y2], axis=-1).astype(np.float32)
    rois[:, 0] = [0, 0, w_img - 1, h_img - 1]                 # whole image
    rois[:, 1] = [5, 5, 5.5, 5.5]                             # sub-cell
    rois[:, 2] = [w_img - 2, h_img - 2, w_img + 50, h_img + 50]  # past border
    return rois


#: the maps the pyramid's train step hands the streaming pair: P2 and P3
#: of a (608, 1024) image at 256 channels, 8 images (tag, map, stride);
#: 128 rois an image, 14x14
STREAM_TRAIN_MAPS = (
    ("stream_p2", (8, 152, 256, 256), 4),
    ("stream_p3", (8, 76, 128, 256), 8),
)


#: the map the VGG train step hands the ROI max pooling pair (cell
#: vgg_train_b8): conv5_3 of eight (608, 1024) images, 128 rois an image,
#: 7x7; under the interpreter a map small enough for it
ROI_POOL_TRAIN_MAP = (8, 38, 64, 512)
ROI_POOL_TINY_MAP = (2, 12, 20, 128)


def _level_rois(rng, b, r, h_img, w_img, stride):
    """(B, R, 4) boxes of the level that pools them at ``stride``: sides up
    to 28 cells (eq. 1 hands P2 the rois under 112 pixels, P3 those under
    224), anywhere in the image."""
    import numpy as np

    side = rng.rand(b, r, 2) * 28 * stride
    x1 = rng.rand(b, r) * (w_img - side[..., 0])
    y1 = rng.rand(b, r) * (h_img - side[..., 1])
    return np.stack([x1, y1, x1 + side[..., 0], y1 + side[..., 1]],
                    axis=-1).astype(np.float32)


def kernels_phase(interpret: bool = False, name: str = "kernels",
                  train_maps=None) -> dict:
    """The Pallas kernels against the repo's own references, on a small
    input, on the device that will run them.  The tier-1 tests check the
    kernels' arithmetic in interpret mode; what Mosaic compiled from them
    only a chip can check.  ROIAlign (resident and streaming, forward and
    backward; the resident forward with ``valid_hw`` as serving calls it)
    against the gather reference ``ops.roi_align.roi_align``; ROI max
    pooling (forward and backward, at the VGG train step's shape on the
    chip) against the jnp sweep ``ops.roi_align.roi_pool``, EQUAL on a map
    no two cells of which tie;
    NMS against the numpy oracle ``ops.nms.nms_numpy``, on boxes chosen
    so that no pair sits within 1e-4 of the IoU threshold.
    On the chip (not under the interpreter, where they would take many
    minutes) also the streaming pair at P2's and P3's shapes in the
    pyramid's train step (``STREAM_TRAIN_MAPS``; ``train_maps`` lets the
    CPU rehearsal put tiny ones in their place), each twice: every roi
    pooled, and as ``pool_levels`` calls it, with a span of the level's
    own rois in a sorted list (a third to a half of them, the rest zero
    boxes) against the gather on the own rois alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mx_rcnn_tpu.ops.nms import nms_numpy
    from mx_rcnn_tpu.ops.pallas.nms import nms_mask_sorted_pallas
    from mx_rcnn_tpu.ops.pallas.roi_align import roi_align_pallas
    from mx_rcnn_tpu.ops.pallas.roi_align_stream import roi_align_stream
    from mx_rcnn_tpu.ops.pallas.roi_pool import roi_pool_pallas
    from mx_rcnn_tpu.ops.roi_align import roi_align, roi_pool

    rng = np.random.RandomState(0)
    errs = {}

    def fwd_bwd(fn):
        """→ jitted (feat, rois, cot) → (output, d(sum(output·cot))/d(feat))
        as f32 numpy; one compile a dtype however often it is called."""
        def loss(f, r, c):
            out = fn(f, r)
            return (out.astype(jnp.float32) * c).sum(), out

        run = jax.jit(jax.value_and_grad(loss, has_aux=True))

        def call(feat, rois, cot):
            (_, out), grad = run(feat, rois, cot)
            return np.asarray(out, np.float32), np.asarray(grad, np.float32)

        return call

    def rel(got, ref):
        return float(np.abs(got - ref).max() / np.abs(ref).max())

    cases = [
        # (tag, kernel, feature map, rois/image, pooled, stride, valid_hw)
        # valid_hw: the serve graph's call, forward only, samples clamped
        # to each image's own extent — one image fills a corner of the
        # 608x1024 canvas, so rois lie across its edge and past it
        ("resident", roi_align_pallas, (2, 38, 64, 256), 64, (7, 7), 16,
         [[400.0, 700.0], [608.0, 1024.0]]),
        ("stream", roi_align_stream, (1, 152, 256, 128), 128, (14, 14), 4,
         None),
    ]
    if train_maps is None:
        train_maps = () if interpret else STREAM_TRAIN_MAPS
    cases += [(tag, roi_align_stream, shape, 128, (14, 14), stride, None)
              for tag, shape, stride in train_maps]
    # the train-shape cases draw from a stream of their own, so the NMS
    # probe below keeps the boxes it always had (checked not borderline)
    train_rng = np.random.RandomState(1)
    train_tags = {tag for tag, _shape, _stride in train_maps}
    for tag, kernel, shape, n_rois, pooled, stride, valid_hw in cases:
        b, h, w, c = shape
        scale = 1.0 / stride
        draw = train_rng if tag in train_tags else rng
        feat = jnp.asarray(draw.randn(*shape).astype(np.float32))
        rois = jnp.asarray(
            _random_rois(draw, b, n_rois, h * stride, w * stride))
        cot = jnp.asarray(draw.randn(b, n_rois, *pooled, c).astype(np.float32))

        def gather(f, r):
            return jax.vmap(
                lambda f1, r1: roi_align(f1, r1, pooled, scale, 2)
            )(f, r)

        def every_roi(f, r):
            return kernel(f, r, pooled, scale, 2, interpret)

        reference, pallas = fwd_bwd(gather), fwd_bwd(every_roi)
        ref_out, ref_grad = reference(feat, rois, cot)
        for dtype, f in (("f32", feat), ("bf16", feat.astype(jnp.bfloat16))):
            out, grad = pallas(f, rois, cot)
            errs[f"{tag}_{dtype}_fwd"] = rel(out, ref_out)
            errs[f"{tag}_{dtype}_bwd"] = rel(grad, ref_grad)
        if tag in train_tags:
            # the step's own call: the list sorted by level, this level's
            # rois in [start, start + count), every other roi a zero box
            count = draw.randint(n_rois // 3, n_rois // 2 + 1, size=b)
            start = (draw.rand(b) * (n_rois - count + 1)).astype(np.int64)
            span = jnp.asarray(np.stack([start, count], axis=1), jnp.int32)
            at = np.arange(n_rois)[None]
            own = (at >= start[:, None]) & (at < (start + count)[:, None])
            rois = jnp.asarray(np.where(own[..., None], _level_rois(
                draw, b, n_rois, h * stride, w * stride, stride), 0.0))
            own = own[..., None, None, None]
            # the gather pools every roi: only the own rois' cotangent is
            # non-zero, and only their rows are compared
            cot = jnp.where(own, cot, 0.0)
            ref_out, ref_grad = reference(feat, rois, cot)
            ref_out = np.where(own, ref_out, 0.0)
            spanned = fwd_bwd(lambda f, r: jnp.where(own, kernel(
                f, r, pooled, scale, 2, interpret, span), 0.0))
            for dtype, f in (("f32", feat), ("bf16", feat.astype(jnp.bfloat16))):
                out, grad = spanned(f, rois, cot)
                errs[f"{tag}_span_{dtype}_fwd"] = rel(out, ref_out)
                errs[f"{tag}_span_{dtype}_bwd"] = rel(grad, ref_grad)
        if valid_hw is None:
            continue
        valid_hw = jnp.asarray(valid_hw)
        ref_out = np.asarray(jax.jit(jax.vmap(
            lambda f1, r1, v1: roi_align(f1, r1, pooled, scale, 2,
                                         valid_hw=v1)
        ))(feat, rois, valid_hw))
        clamped = jax.jit(lambda f: kernel(
            f, rois, pooled, scale, 2, interpret, valid_hw=valid_hw))
        for dtype, f in (("f32", feat), ("bf16", feat.astype(jnp.bfloat16))):
            errs[f"{tag}_valid_hw_{dtype}_fwd"] = rel(
                np.asarray(clamped(f), np.float32), ref_out)

    # ROI max pooling: a permutation of whole numbers (exact in f32, no
    # two cells tie, so MXNet's first-cell rule and the sweep's shared
    # gradient name the same cell) and whole-number cotangents (exact
    # sums): the pair equals the sweep, one image after the other
    shape = ROI_POOL_TINY_MAP if interpret else ROI_POOL_TRAIN_MAP
    pool_rng = np.random.RandomState(2)
    feat = jnp.asarray(pool_rng.permutation(int(np.prod(shape))).reshape(
        shape).astype(np.float32))
    rois = jnp.asarray(_random_rois(
        pool_rng, shape[0], 128, shape[1] * 16, shape[2] * 16))
    cot = jnp.asarray(pool_rng.randint(
        1, 9, (shape[0], 128, 7, 7, shape[3])).astype(np.float32))
    ref_out, ref_grad = fwd_bwd(lambda f, r: jax.lax.map(
        lambda fr: roi_pool(fr[0], fr[1], (7, 7), 1 / 16), (f, r)
    ))(feat, rois, cot)
    out, grad = fwd_bwd(lambda f, r: roi_pool_pallas(
        f, r, (7, 7), 1 / 16, interpret))(feat, rois, cot)
    exact = {"roi_pool_f32_fwd": rel(out, ref_out),
             "roi_pool_f32_bwd": rel(grad, ref_grad)}
    errs.update(exact)

    # NMS: a dense field of boxes, score-sorted as the proposal path hands
    # them over
    n, thresh = 2048, 0.7
    ctr = rng.rand(n, 2).astype(np.float32) * 150
    half = (rng.rand(n, 2).astype(np.float32) * 60 + 12) / 2
    boxes = np.hstack([ctr - half, ctr + half])
    scores = np.sort(rng.rand(n).astype(np.float32))[::-1].copy()
    dets = np.hstack([boxes, scores[:, None]])
    oracle = set(nms_numpy(dets, thresh))
    if not (oracle == set(nms_numpy(dets, thresh - 1e-4))
            == set(nms_numpy(dets, thresh + 1e-4))):
        raise RuntimeError(f"{name}: NMS probe boxes are borderline")
    keep = np.asarray(nms_mask_sorted_pallas(
        jnp.asarray(boxes), jnp.ones((n,), bool), thresh, interpret
    ))
    nms_diff = len(set(np.where(keep)[0]) ^ oracle)

    say(phase=name, rel_err=errs, nms_kept=len(oracle), nms_diff=nms_diff,
        f32_rtol=KERNEL_F32_RTOL, bf16_rtol=KERNEL_BF16_RTOL)
    for key, err in errs.items():
        tol = KERNEL_BF16_RTOL if "bf16" in key else KERNEL_F32_RTOL
        if key in exact:
            tol = 0.0
        if not err <= tol:  # also catches NaN
            raise RuntimeError(f"{name}: {key} off by {err:.3g} > {tol}")
    if nms_diff:
        raise RuntimeError(
            f"{name}: NMS keep set differs from the oracle in "
            f"{nms_diff} box(es)"
        )
    return errs


# --------------------------------------------------------------------- train
def _compiled_step_text(cli, found: dict):
    """Stand between ``train_net`` and the step it builds, as the
    benchmark's driver does, and keep the text of the program the chip's
    compiler made of it: the step is compiled once more from the shapes
    of its first call, which the persistent cache answers.  → the saved
    ``make_train_step`` to put back."""
    import jax

    make = cli.make_train_step

    def spying(*a, **kw):
        step = make(*a, **kw)

        def first_then_plain(state, batch, rng, **kws):
            if found:
                return step(state, batch, rng, **kws)
            shapes = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=getattr(x, "sharding", None)),
                (state, batch, rng))
            out = step(state, batch, rng, **kws)  # donates its state
            found["text"] = step.lower(*shapes, **kws).compile().as_text()
            return out

        return first_then_plain

    cli.make_train_step = spying
    return make


def train_phase(argv, name: str = "train", kernels=(), scopes=()):
    """``train_net`` on ``argv`` exactly as ``train_end2end.main`` calls
    it; fails unless every planned step was applied with a finite loss
    and the NaN guard never had to act (it would otherwise turn a broken
    step into a clean exit).  ``kernels``: names the compiled step must
    hold; with them the loss must also stay flat or fall (no loss over
    twice the first: the default LR's spike on these weights was 2000x).
    ``scopes``: stage scopes some operation of the compiled step must lie
    under (a component of its ``op_name``).  → (final state, report)."""
    from mx_rcnn_tpu.tools import train_end2end as cli

    args = cli.parse_args(argv)
    report: dict = {}
    compiled: dict = {}
    make = _compiled_step_text(cli, compiled) if kernels or scopes else None
    t0 = time.monotonic()
    try:
        state = cli.train_net(args, report=report)
    finally:
        if make is not None:
            cli.make_train_step = make
    wall = time.monotonic() - t0
    if make is not None and "text" not in compiled:
        raise RuntimeError(
            f"{name}: train_net did not build its step through "
            f"make_train_step (more than one device?)")
    held = {k: compiled["text"].count(f"%{k}") for k in kernels}
    held.update({f"/{s}/": compiled["text"].count(f"/{s}/") for s in scopes})
    losses = [loss for _step, loss in report["losses"]]
    deform = report.get("deform") or {}
    say(phase=name, wall_s=round(wall, 1), steps=report["steps"],
        steps_applied=report["steps_applied"], losses=losses,
        skipped_batches=report["skipped_batches"],
        retried_steps=report["retried_steps"],
        rollbacks=report["rollbacks"],
        roi_levels=report.get("roi_levels"), kernels=held,
        # Deformable ConvNets: each deformable layer's sampling points
        # inside the map, as a share
        deform_inside={k[len("deform_inside_"):]: v / deform["deform_points"]
                       for k, v in deform.items()
                       if k.startswith("deform_inside_")})
    if not all(held.values()):
        raise RuntimeError(f"{name}: compiled step holds {held}")
    if kernels and not max(losses) <= 2 * losses[0]:
        raise RuntimeError(f"{name}: loss neither flat nor falling: {losses}")
    if report["steps"] != args.max_steps:
        raise RuntimeError(
            f"{name}: planned {args.max_steps} steps, loop ran "
            f"{report['steps']}"
        )
    if report["steps_applied"] != report["steps"]:
        raise RuntimeError(
            f"{name}: {report['steps']} steps dispatched, "
            f"{report['steps_applied']} applied to the optimizer state"
        )
    for key in ("skipped_batches", "retried_steps", "rollbacks"):
        if report[key]:
            raise RuntimeError(f"{name}: guard acted — {key}={report[key]}")
    if len(losses) != report["steps"] or not all(map(math.isfinite, losses)):
        raise RuntimeError(f"{name}: losses not all finite: {losses}")
    return state, report


# --------------------------------------------------------------------- serve
def _cross_bucket(runner) -> dict:
    """The same square image through every ladder rung it fits: do the
    detections agree, and how closely?  Informational — on CPU the repo
    pins same-kept-set + last-ulp coordinates
    (tests/test_serve_runner.py); this prints what the chip does.  Also
    whether the layout-matched feed stages images anywhere a plain
    ``device_put`` would not."""
    import jax
    import numpy as np

    from mx_rcnn_tpu.serve.buckets import BucketLadder
    from mx_rcnn_tpu.serve.loadgen import synthetic_image
    from mx_rcnn_tpu.serve.runner import prepare_request

    side = min(min(b) for b in runner.ladder) // 2
    im = synthetic_image(0, side, side, seed=1)
    per_bucket, feed_is_default = [], []
    for bucket in runner.ladder:
        req = prepare_request(im, runner.cfg, BucketLadder([bucket]))
        batch = runner.assemble([req])
        if runner.layout_feed:
            feed_is_default.append(
                runner.stage(batch)["images"].format.layout
                == jax.device_put(batch["images"]).format.layout
            )
        out = runner.run(batch)
        per_bucket.append(runner.detections_for(out, batch, 0))
    first = per_bucket[0]
    # per-class (first rung, other rung) detection arrays; [0] is background
    pairs = [
        (a, b) for other in per_bucket[1:]
        for a, b in zip(first[1:], other[1:])
    ]
    same_set = all(len(a) == len(b) for a, b in pairs)
    bitwise = same_set and all(np.array_equal(a, b) for a, b in pairs)
    max_abs = max(
        (float(np.abs(a - b).max()) for a, b in pairs if same_set and len(a)),
        default=None,
    )
    return {"buckets": [list(b) for b in runner.ladder],
            "boxes": sum(len(d) for d in first[1:]),
            "same_kept_set": same_set, "bitwise_equal": bitwise,
            "max_abs_diff": max_abs,
            "feed_layout_is_default": feed_is_default}


def serve_phase(argv, name: str = "serve"):
    """Registry → runner → engine built by ``tools/serve.py``'s own
    ``build_stack``; warm the ladder, drive mixed-size load, and hold the
    engine to: every request answered with finite boxes, no failure /
    retry / requeue / quarantine / trip of any kind, exactly
    ``len(ladder)`` compiles at warmup and not one during load.
    → the load report."""
    import jax
    import numpy as np

    from mx_rcnn_tpu.serve.loadgen import run_load
    from mx_rcnn_tpu.tools import serve as cli

    p, args = cli.parse_args(argv)
    stack = cli.build_stack(p, args)
    runner, engine = stack.runner, stack.engine
    t0 = time.monotonic()
    with engine:  # start() warms every ladder rung
        warm_s = time.monotonic() - t0
        warm_misses = runner.compile_cache.misses
        if warm_misses != len(runner.ladder):
            raise RuntimeError(
                f"{name}: {warm_misses} compiles at warmup for a "
                f"{len(runner.ladder)}-rung ladder"
            )
        report = run_load(
            engine, num_requests=args.requests,
            concurrency=args.concurrency, sizes=stack.sizes,
            seed=args.seed, collect=True, models=stack.load_models,
            tenants=stack.tenant_names,
        )
        cross = _cross_bucket(runner)
    results = report.pop("_results")
    report.pop("_times")
    snap = report["engine"]
    req = snap["requests"]
    say(phase=name, warm_s=round(warm_s, 1), wall_s=report["wall_s"],
        outcomes=report["outcomes"], requests=req,
        compile=snap["compile"], batches=snap["batches"],
        staged_batches=runner.staged_batches,
        layout_staged=runner.layout_staged, cross_bucket=cross)

    bad = {k: v for k, v in report["outcomes"].items() if v and k != "ok"}
    if report["outcomes"]["ok"] != args.requests or bad:
        raise RuntimeError(f"{name}: outcomes {report['outcomes']}")
    for i in range(args.requests):
        _ok, dets = results[i]
        if not all(np.isfinite(d).all() for d in dets[1:]):  # [0]: background
            raise RuntimeError(f"{name}: request {i}: non-finite detection")
    for key in ("failed", "rejected", "expired", "retried", "shed",
                "stopped", "invalid", "poisoned", "exhausted",
                "resubmitted"):
        if req[key]:
            raise RuntimeError(f"{name}: engine counted {key}={req[key]}")
    if req["completed"] != args.requests:
        raise RuntimeError(f"{name}: completed {req['completed']}")
    pool = snap.get("pool")
    if pool is not None:
        routing = pool["routing"]
        states = set(pool["states"].values())
        if (routing["requeued"] or routing["failovers"]
                or routing["no_healthy"] or states != {"healthy"}):
            raise RuntimeError(f"{name}: pool {routing} states {states}")
    quarantine = snap.get("quarantine")
    if quarantine is not None and (
            quarantine["trips"] or quarantine["quarantined_total"]):
        raise RuntimeError(f"{name}: quarantine {quarantine}")
    if runner.compile_cache.misses != warm_misses:
        raise RuntimeError(
            f"{name}: {runner.compile_cache.misses - warm_misses} "
            f"compile(s) during load"
        )
    if jax.default_backend() == "tpu" and not (
            0 < runner.staged_batches == runner.layout_staged):
        # the layout-matched feed (core/pipeline.py) must be LIVE on the
        # chip: every staged batch went into the compiled input formats
        raise RuntimeError(
            f"{name}: layout feed dead — staged "
            f"{runner.staged_batches}, layout-staged {runner.layout_staged}"
        )
    return report


# ----------------------------------------------------------------- multichip
def _distinct_devices(tree) -> int:
    """Number of distinct devices holding a shard of any leaf."""
    import jax

    devs = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        devs.update(s.device for s in leaf.addressable_shards)
    return len(devs)


def dp_matches_single_device(argv, name: str = "dp_vs_single") -> dict:
    """First-step loss of ``make_parallel_train_step`` over every device
    vs ``make_train_step`` on one, same global batch from the real
    loader, same params, same seeds — the on-chip twin of
    tests/test_parallel.py::test_dp_grads_match_single_device.  Built the
    way ``train_net`` builds them, so the DP program is the one it just
    compiled (persistent-cache hit)."""
    import jax

    from mx_rcnn_tpu.core.pipeline import make_place_fn
    from mx_rcnn_tpu.core.train import (
        create_train_state,
        make_lr_schedule,
        make_optimizer,
        make_train_step,
    )
    from mx_rcnn_tpu.data.loader import TrainLoader
    from mx_rcnn_tpu.models import build_model
    from mx_rcnn_tpu.parallel import (
        make_mesh,
        make_parallel_train_step,
        replicate,
    )
    from mx_rcnn_tpu.tools import train_end2end as cli
    from mx_rcnn_tpu.utils.load_data import load_gt_roidb

    args = cli.parse_args(argv)
    n = len(jax.devices())
    cfg = cli.config_from_args(args)
    global_batch = cfg.TRAIN.BATCH_IMAGES * n
    _, roidb = load_gt_roidb(
        cfg, None, flip=cfg.TRAIN.FLIP, synthetic_size=args.synthetic
    )
    loader = TrainLoader(roidb, cfg, global_batch,
                         shuffle=cfg.TRAIN.SHUFFLE, seed=args.seed)
    stream = iter(loader)
    batch = next(stream)
    stream.close()

    model = build_model(cfg)
    params = model.init(
        {"params": jax.random.key(args.seed), "sampling": jax.random.key(1)},
        batch["images"][:1], batch["im_info"][:1],
        batch["gt_boxes"][:1], batch["gt_valid"][:1], train=True,
    )["params"]
    tx = make_optimizer(cfg, make_lr_schedule(cfg, max(len(loader), 1)))
    rng = jax.random.key(args.seed + 123)

    # single device first, undonated: the DP step donates its state
    s_step = make_train_step(model, tx, donate=False)
    _, s_aux = s_step(create_train_state(params, tx), batch, rng)
    s_loss = float(s_aux["loss"])

    mesh = make_mesh(n_data=n, n_model=1)
    p_state = replicate(create_train_state(params, tx), mesh)
    p_batch = make_place_fn(mesh)(batch)
    state_devs = _distinct_devices(p_state.params)
    batch_devs = _distinct_devices(p_batch)
    p_step = make_parallel_train_step(model, tx, mesh)
    _, p_aux = p_step(p_state, p_batch, rng)
    p_loss = float(p_aux["loss"])

    rel = abs(p_loss - s_loss) / abs(s_loss)
    say(phase=name, devices=n, global_batch=global_batch,
        single_loss=s_loss, dp_loss=p_loss, rel_diff=rel,
        rtol=DP_LOSS_RTOL, state_devices=state_devs,
        batch_devices=batch_devs)
    if state_devs != n or batch_devs != n:
        raise RuntimeError(
            f"{name}: state on {state_devs} device(s), batch on "
            f"{batch_devs}, of {n}"
        )
    if not (math.isfinite(s_loss) and math.isfinite(p_loss)):
        raise RuntimeError(f"{name}: losses {s_loss} / {p_loss}")
    if rel > DP_LOSS_RTOL:
        raise RuntimeError(
            f"{name}: DP loss {p_loss} vs single-device {s_loss}: "
            f"rel {rel:.3g} > {DP_LOSS_RTOL}"
        )
    return {"single_loss": s_loss, "dp_loss": p_loss, "rel_diff": rel}


# ---------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--multichip", action="store_true",
        help="the four-chip path and its comparison, and no other phase: "
             "data-parallel train_net on the 2x2 mesh, then the DP step's "
             "first loss against the single-device step's",
    )
    opts = ap.parse_args(argv)

    devices = require_tpu(4 if opts.multichip else 1)
    from mx_rcnn_tpu.utils.platform import cli_bootstrap, compile_cache_dir

    cli_bootstrap()  # every tool's preamble: compile cache + INFO logs
    clock = CompileClock()
    say(phase="start", device_kind=devices[0].device_kind,
        devices=len(devices), compile_cache=compile_cache_dir())
    check_native()

    def phase(fn, arg, name, **kw):
        out = fn(arg, name=name, **kw)
        say(phase=name, compile_s=clock.lap())
        return out

    # checkpoints land under TMPDIR and go with the run
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as out_dir:
        def prefix(tag):
            return ["--prefix", os.path.join(out_dir, tag)]

        if opts.multichip:
            state, _ = phase(
                train_phase, MULTICHIP_TRAIN_ARGV + prefix("dp"), "train_dp"
            )
            held = _distinct_devices(state.params)
            if held != len(devices):
                raise RuntimeError(
                    f"train_dp: final state on {held} of "
                    f"{len(devices)} devices"
                )
            del state
            phase(dp_matches_single_device, MULTICHIP_TRAIN_ARGV,
                  "dp_vs_single")
        else:
            phase(kernels_phase, False, "kernels")
            phase(train_phase, TRAIN_BF16_ARGV + prefix("bf16"),
                  "train_bf16_b8")
            phase(train_phase, TRAIN_F32_ARGV + prefix("f32"),
                  "train_f32_b1")
            phase(train_phase, TRAIN_FPN_BF16_ARGV + prefix("fpn_bf16"),
                  "train_fpn_bf16_b8", kernels=FPN_STEP_KERNELS)
            phase(train_phase, TRAIN_FPN_F32_ARGV + prefix("fpn_f32"),
                  "train_fpn_f32_b1")
            phase(train_phase, TRAIN_VGG_BF16_ARGV + prefix("vgg_bf16"),
                  "train_vgg_bf16_b8", kernels=VGG_STEP_KERNELS,
                  scopes=("roi_pool",))
            phase(train_phase, TRAIN_DCN_BF16_ARGV + prefix("dcn_bf16"),
                  "train_dcn_bf16_b8", kernels=("pallas_nms_mask",),
                  scopes=("deform_conv", "deform_roi_pool"))
            phase(serve_phase, SERVE_ARGV, "serve")

    stats = devices[0].memory_stats() or {}
    say(phase="done", peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    print(result_line(devices), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
