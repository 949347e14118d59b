"""Train→eval integration gate: overfit tiny synthetic data to high mAP.

SURVEY §5.1: "tiny-dataset overfit test (10 images → loss↓, mAP≈1 on
train) as the integration gate".  This closes the loop the reference
closed only via published-mAP reproduction: train a real (small) model on
synthetic images, then run the FULL inference + evaluation stack
(Predictor → im_detect → per-class NMS → evaluate_detections) on the same
images and demand the detections actually score.

``--network`` gates every model family: resnet50 (C4 flagship shape),
resnet_fpn, mask_resnet_fpn, vgg.  The mask gate trains on synthetic
POLYGON gts (ellipses/triangles — ``data/synthetic.py with_masks``) and
must additionally reach segm AP50 ≥ target through the full mask stack
(crop-resize targets → mask head → RLE paste → COCO segm protocol).

Usage:
  python -m mx_rcnn_tpu.tools.integration_gate [--network resnet50]
      [--steps 400] [--target 0.8]

Exit code 0 iff the gate metric ≥ target.  The pytest twin is
``tests/test_integration_gate.py``.
"""

from __future__ import annotations

import argparse
import dataclasses
import logging
import sys

import jax
import numpy as np
import optax

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.core.resilience import host_copy
from mx_rcnn_tpu.core.tester import Predictor, pred_eval
from mx_rcnn_tpu.core.train import create_train_state, make_optimizer, make_train_step
from mx_rcnn_tpu.data.loader import TestLoader, TrainLoader
from mx_rcnn_tpu.data.synthetic import SyntheticDataset
from mx_rcnn_tpu.models import build_model

logger = logging.getLogger(__name__)


def gate_cfg(
    network: str = "resnet50",
    num_classes: int = 4,
    compute_dtype: str | None = None,
    fold_bn: bool | None = None,
):
    """Small-shape config of the requested family: one 128×128 bucket,
    reduced proposal/roi budgets for CPU-speed compiles.

    ``compute_dtype``/``fold_bn`` override the family defaults so the
    gate can run at a measured configuration (the benchmark's train
    cells are bf16 with FOLD_BN off) — VERDICT r4 weak #5: perf numbers
    must come from a config whose correctness evidence is committed."""
    cfg = generate_config(network, "PascalVOC")
    net_over = dict(
        # FIXED_PARAMS cleared: freezing conv0/stage1/BN affines only makes
        # sense with pretrained weights; frozen RANDOM features cap the
        # overfit capacity this gate measures.
        FIXED_PARAMS=(),
    )
    if compute_dtype is not None:
        net_over["COMPUTE_DTYPE"] = compute_dtype
    if fold_bn is not None:
        net_over["FOLD_BN"] = fold_bn
    if not cfg.network.USE_FPN:
        # anchor sizes 32/64/128 px: the flagship scales (8, 16, 32) make
        # anchors of 128-512 px, none of which fit inside a 128×128 image
        # — every RPN label would be ignore and the RPN would never train.
        # (FPN keeps its per-level scale 8: P2/P3 anchors are 32/64 px.)
        net_over["ANCHOR_SCALES"] = (2, 4, 8)
    if cfg.network.depth > 50 and cfg.network.name == "resnet":
        net_over["depth"] = 50  # mask registry defaults to 101; gate speed
    # FPN's stride-4 anchors make proposals saturate the fg/bg IoU
    # boundary once the RPN tightens (measured: RCNN head collapses to
    # the 75% bg prior at the C4 gate's 64-proposal budget); a wider
    # proposal pool and roi batch restore bg diversity for the sampler.
    # Even then, random-init FPN gates plateau (box mAP ~0.5-0.66):
    # per-step roi resampling keeps drawing near-boundary proposals
    # whose fg/bg label flips run to run, leaving the head an
    # irreducible label-churn CE floor (measured RCNNLogLoss ~0.5-0.65
    # while RPN losses go to ~0) — hence the reduced FPN/mask targets
    # in `make integration-gate`; raising them is open work (pretrained
    # init, which the reference always used, sidesteps this entirely)
    post_nms = 192 if cfg.network.USE_FPN else 64
    batch_rois = 64 if cfg.network.USE_FPN else 32
    return cfg.replace(
        SHAPE_BUCKETS=((128, 128),),
        network=dataclasses.replace(cfg.network, **net_over),
        dataset=dataclasses.replace(
            cfg.dataset, NUM_CLASSES=num_classes, SCALES=((128, 128),),
            MAX_GT_BOXES=8,
        ),
        TRAIN=dataclasses.replace(
            cfg.TRAIN,
            RPN_PRE_NMS_TOP_N=400,
            RPN_POST_NMS_TOP_N=post_nms,
            BATCH_ROIS=batch_rois,
            RPN_BATCH_SIZE=64,
            BATCH_IMAGES=2,
            # small data + short schedule: no flip (run_gate applies a
            # 10x lr decay halfway through its step budget)
            FLIP=False,
        ),
        TEST=dataclasses.replace(
            cfg.TEST,
            RPN_PRE_NMS_TOP_N=200,
            RPN_POST_NMS_TOP_N=64 if cfg.network.USE_FPN else 32,
            SCORE_THRESH=0.05,
        ),
    )


# keyed by id(model), holding the model ref so the id can't be recycled:
# jax.jit caches on function identity, so rebuilding the lambda per call
# would re-trace/re-compile the whole probe forward every eval
_PROBE_CACHE: dict = {}


def mask_iou_eval(model, params, cfg, roidb) -> float:
    """Mean decoupled mask-IoU over a roidb (VERDICT r4 #2): masks
    predicted AT the gt boxes with gt classes vs the polygon gt bitmaps
    — isolates mask-head shape quality from the detection stack."""
    from mx_rcnn_tpu.data.loader import make_batch

    if id(model) not in _PROBE_CACHE:
        _PROBE_CACHE[id(model)] = (
            model,
            jax.jit(
                lambda p, b: model.apply(
                    {"params": p},
                    b["images"], b["im_info"], b["gt_boxes"], b["gt_valid"],
                    b["gt_masks"],
                    method=type(model).mask_iou_probe,
                )
            ),
        )
    probe = _PROBE_CACHE[id(model)][1]
    total, count = 0.0, 0
    bucket = tuple(cfg.SHAPE_BUCKETS[0])
    for rec in roidb:
        b = make_batch([rec], cfg, bucket, with_masks=True)
        iou, valid = jax.device_get(probe(params, b))
        v = valid.astype(bool)
        total += float(iou[v].sum())
        count += int(v.sum())
    return total / max(count, 1)


def run_gate(
    network: str = "resnet50",
    num_images: int = 8,
    steps: int = 400,
    lr: float = 2e-3,
    eval_every: int = 100,
    target: float = 0.8,
    seed: int = 0,
    dp: int = 0,
    compute_dtype: str | None = None,
    fold_bn: bool | None = None,
) -> dict:
    """Train on ``num_images`` synthetic images, eval on the same images.

    Returns {"mAP": best, "gate": best_gate_metric, "steps": steps_run,
    "per_eval": [(step, gate_metric)]}.  The gate metric is VOC mAP for
    box models and min(mAP, segm AP50) for Mask R-CNN.  Stops early once
    ``target`` is reached.
    """
    cfg = gate_cfg(network, compute_dtype=compute_dtype, fold_bn=fold_bn)
    if dp:
        # data-parallel gate: one image per device over a dp-way mesh,
        # the exact shard_map train step production uses
        cfg = cfg.replace(
            TRAIN=dataclasses.replace(cfg.TRAIN, BATCH_IMAGES=dp)
        )
    imdb = SyntheticDataset(
        num_images=num_images,
        num_classes=cfg.dataset.NUM_CLASSES,
        image_size=(128, 128),
        max_boxes=2,
        seed=seed,
        with_masks=cfg.network.USE_MASK,
    )
    roidb = imdb.gt_roidb()

    model = build_model(cfg)
    loader = TrainLoader(
        roidb, cfg, cfg.TRAIN.BATCH_IMAGES, shuffle=True, seed=seed
    )
    if len(loader) == 0:
        raise ValueError(
            f"num_images={num_images} yields zero batches at "
            f"BATCH_IMAGES={cfg.TRAIN.BATCH_IMAGES}"
        )
    batch0 = next(iter(loader))
    params = model.init(
        {"params": jax.random.key(seed), "sampling": jax.random.key(seed + 1)},
        train=True,
        **batch0,
    )["params"]
    # random-init frozen-BN networks start unnormalized (the reference
    # always trains from pretrained weights whose moments match).  For
    # the FPN family this diverges at any workable lr (measured: loss
    # 83 → e15), so one calibration pass writes observed moments into
    # the BNs (utils/bn_calibrate).  The C4 family is deliberately LEFT
    # UNCALIBRATED: its oversized activations ride the gradient clip to
    # fast overfit (0.92 mAP @ 300 steps), and normalizing them shrinks
    # gradients enough that the same budget reaches only ~0.003
    # (measured regression when calibration was applied unconditionally).
    if cfg.network.USE_FPN:
        from mx_rcnn_tpu.utils.bn_calibrate import calibrate_frozen_bn

        params = calibrate_frozen_bn(model, params, batch0)
    # 10x decay halfway: the constant-lr run overfits noisily (mAP
    # oscillates 0.4-0.7); the decayed tail lets it polish to convergence
    tx = make_optimizer(
        cfg, optax.piecewise_constant_schedule(lr, {steps // 2: 0.1})
    )
    if dp:
        from mx_rcnn_tpu.parallel import (
            distributed,
            make_mesh,
            make_parallel_train_step,
            replicate,
        )

        mesh = make_mesh(n_data=dp, n_model=1)
        state = replicate(create_train_state(jax.device_get(params), tx), mesh)
        dp_step = make_parallel_train_step(model, tx, mesh)

        def step_fn(st, batch, rng):
            return dp_step(st, distributed.globalize_batch(dict(batch), mesh), rng)
    else:
        state = create_train_state(params, tx)
        step_fn = make_train_step(model, tx, donate=False)
    rng = jax.random.key(seed + 123)

    def eval_gate(state):
        predictor = Predictor(model, state.params)
        _, results = pred_eval(predictor, TestLoader(roidb, cfg), imdb, cfg)
        logger.info("per-class AP: %s",
                    {k: round(v, 3) for k, v in results.items()})
        m = float(results["mAP"])
        if cfg.network.USE_MASK:
            # the mask gate must prove SEGMENTATION quality, not ride on
            # box mAP: min() forces both stacks to converge
            return min(m, float(results.get("segm_AP50", 0.0))), results
        return m, results

    per_eval = []
    best, best_results, best_params = 0.0, {}, None
    done = 0
    it = iter(loader)
    while done < steps:
        try:
            batch = next(it)
        except StopIteration:
            it = iter(loader)
            continue
        state, aux = step_fn(state, batch, rng)
        done += 1
        if done % eval_every == 0 or done == steps:
            loss = float(aux["loss"])
            m, results = eval_gate(state)
            per_eval.append((done, m))
            if m > best:
                best, best_results = m, results
                # keep the checkpoint the reported metrics describe, so
                # the decoupled mask-IoU below measures the SAME params
                # as the best mAP/segm_AP50 (not the final state's)
                # owning copy, not a device_get view: the DP step donates
                # its state, so later steps reuse these very buffers
                best_params = host_copy(state.params)
            logger.info("step %d loss %.3f gate %.3f", done, loss, m)
            if best >= target:
                break
    out = {
        "mAP": float(best_results.get("mAP", best)),
        "segm_AP50": float(best_results["segm_AP50"])
        if "segm_AP50" in best_results else None,
        "gate": best,
        "network": network,
        "steps": done,
        "per_eval": per_eval,
    }
    if cfg.network.USE_MASK:
        # decoupled shape-quality evidence, no detection confound —
        # measured on the best checkpoint, the one the AP numbers describe
        probe_params = (
            best_params if best_params is not None
            else host_copy(state.params)
        )
        out["mask_iou"] = round(
            mask_iou_eval(model, probe_params, cfg, roidb), 4
        )
        logger.info("decoupled mask IoU at gt boxes: %.4f", out["mask_iou"])
    return out


def main():
    from mx_rcnn_tpu.utils.platform import cli_bootstrap

    cli_bootstrap()
    p = argparse.ArgumentParser()
    p.add_argument("--network", default="resnet50",
                   choices=["resnet50", "resnet_fpn", "mask_resnet_fpn", "vgg"])
    p.add_argument("--steps", type=int, default=400)
    p.add_argument("--num_images", type=int, default=8)
    p.add_argument("--lr", type=float, default=2e-3)
    p.add_argument("--eval_every", type=int, default=100)
    p.add_argument("--target", type=float, default=0.8)
    p.add_argument("--cpu", type=int, default=0)
    p.add_argument("--dp", type=int, default=0,
                   help="data-parallel gate over an N-device mesh "
                        "(combine with --cpu N for virtual devices)")
    p.add_argument("--bf16", action="store_true",
                   help="gate at COMPUTE_DTYPE=bfloat16 (the benchmark's "
                        "train cells' dtype)")
    p.add_argument("--fold_bn", action="store_true",
                   help="gate with FOLD_BN=True")
    args = p.parse_args()
    if args.cpu:
        from mx_rcnn_tpu.utils.platform import force_cpu

        force_cpu(args.cpu)
    out = run_gate(
        network=args.network,
        num_images=args.num_images,
        steps=args.steps,
        lr=args.lr,
        eval_every=args.eval_every,
        target=args.target,
        dp=args.dp,
        compute_dtype="bfloat16" if args.bf16 else None,
        fold_bn=True if args.fold_bn else None,
    )
    print(out)
    sys.exit(0 if out["gate"] >= args.target else 1)


if __name__ == "__main__":
    main()
