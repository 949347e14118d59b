"""Label-churn ablation for the FPN/Mask gate plateau (VERDICT r4 #2).

Hypothesis under test (integration_gate.gate_cfg notes): random-init
FPN-family gates plateau at ~0.5 box / ~0.45 segm-AP50 because per-step
roi resampling on the dense stride-4 proposal pool keeps flipping
near-boundary fg/bg labels, leaving the RCNN head an irreducible CE
floor.  This probe removes the churn with machinery that already exists
and measures where the ceiling really is:

  phase 1  train the mask gate normally for --warmup steps
  dump     freeze the proposal set: generate_proposals() from the
           phase-1 RPN (the test_rpn --dump → ROIIter path)
  phase 2a CONTROL — keep training live-RPN + per-step resampling
  phase 2b FROZEN  — same steps, same init, but proposals fixed to the
           dump AND the sampling rng constant (fold_step_rng=False):
           every image's roi set and labels are identical every step

Both phases report box mAP / segm AP50 (full eval stack) and the
decoupled mask-IoU at gt boxes.  (frozen − control) at equal budget is
the fraction of the plateau the churn explains.

Usage:
  PYTHONPATH=. python scripts/probe_mask_churn.py \
      [--warmup 600] [--steps 600] [--eval_every 200]
Prints one JSON line per phase and a final summary line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json

import jax
import numpy as np
import optax


def train_steps(model, state, loader, step_fn, rng, n, eval_fn, eval_every, tag):
    done, history = 0, []
    it = iter(loader)
    while done < n:
        try:
            batch = next(it)
        except StopIteration:
            it = iter(loader)
            continue
        state, aux = step_fn(state, batch, rng)
        done += 1
        if done % eval_every == 0 or done == n:
            m = eval_fn(state)
            m["step"] = done
            history.append(m)
            print(json.dumps({"phase": tag, **m}), flush=True)
    return state, history


def main():
    from mx_rcnn_tpu.utils.platform import cli_bootstrap

    cli_bootstrap()

    ap = argparse.ArgumentParser()
    ap.add_argument("--warmup", type=int, default=600)
    ap.add_argument("--steps", type=int, default=600)
    ap.add_argument("--eval_every", type=int, default=200)
    ap.add_argument("--lr", type=float, default=5e-4)
    ap.add_argument("--num_images", type=int, default=8)
    ap.add_argument("--cpu", type=int, default=0)
    args = ap.parse_args()
    if args.cpu:
        from mx_rcnn_tpu.utils.platform import force_cpu

        force_cpu(args.cpu)

    from mx_rcnn_tpu.core.tester import Predictor, generate_proposals, pred_eval
    from mx_rcnn_tpu.core.train import (
        create_train_state,
        make_optimizer,
        make_train_step,
    )
    from mx_rcnn_tpu.data.loader import TestLoader, TrainLoader
    from mx_rcnn_tpu.data.synthetic import SyntheticDataset
    from mx_rcnn_tpu.models import build_model
    from mx_rcnn_tpu.tools.integration_gate import gate_cfg, mask_iou_eval
    from mx_rcnn_tpu.utils.bn_calibrate import calibrate_frozen_bn

    cfg = gate_cfg("mask_resnet_fpn")
    imdb = SyntheticDataset(
        num_images=args.num_images,
        num_classes=cfg.dataset.NUM_CLASSES,
        image_size=(128, 128),
        max_boxes=2,
        seed=0,
        with_masks=True,
    )
    roidb = imdb.gt_roidb()
    model = build_model(cfg)

    loader = TrainLoader(roidb, cfg, cfg.TRAIN.BATCH_IMAGES, shuffle=True, seed=0)
    batch0 = next(iter(loader))
    params = model.init(
        {"params": jax.random.key(0), "sampling": jax.random.key(1)},
        train=True,
        **batch0,
    )["params"]
    params = calibrate_frozen_bn(model, params, batch0)
    # constant lr through warmup; phases 2a/2b share one 10x-decayed lr
    tx = make_optimizer(cfg, lambda s: args.lr)
    tx2 = make_optimizer(cfg, lambda s: args.lr * 0.1)

    def eval_fn(state):
        p = jax.device_get(state.params)
        predictor = Predictor(model, p)
        _, results = pred_eval(predictor, TestLoader(roidb, cfg), imdb, cfg)
        return {
            "mAP": round(float(results["mAP"]), 4),
            "segm_AP50": round(float(results.get("segm_AP50", 0.0)), 4),
            "mask_iou": round(mask_iou_eval(model, p, cfg, roidb), 4),
        }

    rng = jax.random.key(123)
    state = create_train_state(params, tx)
    step = make_train_step(model, tx, donate=False)
    state, _ = train_steps(
        model, state, loader, step, rng, args.warmup, eval_fn,
        args.eval_every, "warmup",
    )
    warm_params = jax.device_get(state.params)

    # freeze the proposal set from the warmed-up RPN (original-image
    # coords; make_batch re-scales per bucket like any ROIIter batch)
    props = generate_proposals(
        Predictor(model, warm_params),
        TestLoader(roidb, cfg, batch_size=2),
        cfg,
    )
    for rec, dets in zip(roidb, props):
        rec["proposals"] = dets[:, :4]

    # phase 2a CONTROL: live RPN + per-step resampling, as today
    ctl_state = create_train_state(warm_params, tx2)
    ctl_state, ctl_hist = train_steps(
        model, ctl_state, loader, make_train_step(model, tx2, donate=False),
        rng, args.steps, eval_fn, args.eval_every, "control",
    )

    # phase 2b FROZEN: fixed proposals + constant sampling rng
    frozen_loader = TrainLoader(
        roidb, cfg, cfg.TRAIN.BATCH_IMAGES, shuffle=True, seed=0,
        proposal_count=cfg.TRAIN.RPN_POST_NMS_TOP_N,
    )
    frz_state = create_train_state(warm_params, tx2)
    frz_state, frz_hist = train_steps(
        model, frz_state, frozen_loader,
        make_train_step(model, tx2, donate=False, fold_step_rng=False),
        rng, args.steps, eval_fn, args.eval_every, "frozen",
    )

    best = lambda h, k: max(m[k] for m in h)  # noqa: E731
    print(json.dumps({
        "summary": "churn_ablation",
        "control": {k: best(ctl_hist, k) for k in ("mAP", "segm_AP50", "mask_iou")},
        "frozen": {k: best(frz_hist, k) for k in ("mAP", "segm_AP50", "mask_iou")},
        "churn_explains_box": round(
            best(frz_hist, "mAP") - best(ctl_hist, "mAP"), 4
        ),
        "churn_explains_segm": round(
            best(frz_hist, "segm_AP50") - best(ctl_hist, "segm_AP50"), 4
        ),
    }))


if __name__ == "__main__":
    main()
