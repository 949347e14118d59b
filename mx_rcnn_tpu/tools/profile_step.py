"""Decompose the flagship train step cost component by component.

Times each stage of the Faster R-CNN step (backbone, RPN, proposal/NMS,
targets, ROI feature extraction, top head, full fwd, full train step) as
its own jitted function on the current default backend.  This is the
SURVEY §5.2 profiling upgrade: the reference had only a Speedometer.

Usage: python -m mx_rcnn_tpu.tools.profile_step [--dtype bfloat16]
       python -m mx_rcnn_tpu.tools.profile_step --ablate

Caveat: an unchained timing of a cheap component includes the host's
per-dispatch cost, so only the ``full_train_step`` row (state-chained)
is a device number.  ``--ablate`` instead times each component as a
*self-chained* update (output feeds the next iteration's input) so
iterations serialize on-device and the dispatch cost amortizes.  Not
measured on this code; the per-op budget belongs to the device trace
(ROADMAP S2).
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np


def timeit(fn, *args, iters=10, warmup=2):
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    # and a value fetch: the value cannot exist before the chain ran
    _ = np.asarray(jax.tree_util.tree_leaves(out)[0]).ravel()[:1]
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    _ = np.asarray(jax.tree_util.tree_leaves(out)[0]).ravel()[:1]
    return (time.perf_counter() - t0) / iters


def timeit_chained(step, state, iters=20):
    """Self-chained timing: ``state = step(state)`` serializes iterations
    on-device, so one value fetch at the end syncs the whole chain and
    the per-dispatch host cost amortizes over ``iters``."""
    state = step(state)  # warmup / compile
    _ = float(np.asarray(jax.tree_util.tree_leaves(state)[0]).ravel()[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        state = step(state)
    _ = float(np.asarray(jax.tree_util.tree_leaves(state)[0]).ravel()[0])
    return (time.perf_counter() - t0) / iters


def ablate(args):
    """Chained per-component ablation of the flagship b8 train step."""
    from __graft_entry__ import _batch, _flagship_cfg
    from mx_rcnn_tpu.models.resnet import ResNetBackbone, ResNetTopHead
    from mx_rcnn_tpu.models.rpn import RPNHead
    from mx_rcnn_tpu.ops.anchors import shifted_anchors
    from mx_rcnn_tpu.ops.proposal import propose
    from mx_rcnn_tpu.ops.roi_align import extract_roi_features_batched
    from mx_rcnn_tpu.ops.targets import assign_anchor, sample_rois

    cfg = _flagship_cfg()
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    net, t = cfg.network, cfg.TRAIN
    h, w = cfg.SHAPE_BUCKETS[0]
    b = args.batch
    batch = _batch(cfg, b, h, w)
    imgs, info = batch["images"], batch["im_info"]
    fh, fw = h // 16, w // 16
    it = args.iters

    bb = ResNetBackbone(depth=net.depth, dtype=dtype)
    rpn = RPNHead(num_anchors=net.NUM_ANCHORS, channels=512, dtype=dtype)
    th = ResNetTopHead(depth=net.depth, dtype=dtype)
    p_bb = bb.init(jax.random.key(0), imgs)
    feat0 = jax.jit(lambda p, x: bb.apply(p, x))(p_bb, imgs)
    p_rpn = rpn.init(jax.random.key(0), feat0)
    rois = jnp.tile(jnp.asarray([[10.0, 10.0, 300.0, 300.0]]), (b, t.BATCH_ROIS, 1))

    def pool(f, r):
        return extract_roi_features_batched(
            f, r, net.ROI_MODE, net.POOLED_SIZE,
            1.0 / net.RCNN_FEAT_STRIDE, net.ROI_SAMPLE_RATIO,
        )

    pooled0 = jax.jit(pool)(feat0, rois)
    p_th = th.init(jax.random.key(0), pooled0.reshape((-1,) + pooled0.shape[2:]))
    anchors = jnp.asarray(shifted_anchors(
        fh, fw, 16, ratios=net.ANCHOR_RATIOS, scales=net.ANCHOR_SCALES))

    def sgd(ps, g):
        return jax.tree_util.tree_map(lambda a, b_: a - 1e-6 * b_, ps, g)

    @jax.jit
    def step_bb(ps):
        def loss(p):
            f = bb.apply(p[0], imgs)
            lg, dl = rpn.apply(p[1], f)
            return (jnp.mean(f.astype(jnp.float32) ** 2)
                    + jnp.mean(lg.astype(jnp.float32) ** 2)
                    + jnp.mean(dl.astype(jnp.float32) ** 2))
        return sgd(ps, jax.grad(loss)(ps))

    print(f"backbone+rpn fwd/bwd/update : "
          f"{timeit_chained(step_bb, (p_bb, p_rpn), it) * 1e3:8.1f} ms")

    @jax.jit
    def step_roi(ps):
        def loss(p):
            out = th.apply(p, pool(feat0, rois).reshape((-1,) + pooled0.shape[2:]))
            return jnp.mean(out.astype(jnp.float32) ** 2)
        return sgd(ps, jax.grad(loss)(ps))

    print(f"roi_extract+top_head f/b    : "
          f"{timeit_chained(step_roi, p_th, it) * 1e3:8.1f} ms")

    @jax.jit
    def step_pool_only(f):
        def loss(ff):
            return jnp.mean(pool(ff, rois).astype(jnp.float32) ** 2)

        return f - 1e-6 * jax.grad(loss)(f)

    print(f"  of which roi_extract f/b  : "
          f"{timeit_chained(step_pool_only, feat0, it) * 1e3:8.1f} ms")

    key = jax.random.key(0)
    scores0 = jax.random.uniform(key, (b, anchors.shape[0]))
    deltas = jax.random.normal(key, (b, anchors.shape[0], 4)) * 0.1

    @jax.jit
    def step_prop(s):
        pr = jax.vmap(lambda sc, d, ii: propose(
            sc, d, anchors, ii, t.RPN_PRE_NMS_TOP_N, t.RPN_POST_NMS_TOP_N,
            t.RPN_NMS_THRESH, t.RPN_MIN_SIZE))(s, deltas, info)
        return s + 1e-9 * pr.scores.sum()

    print(f"propose train-NMS x{b}       : "
          f"{timeit_chained(step_prop, scores0, it) * 1e3:8.1f} ms")

    gtb, gtv = batch["gt_boxes"], batch["gt_valid"]
    pr_rois = jnp.tile(jnp.asarray([[10.0, 10.0, 300.0, 300.0]]),
                       (b, t.RPN_POST_NMS_TOP_N, 1))
    pr_valid = jnp.ones((b, t.RPN_POST_NMS_TOP_N), bool)
    keys = jax.random.split(key, b)

    @jax.jit
    def step_tgt(g):
        at = jax.vmap(lambda gb, gv, ii, k: assign_anchor(
            anchors, gb[:, :4], gv, ii, k, cfg))(g, gtv, info, keys)
        sm = jax.vmap(lambda r, rv, gb, gv, k: sample_rois(
            r, rv, gb, gv, k, cfg))(pr_rois, pr_valid, g, gtv, keys)
        return g + 1e-9 * (at.bbox_targets.sum() + sm.bbox_targets.sum())

    print(f"anchor+roi targets x{b}      : "
          f"{timeit_chained(step_tgt, gtb, it) * 1e3:8.1f} ms")

    # --- the two rows the component sum was missing (VERDICT r4 #5):
    # the full bench-config train step (the number the rows must sum to)
    # and the optimizer update alone.  Both at the EXACT bench config:
    # bf16 + FOLD_BN.  CAVEAT: like every row here these are
    # per-dispatch timings, so SMALL ops read above their device time;
    # the per-op budget belongs to the device trace (ROADMAP S2).
    import optax

    from mx_rcnn_tpu.core.train import (
        TrainState,
        create_train_state,
        make_optimizer,
        make_train_step,
    )
    from mx_rcnn_tpu.models import build_model

    bcfg = cfg.replace(
        network=dataclasses.replace(
            cfg.network, COMPUTE_DTYPE=args.dtype, FOLD_BN=True
        ),
        TRAIN=dataclasses.replace(cfg.TRAIN, BATCH_IMAGES=b),
    )
    bmodel = build_model(bcfg)
    bparams = bmodel.init(
        {"params": jax.random.key(0), "sampling": jax.random.key(1)},
        train=True,
        **batch,
    )["params"]
    btx = make_optimizer(bcfg, lambda s: bcfg.TRAIN.LEARNING_RATE)

    g0 = jax.tree_util.tree_map(lambda p_: jnp.full_like(p_, 1e-6), bparams)

    @jax.jit
    def step_opt(st, g):
        updates, opt_state = btx.update(g, st.opt_state, st.params)
        return TrainState(
            st.step + 1, optax.apply_updates(st.params, updates), opt_state
        )

    opt_state0 = create_train_state(bparams, btx)
    print(f"optimizer update only       : "
          f"{timeit_chained(lambda st: step_opt(st, g0), opt_state0, it) * 1e3:8.1f} ms")

    bstep = make_train_step(bmodel, btx, donate=False)
    rng0 = jax.random.key(0)

    def full_step(st):
        st2, _ = bstep(st, batch, rng0)
        return st2

    bstate = create_train_state(bparams, btx)
    print(f"FULL bench-config step      : "
          f"{timeit_chained(full_step, bstate, it) * 1e3:8.1f} ms")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dtype", default="bfloat16", choices=["float32", "bfloat16"])
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--batch", type=int, default=8,
                    help="--ablate batch size (bench flagship = 8)")
    ap.add_argument("--ablate", action="store_true",
                    help="chained per-component ablation (dispatch cost "
                         "amortized)")
    args = ap.parse_args()

    from mx_rcnn_tpu.utils.platform import cli_bootstrap as _boot

    _boot()
    if args.ablate:
        ablate(args)
        return

    from __graft_entry__ import _batch, _flagship_cfg
    from mx_rcnn_tpu.core.train import create_train_state, make_optimizer, make_train_step
    from mx_rcnn_tpu.models import FasterRCNN
    from mx_rcnn_tpu.models.resnet import ResNetBackbone, ResNetTopHead
    from mx_rcnn_tpu.models.rpn import RPNHead
    from mx_rcnn_tpu.ops.anchors import shifted_anchors
    from mx_rcnn_tpu.ops.proposal import propose
    from mx_rcnn_tpu.ops.roi_align import extract_roi_features_batched
    from mx_rcnn_tpu.ops.targets import assign_anchor, sample_rois

    cfg = _flagship_cfg()
    cfg = cfg.replace(network=dataclasses.replace(cfg.network, COMPUTE_DTYPE=args.dtype))
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    h, w = cfg.SHAPE_BUCKETS[0]
    b = cfg.TRAIN.BATCH_IMAGES
    batch = _batch(cfg, b, h, w)
    fh, fw = h // 16, w // 16
    report = {}

    # --- backbone fwd + fwd/bwd
    bb = ResNetBackbone(depth=cfg.network.depth, dtype=dtype)
    bb_params = bb.init(jax.random.key(0), batch["images"])
    f = jax.jit(lambda p, x: bb.apply(p, x))
    report["backbone_fwd"] = timeit(f, bb_params, batch["images"], iters=args.iters)
    g = jax.jit(jax.grad(lambda p, x: bb.apply(p, x).astype(jnp.float32).sum()))
    report["backbone_fwdbwd"] = timeit(g, bb_params, batch["images"], iters=args.iters)
    feat = jax.jit(lambda p, x: bb.apply(p, x))(bb_params, batch["images"])

    # --- rpn head
    rpn = RPNHead(num_anchors=cfg.network.NUM_ANCHORS, channels=512, dtype=dtype)
    rpn_params = rpn.init(jax.random.key(0), feat)
    f = jax.jit(lambda p, x: rpn.apply(p, x))
    report["rpn_fwd"] = timeit(f, rpn_params, feat, iters=args.iters)

    # --- proposal (train-size NMS: 12000 -> 2000)
    anchors = jnp.asarray(
        shifted_anchors(fh, fw, 16, ratios=cfg.network.ANCHOR_RATIOS,
                        scales=cfg.network.ANCHOR_SCALES)
    )
    n = anchors.shape[0]
    key = jax.random.key(0)
    scores = jax.random.uniform(key, (n,))
    deltas = jax.random.normal(key, (n, 4)) * 0.1
    info = batch["im_info"][0]
    t = cfg.TRAIN
    f = jax.jit(
        lambda s, d: propose(s, d, anchors, info, t.RPN_PRE_NMS_TOP_N,
                             t.RPN_POST_NMS_TOP_N, t.RPN_NMS_THRESH, t.RPN_MIN_SIZE)
    )
    report["propose_train_nms"] = timeit(f, scores, deltas, iters=args.iters)

    # --- assign_anchor + sample_rois
    f = jax.jit(
        lambda k: assign_anchor(anchors, batch["gt_boxes"][0][:, :4],
                                batch["gt_valid"][0], info, k, cfg)
    )
    report["assign_anchor"] = timeit(f, key, iters=args.iters)
    props = jax.jit(
        lambda s, d: propose(s, d, anchors, info, t.RPN_PRE_NMS_TOP_N,
                             t.RPN_POST_NMS_TOP_N, t.RPN_NMS_THRESH, t.RPN_MIN_SIZE)
    )(scores, deltas)
    f = jax.jit(
        lambda r, v, k: sample_rois(r, v, batch["gt_boxes"][0],
                                    batch["gt_valid"][0], k, cfg)
    )
    report["sample_rois"] = timeit(f, props.rois, props.valid, key, iters=args.iters)

    # --- roi feature extraction (128 rois) + top head
    rois = jax.random.uniform(key, (b, cfg.TRAIN.BATCH_ROIS, 4)) * 500
    rois = jnp.concatenate([rois[..., :2], rois[..., :2] + 100], axis=-1)
    net = cfg.network
    f = jax.jit(
        lambda ft, r: extract_roi_features_batched(
            ft, r, net.ROI_MODE, net.POOLED_SIZE, 1.0 / net.RCNN_FEAT_STRIDE,
            net.ROI_SAMPLE_RATIO)
    )
    report["roi_extract_fwd"] = timeit(f, feat, rois, iters=args.iters)
    g = jax.jit(
        jax.grad(lambda ft, r: extract_roi_features_batched(
            ft, r, net.ROI_MODE, net.POOLED_SIZE, 1.0 / net.RCNN_FEAT_STRIDE,
            net.ROI_SAMPLE_RATIO).astype(jnp.float32).sum())
    )
    report["roi_extract_fwdbwd"] = timeit(g, feat, rois, iters=args.iters)

    pooled = f(feat, rois)[0]
    th = ResNetTopHead(depth=cfg.network.depth, dtype=dtype)
    th_params = th.init(jax.random.key(0), pooled)
    f2 = jax.jit(lambda p, x: th.apply(p, x))
    report["top_head_fwd"] = timeit(f2, th_params, pooled, iters=args.iters)
    g2 = jax.jit(jax.grad(lambda p, x: th.apply(p, x).astype(jnp.float32).sum()))
    report["top_head_fwdbwd"] = timeit(g2, th_params, pooled, iters=args.iters)

    # --- full model
    model = FasterRCNN(cfg)
    params = model.init(
        {"params": jax.random.key(0), "sampling": jax.random.key(1)},
        batch["images"], batch["im_info"], batch["gt_boxes"], batch["gt_valid"],
        train=True,
    )["params"]
    tx = make_optimizer(cfg, lambda s: cfg.TRAIN.LEARNING_RATE)
    state = create_train_state(params, tx)
    step = make_train_step(model, tx, donate=False)
    report["full_train_step"] = timeit(
        lambda: step(state, batch, jax.random.key(0)), iters=args.iters
    )

    print(f"\n=== profile ({args.dtype}, {jax.devices()[0].platform}) ===")
    for k, v in sorted(report.items(), key=lambda kv: -kv[1]):
        print(f"{k:24s} {v * 1e3:9.2f} ms")
    print(f"{'imgs/sec (full step)':24s} {b / report['full_train_step']:9.2f}")


if __name__ == "__main__":
    main()
