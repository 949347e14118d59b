"""Benchmark: end-to-end train throughput per model family.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "imgs/sec/chip", "vs_baseline": N/30}

Default (driver) config: ResNet-101 C4 Faster R-CNN, the flagship.
``--network resnet_fpn`` / ``--network mask_resnet_fpn`` benchmark the
BASELINE config-4/5 graphs (VERDICT r3 #3) with the same JSON contract.

``--all`` (VERDICT r4 #4): bench every family in one process — one JSON
line per family, plus ``--out FILE`` to write the driver-format artifact
(``BENCH_families_rNN.json``) that replaces README-quoted perf prose.

Baseline = the 30 imgs/sec/chip north-star target from BASELINE.json
(the reference never published per-chip throughput; its GPU-era numbers
were O(2-5) imgs/sec/GPU).
"""

import argparse
import dataclasses
import json
import threading
import time

import numpy as np

BASELINE_IMGS_PER_SEC_PER_CHIP = 30.0

_METRIC_NAMES = {
    "resnet": "resnet101_e2e",
    "resnet50": "resnet50_e2e",
    "resnet_fpn": "resnet50_fpn_e2e",
    "mask_resnet_fpn": "mask_resnet101_fpn_e2e",
    "vgg": "vgg16_e2e",
}

# the per-family artifact set: flagship + BASELINE configs 4/5 + VGG
_ALL_FAMILIES = ("resnet", "resnet_fpn", "mask_resnet_fpn", "vgg")


def bench_one(
    network: str, batch_images: int, iters: int, steps_per_call: int = 1
) -> dict:
    """Train-throughput measurement for one family; → the JSON record."""
    import jax

    from __graft_entry__ import _batch
    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.core.train import (
        create_train_state,
        make_optimizer,
        make_train_step,
    )
    from mx_rcnn_tpu.models import build_model

    cfg = generate_config(network, "PascalVOC")
    # The perf configuration: bf16 compute (f32 params) rides the MXU,
    # 8 images/chip/step amortize fixed per-step costs (measured: b1=29.9,
    # b2=40.2, b4=44.6, b8=52.9 img/s on the C4 flagship), and FOLD_BN
    # folds the frozen-BN affines into the conv kernels (+2-3%; exact
    # rewrite — default-off only because its fp-reassociation measurably
    # shifted the f32 random-init gate trajectory; the bf16+FOLD_BN bench
    # config has its own committed gate evidence, see PARITY.md round-5
    # notes).  entry()/dryrun keep f32 batch-1 defaults for conservative
    # compile/correctness checks.
    cfg = cfg.replace(
        network=dataclasses.replace(
            cfg.network, COMPUTE_DTYPE="bfloat16", FOLD_BN=True
        ),
        TRAIN=dataclasses.replace(cfg.TRAIN, BATCH_IMAGES=batch_images),
    )
    model = build_model(cfg)
    h, w = cfg.SHAPE_BUCKETS[0]
    b = cfg.TRAIN.BATCH_IMAGES
    batch = _batch(cfg, b, h, w)
    if cfg.network.USE_MASK:
        # all-ones box-frame bitmaps: same shapes/flops as real polygon
        # gts through crop_resize_masks (the bitmap content is data)
        batch["gt_masks"] = np.ones(
            (b, batch["gt_boxes"].shape[1], cfg.TRAIN.MASK_GT_SIZE,
             cfg.TRAIN.MASK_GT_SIZE),
            np.uint8,
        )
    params = model.init(
        {"params": jax.random.key(0), "sampling": jax.random.key(1)},
        train=True,
        **batch,
    )["params"]
    tx = make_optimizer(cfg, lambda s: cfg.TRAIN.LEARNING_RATE)
    state = create_train_state(params, tx)
    # steps_per_call > 1: the device-side training loop (lax.scan of K
    # full optimizer steps per dispatch) — K amortizes the host's
    # per-dispatch cost; exact-equivalence pinned by
    # test_model.py::test_multi_step_matches_sequential_steps
    step = make_train_step(model, tx, donate=True,
                           steps_per_call=steps_per_call)
    if steps_per_call > 1:
        # device-resident stack (jnp): a numpy stack here would be
        # re-uploaded (~300 MB) on EVERY dispatch
        import jax.numpy as jnp

        batch = {
            k: jnp.broadcast_to(v[None], (steps_per_call,) + v.shape)
            for k, v in batch.items()
        }

    def last_loss(aux):
        l = np.asarray(aux["loss"])
        return float(l[-1]) if l.ndim else float(l)

    rng = jax.random.key(0)
    # warmup / compile (synced by a value fetch: the loss cannot exist
    # before the step that computes it has run)
    state, aux = step(state, batch, rng)
    last_loss(aux)

    t0 = time.perf_counter()
    for _ in range(iters):
        state, aux = step(state, batch, rng)
    # the final loss depends on every chained step, so this fetch forces
    # the whole sequence, with one fetch amortized over iters
    assert np.isfinite(last_loss(aux))
    dt = time.perf_counter() - t0

    imgs_per_sec = b * iters * steps_per_call / dt
    return {
        "metric": f"train_imgs_per_sec_per_chip_{_METRIC_NAMES[network]}",
        "value": round(imgs_per_sec, 3),
        "unit": "imgs/sec/chip",
        "vs_baseline": round(imgs_per_sec / BASELINE_IMGS_PER_SEC_PER_CHIP, 3),
    }


def _serve_model(network: str, small: bool, max_batch: int):
    """Shared serve-bench setup → (model, params, cfg, sizes, factory).
    ``factory`` builds one device-pinned ServeRunner per replica index —
    the ReplicaPool's runner source (and what a rewarm re-invokes)."""
    import jax

    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.models import build_model
    from mx_rcnn_tpu.serve.loadgen import DEFAULT_SIZES
    from mx_rcnn_tpu.serve.router import make_replica_factory
    from mx_rcnn_tpu.serve.runner import ServeRunner
    from mx_rcnn_tpu.tools.serve import small_config

    if small:
        cfg = small_config(network)
        sizes = ((72, 96), (96, 128), (64, 80))
    else:
        cfg = generate_config(network, "PascalVOC")
        sizes = DEFAULT_SIZES
    model = build_model(cfg)
    h, w = cfg.SHAPE_BUCKETS[0]
    params = model.init(
        {"params": jax.random.key(0)},
        np.zeros((1, h, w, 3), np.float32),
        np.array([[h, w, 1.0]], np.float32),
        train=False,
    )["params"]
    factory = make_replica_factory(
        lambda params: ServeRunner(model, params, cfg, max_batch=max_batch),
        params,
    )
    return model, params, cfg, sizes, factory


def bench_serve(
    network: str,
    requests: int,
    concurrency: int,
    max_batch: int,
    linger_ms: float,
    small: bool = True,
    replicas: int = 1,
    inflight_depth: int = 2,
) -> tuple:
    """Online-serving measurement: drive the dynamic-batching engine with
    the deterministic synthetic load generator and report latency,
    throughput, occupancy, and the compile count that proves the shape
    ladder held (misses == len(ladder), and not one more).

    → (records, report): the per-metric JSON-line records plus the full
    engine snapshot for the artifact.  Serving has no reference baseline
    (the MXNet repo had no online path), so ``vs_baseline`` is null.

    Routing always goes through the :class:`ReplicaPool` (ISSUE 6) —
    ``replicas=1`` is the no-regression case the committed
    ``BENCH_serve_cpu.json`` pins (same compile-miss invariant through
    the pool's merged cache view).
    """
    from mx_rcnn_tpu.serve.engine import ServingEngine
    from mx_rcnn_tpu.serve.loadgen import run_load
    from mx_rcnn_tpu.serve.router import ReplicaPool

    _, _, _, sizes, factory = _serve_model(network, small, max_batch)
    pool = ReplicaPool(factory, n_replicas=replicas,
                       inflight_depth=inflight_depth)
    with ServingEngine(pool, max_linger=linger_ms / 1000.0) as engine:
        report = run_load(
            engine, num_requests=requests, concurrency=concurrency,
            sizes=sizes, seed=0,
        )
    pool.close()
    eng = report["engine"]
    tag = _METRIC_NAMES[network].replace("_e2e", "")
    records = [
        {
            "metric": f"serve_p50_ms_{tag}",
            "value": eng["latency"]["e2e"]["p50_ms"],
            "unit": "ms",
            "vs_baseline": None,
        },
        {
            "metric": f"serve_p99_ms_{tag}",
            "value": eng["latency"]["e2e"]["p99_ms"],
            "unit": "ms",
            "vs_baseline": None,
        },
        {
            "metric": f"serve_imgs_per_sec_{tag}",
            "value": report["imgs_per_sec"],
            "unit": "imgs/sec",
            "vs_baseline": None,
        },
        {
            "metric": f"serve_batch_occupancy_{tag}",
            "value": eng["batches"]["occupancy"],
            "unit": "fraction",
            "vs_baseline": None,
        },
        {
            "metric": f"serve_compile_misses_{tag}",
            "value": eng["compile"]["misses"],
            "unit": "compiles",
            "vs_baseline": None,
        },
    ]
    return records, report


def _mask_serve_cfg():
    """Small mask-family serving config sized so the fetch ratio is a
    statement about the PATH, not the padding: 64 post-NMS rois keep the
    raw ``(B, R, S, S, K)`` mask stack the dominant fetch term (~3.2 MB
    per b=4 batch at S=28, K=4) while the device path ships only the 16
    capped survivors' grids (~0.2 MB).  The flagship config's ratio is
    larger still (R=300, K=21 → ~50×); this is the CPU-runnable
    miniature of the same geometry."""
    from mx_rcnn_tpu.tools.serve import small_config

    cfg = small_config("mask_resnet_fpn")
    return cfg.replace(
        TEST=dataclasses.replace(
            cfg.TEST,
            RPN_POST_NMS_TOP_N=64,
            DET_PER_CLASS=16,
            MAX_PER_IMAGE=16,
        ),
    )


def _rles_for_image(runner, out, batch, h, w, model=None):
    """One image's outputs → (cls_dets, {cls: [rle, ...]}) through the
    canonical decode + cap + paste + RLE chain (eval/segm.py)."""
    from mx_rcnn_tpu.eval.segm import rles_for_detections

    cls_dets, mask_probs = runner.detections_for(
        out, batch, 0, orig_hw=(h, w), model=model, with_masks=True
    )
    rles = {
        j: rles_for_detections(mask_probs[j], cls_dets[j], h, w)
        for j in range(1, len(cls_dets))
    }
    return cls_dets, rles


def _rles_equal(a: dict, b: dict) -> bool:
    if set(a) != set(b):
        return False
    for j in a:
        if len(a[j]) != len(b[j]):
            return False
        for ra, rb in zip(a[j], b[j]):
            if ra["size"] != rb["size"] or ra["counts"] != rb["counts"]:
                return False
    return True


def bench_serve_mask(
    requests: int,
    concurrency: int,
    max_batch: int,
    linger_ms: float,
    replicas: int = 1,
    inflight_depth: int = 2,
) -> tuple:
    """Mask-family serving bench (ISSUE 14): device-side mask selection
    vs the raw-head path.

    Two phases on one model + params:

    1. **parity + fetch accounting** — every ladder bucket (and an
       odd-size request per bucket, exercising the padding config) runs
       through BOTH a device-postprocess runner and a raw-head runner
       (``device_postprocess=False``); the
       final per-detection RLEs must be byte-identical and the
       ``fetch_bytes`` counters give the measured per-complete reduction.
    2. **pool + engine load** — the mask family registered as a NAMED
       registry entry ("masks") served through the ReplicaPool and the
       real engine intake by the synthetic load generator; p50/p99,
       per-model pool fetch bytes, and the zero-steady-state-recompile
       invariant (misses == ladder rungs) come from this phase.
    """
    import jax

    from mx_rcnn_tpu.models import build_model
    from mx_rcnn_tpu.serve.engine import ServingEngine
    from mx_rcnn_tpu.serve.loadgen import run_load, synthetic_image
    from mx_rcnn_tpu.serve.registry import ModelRegistry
    from mx_rcnn_tpu.serve.router import ReplicaPool, make_replica_factory
    from mx_rcnn_tpu.serve.runner import ServeRunner

    cfg = _mask_serve_cfg()
    sizes = ((72, 96), (96, 128), (64, 80), (128, 128))
    model = build_model(cfg)
    h0, w0 = cfg.SHAPE_BUCKETS[0]
    params = model.init(
        {"params": jax.random.key(0)},
        np.zeros((1, h0, w0, 3), np.float32),
        np.array([[h0, w0, 1.0]], np.float32),
        train=False,
    )["params"]

    # Random init saturates the softmax — every roi scores EXACTLY 1.0
    # for one class, so host-vs-device keep order on those exact float
    # ties is undefined and the parity phase would measure tie-break
    # luck, not the path.  Damp the score/delta heads so every roi
    # carries a distinct non-saturated score and decoded boxes stay off
    # the clip rails; the mask head too, which also keeps the reference
    # sigmoid out of float overflow.  The compiled programs are
    # unchanged — only the weights are.
    def _damp(path, leaf):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        for frag in ("rpn_cls_score", "rpn_bbox_pred", "cls_score",
                     "bbox_pred", "mask_logits"):
            if frag in name:
                return leaf * 1e-2
        return leaf

    params = jax.tree_util.tree_map_with_path(_damp, params)

    registry = ModelRegistry()
    registry.register("masks", model, cfg, params)
    factory = make_replica_factory(
        lambda registry, device: ServeRunner(
            registry=registry, device=device, max_batch=max_batch,
        ),
        registry=registry,
    )
    pool = ReplicaPool(factory, n_replicas=replicas,
                       inflight_depth=inflight_depth)
    rungs = pool.warmup()

    # raw-head reference runner: same model/params/cfg, postprocess OFF —
    # the pre-ISSUE-14 mask serving path, fetching the full head outputs
    raw = ServeRunner(
        model, params, cfg, max_batch=max_batch,
        device_postprocess=False,
    )
    raw.warmup()

    dev_runner = pool.replicas[0].runner
    dev_base = (dev_runner.fetch_bytes_total, dev_runner.split_completes)
    raw_base = (raw.fetch_bytes_total, raw.split_completes)
    byte_identical = True
    parity = []
    for i, (ih, iw) in enumerate(sizes):
        im = synthetic_image(i, ih, iw, seed=0)
        dreq = dev_runner.make_request(im, model="masks")
        rreq = raw.make_request(im)
        dout = dev_runner.run(dev_runner.assemble([dreq]), model="masks")
        rout = raw.run(raw.assemble([rreq]))
        d_dets, d_rles = _rles_for_image(
            dev_runner, dout, {"im_info": [dreq.im_info]}, ih, iw,
            model="masks",
        )
        r_dets, r_rles = _rles_for_image(
            raw, rout, {"im_info": [rreq.im_info]}, ih, iw
        )
        # scores must be bitwise equal (pure gather on device); box
        # coords carry the known XLA-vs-numpy decode ulp (~4e-6 px), so
        # they get a tight tolerance, NOT equality — the RLE check
        # downstream is the strict byte-level bar
        scores_eq, box_delta, count_eq = True, 0.0, True
        for a, b in zip(d_dets[1:], r_dets[1:]):
            if (a is None) != (b is None) or \
                    (a is not None and len(a) != len(b)):
                count_eq = False
                continue
            if a is None or len(a) == 0:
                continue
            scores_eq &= a[:, 4].tobytes() == b[:, 4].tobytes()
            box_delta = max(
                box_delta, float(np.abs(a[:, :4] - b[:, :4]).max())
            )
        dets_eq = count_eq and scores_eq and box_delta <= 1e-4
        rles_eq = _rles_equal(d_rles, r_rles)
        byte_identical &= dets_eq and rles_eq
        parity.append({
            "size": [ih, iw], "bucket": list(dreq.bucket),
            "detections": int(sum(
                len(d) for d in d_dets[1:] if d is not None
            )),
            "scores_byte_identical": scores_eq,
            "max_box_delta": box_delta,
            "rles_byte_identical": rles_eq,
        })
    dev_bytes = dev_runner.fetch_bytes_total - dev_base[0]
    dev_completes = dev_runner.split_completes - dev_base[1]
    raw_bytes = raw.fetch_bytes_total - raw_base[0]
    raw_completes = raw.split_completes - raw_base[1]
    dev_per_batch = dev_bytes / max(dev_completes, 1)
    raw_per_batch = raw_bytes / max(raw_completes, 1)
    reduction = raw_per_batch / max(dev_per_batch, 1)

    with ServingEngine(pool, max_linger=linger_ms / 1000.0) as engine:
        load = run_load(
            engine, num_requests=requests, concurrency=concurrency,
            sizes=sizes[:3], seed=0, models=["masks"],
        )
    snap = pool.snapshot()
    pool.close()
    eng = load["engine"]
    steady_misses = snap["compile"]["misses"] - rungs
    claims = {
        "fetch_reduction_ge_5x": bool(reduction >= 5.0),
        "rle_byte_identical": bool(byte_identical),
        "zero_steady_state_recompiles": bool(steady_misses == 0),
    }
    report = {
        "claims": claims,
        "fetch_bytes": {
            "raw_per_batch": round(raw_per_batch, 1),
            "device_per_batch": round(dev_per_batch, 1),
            "reduction": round(reduction, 2),
            "pool_fetch_bytes": snap["overlap"]["fetch_bytes"],
            "pool_fetch_bytes_by_model":
                snap["overlap"]["fetch_bytes_by_model"],
        },
        "parity": parity,
        "config": {
            "rpn_post_nms_top_n": cfg.TEST.RPN_POST_NMS_TOP_N,
            "det_per_class": cfg.TEST.DET_PER_CLASS,
            "max_per_image": cfg.TEST.MAX_PER_IMAGE,
            "mask_size": cfg.TRAIN.MASK_SIZE,
            "num_classes": cfg.dataset.NUM_CLASSES,
            "ladder_rungs": rungs,
        },
        "engine": eng,
        "load": {
            "imgs_per_sec": load["imgs_per_sec"],
            "requests": requests,
        },
    }
    records = [
        {"metric": "serve_mask_p50_ms",
         "value": eng["latency"]["e2e"]["p50_ms"],
         "unit": "ms", "vs_baseline": None},
        {"metric": "serve_mask_p99_ms",
         "value": eng["latency"]["e2e"]["p99_ms"],
         "unit": "ms", "vs_baseline": None},
        {"metric": "serve_mask_imgs_per_sec",
         "value": load["imgs_per_sec"],
         "unit": "imgs/sec", "vs_baseline": None},
        {"metric": "serve_mask_fetch_bytes_per_batch_raw",
         "value": round(raw_per_batch, 1),
         "unit": "bytes", "vs_baseline": None},
        {"metric": "serve_mask_fetch_bytes_per_batch_device",
         "value": round(dev_per_batch, 1),
         "unit": "bytes", "vs_baseline": None},
        {"metric": "serve_mask_fetch_reduction",
         "value": round(reduction, 2),
         "unit": "x", "vs_baseline": None},
        {"metric": "serve_mask_rle_byte_identical",
         "value": 1.0 if byte_identical else 0.0,
         "unit": "bool", "vs_baseline": None},
        {"metric": "serve_mask_steady_state_compile_misses",
         "value": steady_misses,
         "unit": "compiles", "vs_baseline": None},
    ]
    return records, report


def _pctl_ms(lats_ms: list, p: float) -> float:
    """Exact percentile over a small latency sample (sorted interp)."""
    if not lats_ms:
        return None
    return round(float(np.percentile(np.asarray(lats_ms), p)), 3)


def _dets_equal(a, b) -> bool:
    """Byte-level equality of two per-class detections lists."""
    if len(a) != len(b):
        return False
    for x, y in zip(a, b):
        if x is None or y is None:
            if x is not y:
                return False
            continue
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape or x.dtype != y.dtype:
            return False
        if x.tobytes() != y.tobytes():
            return False
    return True


class _OverlapStubRunner:
    """Split-capable runner stub with a CALIBRATED device-stall model
    (the ``bench_eval --stub_device_ms`` idiom, applied to serving).

    The real overlap win is invisible on a 1-core CPU — model FLOPs
    dwarf the fetch — so the stub models the three phases the split
    predict path actually reorders, each as an explicit stall:

    * ``dispatch`` sleeps ``h2d_ms`` (host-blocking staging copy), then
      books ``device_ms`` of modeled device time onto a single-device
      timeline (``_device_free_t``): compute for batch N+1 queues
      behind batch N exactly like one accelerator's stream.
    * ``complete`` blocks until the handle's modeled ready time, then
      sleeps ``fetch_ms`` (the D2H output copy + host postprocess).

    Serial cost per batch is ``h2d + device + fetch``; at depth 2 the
    fetch of batch N overlaps the staging + compute of batch N+1, so
    steady-state cost drops to ``max(device, h2d + fetch)`` — the same
    algebra as the train pipeline's ROOFLINE entry.  Outputs stay the
    FakeRunner digest (a pure function of the slot pixels), so the
    depth-1 vs depth-2 byte-identity check is exact, and
    ``device_busy_s`` gives a stub-exact device-busy fraction to put
    next to the conservative estimate the replicas export.
    """

    LADDER = ((32, 32), (48, 64))

    def __init__(self, index: int = 0, h2d_ms: float = 10.0,
                 device_ms: float = 60.0, fetch_ms: float = 25.0):
        from mx_rcnn_tpu.serve.buckets import BucketLadder, CompileCache

        self.index = index
        self.h2d_s = h2d_ms / 1000.0
        self.device_s = device_ms / 1000.0
        self.fetch_s = fetch_ms / 1000.0
        self.ladder = BucketLadder(self.LADDER)
        self.max_batch = 2
        self.cfg = None
        self.compile_cache = CompileCache()
        self._lock = threading.Lock()
        self._device_free_t = 0.0
        self.device_busy_s = 0.0

    def warmup(self) -> int:
        for bh, bw in self.ladder:
            self.compile_cache.record(((self.max_batch, bh, bw, 3), "f32"))
        return self.compile_cache.misses

    def make_request(self, im, deadline=None):
        from mx_rcnn_tpu.serve.batcher import Request

        h, w = im.shape[:2]
        bh, bw = self.ladder.select(h, w)
        canvas = np.zeros((bh, bw, 3), np.float32)
        canvas[:h, :w] = im
        return Request(
            image=canvas,
            im_info=np.array([h, w, 1.0], np.float32),
            orig_hw=(h, w),
            bucket=(bh, bw),
            deadline=deadline,
        )

    def assemble(self, requests):
        images = [r.image for r in requests]
        while len(images) < self.max_batch:
            images.append(images[0])
        return {
            "images": np.stack(images),
            "im_info": np.stack(
                [r.im_info for r in requests]
                + [requests[0].im_info] * (self.max_batch - len(requests))
            ),
            "orig_hw": np.array(
                [r.orig_hw for r in requests]
                + [requests[0].orig_hw] * (self.max_batch - len(requests))
            ),
        }

    def dispatch(self, batch, model=None):
        time.sleep(self.h2d_s)  # host-blocking H2D staging
        self.compile_cache.record((batch["images"].shape, "f32"))
        im = batch["images"].astype(np.float64)
        out = {
            "digest": np.stack(
                [im.sum(axis=(1, 2, 3)), (im * im).sum(axis=(1, 2, 3))],
                axis=1,
            )
        }
        with self._lock:
            start = max(time.monotonic(), self._device_free_t)
            ready = start + self.device_s
            self._device_free_t = ready
            self.device_busy_s += self.device_s
        return {"out": out, "ready_t": ready}

    def complete(self, handle):
        delay = handle["ready_t"] - time.monotonic()
        if delay > 0:
            time.sleep(delay)  # modeled device compute still running
        time.sleep(self.fetch_s)  # D2H fetch + host postprocess
        return handle["out"]

    def run(self, batch, model=None):
        return self.complete(self.dispatch(batch, model=model))

    def detections_for(self, out, batch, index, orig_hw=None, thresh=None):
        return [out["digest"][index].copy()]


# overlap fault matrix (depth=2, 2 replicas): one transient predict
# failure absorbed by the retry tail, and a hard stall that trips the
# watchdog while TWO dispatches are in flight — both must requeue
_OVERLAP_FAULT_SCENARIOS = {
    "predict_fail": "predict_fail@0.2x1",
    "stall_two_inflight": "predict_stall@0.5:1.5",
}


def bench_serve_overlap(
    requests: int = 48,
    concurrency: int = 8,
    linger_ms: float = 5.0,
    h2d_ms: float = 10.0,
    device_ms: float = 60.0,
    fetch_ms: float = 25.0,
) -> tuple:
    """Overlapped-serving bench (ISSUE 13 acceptance evidence).

    Three legs over the :class:`_OverlapStubRunner` timing model:

    1. depth=1 on a 1-replica pool — the serial reference;
    2. depth=2 on a 1-replica pool — same load, same seed; claims
       throughput >= 1.3x the serial leg with byte-identical
       detections, and reports both the stub-exact device-busy
       fraction (``device_busy_s / wall``) and the conservative
       estimate the replica's :class:`OverlapStats` exports;
    3. the overlap fault matrix at depth=2 on 2 replicas — zero lost
       requests per scenario, ok detections byte-identical to the
       healthy depth-2 leg, and zero steady-state recompiles (a second
       traffic wave after recovery adds no compile-cache misses).
    """
    from mx_rcnn_tpu.serve.engine import ServingEngine
    from mx_rcnn_tpu.serve.loadgen import run_load
    from mx_rcnn_tpu.serve.replica import HealthPolicy
    from mx_rcnn_tpu.serve.router import ReplicaPool
    from mx_rcnn_tpu.utils import faults

    sizes = ((24, 24), (32, 48), (16, 16))

    def factory(index: int) -> _OverlapStubRunner:
        return _OverlapStubRunner(
            index, h2d_ms=h2d_ms, device_ms=device_ms, fetch_ms=fetch_ms
        )

    def throughput_leg(depth: int):
        pool = ReplicaPool(factory, n_replicas=1, inflight_depth=depth)
        engine = ServingEngine(
            pool, max_linger=linger_ms / 1000.0, in_flight=4
        )
        t0 = time.monotonic()
        with engine:
            report = run_load(
                engine, num_requests=requests, concurrency=concurrency,
                sizes=sizes, seed=0, collect=True,
            )
        wall = time.monotonic() - t0
        busy = sum(r.runner.device_busy_s for r in pool.replicas)
        snap = pool.snapshot()
        pool.close()
        results = report.pop("_results")
        return {
            "inflight_depth": depth,
            "imgs_per_sec": report["imgs_per_sec"],
            "p50_ms": report["engine"]["latency"]["e2e"]["p50_ms"],
            "p99_ms": report["engine"]["latency"]["e2e"]["p99_ms"],
            "compile_misses": report["engine"]["compile"]["misses"],
            "device_busy_fraction": round(busy / wall, 4),
            "overlap": snap["overlap"],
        }, {i: r for i, (kind, r) in results.items() if kind == "ok"}

    depth1, ok1 = throughput_leg(1)
    depth2, ok2 = throughput_leg(2)
    speedup = round(depth2["imgs_per_sec"] / depth1["imgs_per_sec"], 3)
    byte_identical = (
        set(ok1) == set(ok2)
        and all(_dets_equal(ok1[i], ok2[i]) for i in ok1)
    )

    # ---- fault matrix leg: depth=2, 2 replicas, watchdog sized so the
    # injected 1.5 s stall trips it with the window full
    import os

    policy = HealthPolicy(stall_timeout=0.4, fail_threshold=2,
                          breaker_backoff=0.05, breaker_max_backoff=0.5)
    fault = {}
    prior = os.environ.get(faults.ENV_VAR)
    try:
        for name, spec in _OVERLAP_FAULT_SCENARIOS.items():
            os.environ[faults.ENV_VAR] = spec
            faults.reset()
            pool = ReplicaPool(factory, n_replicas=2, inflight_depth=2,
                               policy=policy)
            engine = ServingEngine(
                pool, max_linger=linger_ms / 1000.0, in_flight=4
            )
            with engine:
                report = run_load(
                    engine, num_requests=requests,
                    concurrency=concurrency, sizes=sizes, seed=0,
                    collect=True,
                )
                # wait out any drain -> rewarm -> rejoin before the
                # steady-state wave (stub warmup is instant; bounded)
                t_wait = time.monotonic()
                while time.monotonic() - t_wait < 30.0:
                    reps = pool.snapshot()["replicas"]
                    if all(r["state"] == "healthy" for r in reps):
                        break
                    time.sleep(0.05)
                misses_settled = engine.snapshot()["compile"]["misses"]
                report2 = run_load(
                    engine, num_requests=requests,
                    concurrency=concurrency, sizes=sizes, seed=0,
                )
            pool_snap = pool.snapshot()
            pool.close()
            results = report.pop("_results")
            ok = {i: r for i, (kind, r) in results.items() if kind == "ok"}
            out1, out2 = report["outcomes"], report2["outcomes"]
            lost = (
                requests - (out1["ok"] + out1["deadline"] + out1["error"])
            ) + (
                requests - (out2["ok"] + out2["deadline"] + out2["error"])
            )
            fault[name] = {
                "spec": spec,
                "lost_requests": lost,
                "detections_match_healthy": all(
                    _dets_equal(ok2[i], ok[i]) for i in ok if i in ok2
                ),
                "steady_state_compile_misses": (
                    report2["engine"]["compile"]["misses"] - misses_settled
                ),
                "requeued": sum(
                    rep["requeued_out"] for rep in pool_snap["replicas"]
                ),
                "overlap": pool_snap["overlap"],
            }
    finally:
        if prior is None:
            os.environ.pop(faults.ENV_VAR, None)
        else:
            os.environ[faults.ENV_VAR] = prior
        faults.reset()

    zero_lost = all(s["lost_requests"] == 0 for s in fault.values())
    zero_recompiles = all(
        s["steady_state_compile_misses"] == 0 for s in fault.values()
    )
    records = [
        {"metric": "serve_overlap_imgs_per_sec_depth1",
         "value": depth1["imgs_per_sec"], "unit": "imgs/sec",
         "vs_baseline": None},
        {"metric": "serve_overlap_imgs_per_sec_depth2",
         "value": depth2["imgs_per_sec"], "unit": "imgs/sec",
         "vs_baseline": None},
        {"metric": "serve_overlap_speedup",
         "value": speedup, "unit": "x", "vs_baseline": None},
        {"metric": "serve_overlap_device_busy_fraction_depth1",
         "value": depth1["device_busy_fraction"], "unit": "fraction",
         "vs_baseline": None},
        {"metric": "serve_overlap_device_busy_fraction_depth2",
         "value": depth2["device_busy_fraction"], "unit": "fraction",
         "vs_baseline": None},
        {"metric": "serve_overlap_fetch_stall_ms_depth1",
         "value": depth1["overlap"]["fetch_stall_ms"], "unit": "ms",
         "vs_baseline": None},
        {"metric": "serve_overlap_fetch_stall_ms_depth2",
         "value": depth2["overlap"]["fetch_stall_ms"], "unit": "ms",
         "vs_baseline": None},
        {"metric": "serve_overlap_hidden_host_ms_depth2",
         "value": depth2["overlap"]["overlap_hidden_host_ms"], "unit": "ms",
         "vs_baseline": None},
        {"metric": "serve_overlap_inflight_hw_depth2",
         "value": depth2["overlap"]["inflight_hw"], "unit": "dispatches",
         "vs_baseline": None},
        {"metric": "serve_overlap_byte_identical",
         "value": int(byte_identical), "unit": "bool", "vs_baseline": None},
        {"metric": "serve_overlap_fault_lost",
         "value": sum(s["lost_requests"] for s in fault.values()),
         "unit": "requests", "vs_baseline": None},
        {"metric": "serve_overlap_steady_state_compile_misses",
         "value": sum(
             s["steady_state_compile_misses"] for s in fault.values()
         ),
         "unit": "compiles", "vs_baseline": None},
    ]
    report = {
        "stub": {"h2d_ms": h2d_ms, "device_ms": device_ms,
                 "fetch_ms": fetch_ms},
        "requests": requests,
        "concurrency": concurrency,
        "depth1": depth1,
        "depth2": depth2,
        "speedup": speedup,
        "byte_identical": byte_identical,
        "fault": fault,
        "claims": {
            "speedup_ge_1_3": speedup >= 1.3,
            "byte_identical": byte_identical,
            "zero_lost_under_faults": zero_lost,
            "zero_steady_state_recompiles": zero_recompiles,
        },
    }
    return records, report


def bench_serve_fleet(
    requests_per_backend: int = 120,
    concurrency_per_backend: int = 32,
    service_ms: float = 50.0,
    fleet_sizes=(1, 2, 4),
):
    """Multi-host serving fleet (ISSUE 19): a wire-protocol gateway
    fanning traffic over N backend engine *processes*.

    Four phases against stub backends whose device stall is a
    calibrated sleep (the ``--serve_overlap`` discipline — measures the
    serve path, not model FLOPs; digests are pure functions of pixels
    so every identity check is exact):

    1. direct in-process engine, the reference responses;
    2. gateway over ONE backend process, same seed — responses must be
       byte-identical to (1): the wire adds routing, never bytes;
    3. weak-scaling sweep over ``fleet_sizes`` processes (requests and
       concurrency scale with N) — aggregate imgs/s vs the 1-backend
       gateway is the scale-out claim;
    4. chaos — SIGKILL one of two backends mid-load: zero lost
       requests, and every response byte-identical to the unfaulted
       2-backend run (requeued work re-executes to the same bytes).
    """
    import threading

    from mx_rcnn_tpu.serve import loadgen
    from mx_rcnn_tpu.serve.engine import ServingEngine
    from mx_rcnn_tpu.serve.fleet import (
        FleetGateway,
        _FleetStubRunner,
        spawn_stub_backends,
    )

    sizes = ((24, 24), (32, 48))
    n_req, conc = requests_per_backend, concurrency_per_backend

    def run_gateway(n_backends: int, collect: bool,
                    chaos_kill_at: float = 0.0):
        procs = spawn_stub_backends(n_backends, service_ms=service_ms)
        gw = FleetGateway(
            [p.addr for p in procs], fail_threshold=2
        ).start()
        killer = None
        if chaos_kill_at > 0.0:
            killer = threading.Timer(chaos_kill_at, procs[0].kill)
            killer.start()
        try:
            rep = loadgen.run_load(
                gw, num_requests=n_req * n_backends,
                concurrency=conc * n_backends, sizes=sizes, seed=0,
                collect=collect,
            )
            rep["gateway"] = gw.snapshot()
            rep["fleet"] = gw.fleet_snapshot()
            return rep
        finally:
            if killer is not None:
                killer.cancel()
            gw.stop()
            for p in procs:
                p.stop()

    # -- phase 1: the direct engine reference ------------------------
    print("# fleet phase 1: direct in-process engine", flush=True)
    engine = ServingEngine(
        _FleetStubRunner(service_ms=service_ms), max_linger=0.004,
        max_queue=512,
    )
    with engine:
        direct = loadgen.run_load(
            engine, num_requests=n_req, concurrency=conc, sizes=sizes,
            seed=0, collect=True,
        )

    # -- phase 2 + 3: gateway sweep (N=1 doubles as the identity run) -
    sweep = {}
    for n in fleet_sizes:
        print(f"# fleet phase 2/3: gateway over {n} backend "
              f"process(es)", flush=True)
        sweep[n] = run_gateway(n, collect=(n in (1, 2)))

    def outcomes_ok(rep):
        return rep["outcomes"]["ok"]

    def results_identical(a, b, n_expect):
        ra, rb = a["_results"], b["_results"]
        if len(ra) != n_expect or len(rb) != n_expect:
            return False
        for i in range(n_expect):
            ka, va = ra[i]
            kb, vb = rb[i]
            if ka != "ok" or kb != "ok" or not _dets_equal(va, vb):
                return False
        return True

    n1_identical = results_identical(direct, sweep[1], n_req)

    base_ips = sweep[1]["imgs_per_sec"]
    scaling = [
        {
            "backends": n,
            "imgs_per_sec": round(sweep[n]["imgs_per_sec"], 2),
            "speedup_x": round(sweep[n]["imgs_per_sec"] / base_ips, 3),
            "ok": outcomes_ok(sweep[n]),
            "requests": n_req * n,
        }
        for n in fleet_sizes
    ]

    # -- phase 4: SIGKILL one of two backends mid-load ---------------
    print("# fleet phase 4: chaos — SIGKILL one of 2 backends",
          flush=True)
    # kill ~25% into the unfaulted 2-backend wall time, while the
    # victim still holds a full window of in-flight requests
    kill_at = max(0.05, sweep[2]["wall_s"] * 0.25)
    chaos = run_gateway(2, collect=True, chaos_kill_at=kill_at)
    chaos_ok = outcomes_ok(chaos)
    chaos_lost = n_req * 2 - chaos_ok
    chaos_identical = results_identical(sweep[2], chaos, n_req * 2)
    chaos_gw = chaos["gateway"]["gateway"]

    claims = {
        "n1_byte_identical": bool(n1_identical),
        "scaling_2x": scaling[1]["speedup_x"] >= 1.7,
        "scaling_4x": scaling[2]["speedup_x"] >= 3.0,
        "chaos_zero_lost": chaos_lost == 0,
        "chaos_byte_identical": bool(chaos_identical),
    }

    records = [
        {"metric": f"serve_fleet_imgs_per_sec_{n}",
         "value": round(sweep[n]["imgs_per_sec"], 2), "unit": "imgs/s",
         "vs_baseline": None}
        for n in fleet_sizes
    ] + [
        {"metric": "serve_fleet_speedup_2x",
         "value": scaling[1]["speedup_x"], "unit": "x",
         "vs_baseline": None},
        {"metric": "serve_fleet_speedup_4x",
         "value": scaling[2]["speedup_x"], "unit": "x",
         "vs_baseline": None},
        {"metric": "serve_fleet_n1_byte_identical",
         "value": int(n1_identical), "unit": "bool", "vs_baseline": None},
        {"metric": "serve_fleet_chaos_lost",
         "value": chaos_lost, "unit": "requests", "vs_baseline": None},
        {"metric": "serve_fleet_chaos_requeued",
         "value": chaos_gw["requeued"], "unit": "requests",
         "vs_baseline": None},
        {"metric": "serve_fleet_chaos_byte_identical",
         "value": int(chaos_identical), "unit": "bool",
         "vs_baseline": None},
        {"metric": "serve_fleet_chaos_hedged",
         "value": chaos_gw["hedged"], "unit": "requests",
         "vs_baseline": None},
    ]
    report = {
        "stub": {"service_ms": service_ms,
                 "requests_per_backend": n_req,
                 "concurrency_per_backend": conc},
        "scaling": scaling,
        "chaos": {
            "killed_at_s": round(kill_at, 3),
            "ok": chaos_ok,
            "lost": chaos_lost,
            "requeued": chaos_gw["requeued"],
            "hedged": chaos_gw["hedged"],
            "abandoned": chaos_gw["abandoned"],
            "byte_identical": bool(chaos_identical),
            "links": chaos["gateway"]["links"],
        },
        "claims": claims,
    }
    # drop the replay payloads before the artifact is serialized
    for rep in (direct, chaos, *sweep.values()):
        rep.pop("_results", None)
        rep.pop("_times", None)
    return records, report


def bench_serve_slo(
    network: str,
    probes: int = 5,
    probe_spacing_s: float = 10.0,
    bulk_concurrency: int = 32,
    max_batch: int = 2,
    backlog_s: float = 2.0,
    bulk_age_limit: float = 2.0,
    cache_lookups: int = 8,
) -> tuple:
    """SLO-tier serving bench: sparse interactive probes against a
    saturating bulk backlog, single-lane vs two-lane.

    Two phases over ONE runner (so the compile cache spans both — the
    cross-lane zero-recompile evidence): a *baseline* phase submits the
    probes untagged (they queue FIFO behind the backlog, today's
    single-lane behavior) and a *two-lane* phase tags them
    ``interactive`` (they preempt bulk for the next device slot).  The
    probe stream is OPEN-LOOP — one probe every ``probe_spacing_s``
    regardless of completion — so both phases offer the same interactive
    arrival rate and the bulk-throughput comparison is apples-to-apples.
    Bulk is a closed loop of ``bulk_concurrency`` clients that refills
    until the probes finish (exhaustion can't deflate the baseline).

    ``probe_spacing_s`` sets the retention floor: a two-lane probe takes
    a whole batch slot (lane-pure batch-of-1) where a baseline probe
    shares one, so bulk gives up ``max_batch - 1`` image slots per probe
    — spacing must dwarf the per-batch service time for bulk throughput
    to hold within the 10% acceptance band.

    Then two short phases on the same registry: an idempotent response-
    cache phase (same image ``cache_lookups`` times; hits must be
    byte-identical to the miss) and a bf16 serve-graph phase (a second
    runner at ``precision="bfloat16"`` whose warmup runs the detection-
    parity gate against f32 — the report lands in the artifact).

    → (records, report) in the standard artifact shape.
    """
    import dataclasses as _dc
    import threading

    from mx_rcnn_tpu.models import build_model
    from mx_rcnn_tpu.serve.batcher import QueueFull
    from mx_rcnn_tpu.serve.engine import ServingEngine
    from mx_rcnn_tpu.serve.loadgen import synthetic_image
    from mx_rcnn_tpu.serve.registry import DEFAULT_MODEL, ModelRegistry
    from mx_rcnn_tpu.serve.respcache import ResponseCache
    from mx_rcnn_tpu.serve.runner import ServeRunner
    from mx_rcnn_tpu.tools.serve import random_params, small_config

    # smaller than the serve-bench small_config: scheduling contrast is
    # the point, so short service times let a deep backlog stay cheap
    cfg = small_config(network).replace(
        SHAPE_BUCKETS=((64, 96), (96, 96)),
    )
    cfg = cfg.replace(
        dataset=_dc.replace(cfg.dataset, SCALES=((64, 96),))
    )
    bulk_sizes = ((48, 64), (64, 72), (96, 64))  # 2 rungs exercised
    probe_hw = (48, 64)                          # smallest rung
    model = build_model(cfg)
    params = random_params(model, cfg, 0)
    registry = ModelRegistry()
    registry.register(DEFAULT_MODEL, model, cfg, params)
    runner = ServeRunner(registry=registry, max_batch=max_batch)
    misses_warm = runner.warmup()

    def phase(probe_lane):
        stop = threading.Event()
        bulk_ok: list = []
        bulk_failed: list = []
        lats_ms: list = []
        idx_lock = threading.Lock()
        idx = [0]

        engine = ServingEngine(
            runner, max_queue=128, in_flight=1,
            bulk_age_limit=bulk_age_limit,
        )

        def bulk_client():
            while not stop.is_set():
                with idx_lock:
                    i = idx[0]
                    idx[0] += 1
                h, w = bulk_sizes[i % len(bulk_sizes)]
                im = synthetic_image(i, h, w, seed=0)
                try:
                    fut = engine.submit(im)
                except QueueFull:
                    time.sleep(0.005)
                    continue
                except RuntimeError:
                    return  # engine stopping
                try:
                    fut.result()
                    bulk_ok.append(1)
                except Exception:  # noqa: BLE001 — counted, not fatal
                    bulk_failed.append(1)

        with engine:
            clients = [
                threading.Thread(target=bulk_client, daemon=True,
                                 name=f"slo-bulk-{t}")
                for t in range(bulk_concurrency)
            ]
            for t in clients:
                t.start()
            time.sleep(backlog_s)  # saturate before the first probe
            t_win = time.monotonic()
            n0 = len(bulk_ok)
            futs = []
            for k in range(probes):
                im = synthetic_image(1_000_000 + k, *probe_hw, seed=1)
                lkw = {} if probe_lane is None else {"lane": probe_lane}
                t0 = time.monotonic()
                f = engine.submit(im, **lkw)
                f.add_done_callback(
                    lambda _f, _t0=t0: lats_ms.append(
                        (time.monotonic() - _t0) * 1000.0
                    )
                )
                futs.append(f)
                time.sleep(probe_spacing_s)
            for f in futs:
                f.result()  # raises if any probe failed
            window = time.monotonic() - t_win
            bulk_done = len(bulk_ok) - n0
            stop.set()
        for t in clients:
            t.join(timeout=30.0)
        snap = engine.snapshot()
        r = snap["requests"]
        return {
            "probe_lane": probe_lane or "untagged(bulk)",
            "interactive_ms": {
                "p50": _pctl_ms(lats_ms, 50),
                "p99": _pctl_ms(lats_ms, 99),
                "samples": sorted(round(x, 1) for x in lats_ms),
            },
            "bulk_imgs_per_sec": round(bulk_done / window, 3),
            "bulk_completed_in_window": bulk_done,
            "bulk_failed": len(bulk_failed),
            "window_s": round(window, 3),
            "lost_requests": (
                r["submitted"] - r["completed"] - r["failed"]
                - r["expired"] - r["stopped"]
            ),
            "scheduler": snap["scheduler"],
            "lanes": snap.get("lanes", {}),
        }

    baseline = phase(None)
    two_lane = phase("interactive")
    misses_steady = runner.compile_cache.misses - misses_warm

    # --- idempotent response cache: same image again must be a hit and
    # byte-identical to what the miss computed
    cache = ResponseCache(capacity=32)
    with ServingEngine(runner, response_cache=cache) as engine:
        im = synthetic_image(424_242, *probe_hw, seed=2)
        ref = engine.submit(im).result()
        hits = [
            engine.submit(im).result() for _ in range(cache_lookups)
        ]
    cache_identical = all(_dets_equal(ref, h) for h in hits)
    cache_snap = cache.snapshot()

    # --- bf16 serve graph: second runner on the SAME registry/params;
    # its warmup runs the f32 detection-parity gate (raises on drift)
    runner_bf16 = ServeRunner(
        registry=registry, max_batch=max_batch, precision="bfloat16"
    )
    runner_bf16.warmup()
    parity = dict(
        runner_bf16.parity[f"{registry.default_model}:bf16"]
    )

    def service_s(r):
        req = r.make_request(synthetic_image(7, *probe_hw, seed=3))
        b = r.assemble([req])
        r.run(b)
        t0 = time.monotonic()
        for _ in range(3):
            r.run(b)
        return round((time.monotonic() - t0) / 3, 4)

    svc = {"f32": service_s(runner), "bf16": service_s(runner_bf16)}

    p99_base = baseline["interactive_ms"]["p99"]
    p99_two = two_lane["interactive_ms"]["p99"]
    speedup = round(p99_base / p99_two, 2) if p99_two else None
    retention = (
        round(
            two_lane["bulk_imgs_per_sec"] / baseline["bulk_imgs_per_sec"], 4
        )
        if baseline["bulk_imgs_per_sec"] else None
    )
    report = {
        "config": {
            "network": network,
            "buckets": [list(b) for b in cfg.SHAPE_BUCKETS],
            "max_batch": max_batch,
            "probes": probes,
            "probe_spacing_s": probe_spacing_s,
            "bulk_concurrency": bulk_concurrency,
            "bulk_age_limit": bulk_age_limit,
        },
        "baseline": baseline,
        "two_lane": two_lane,
        "compile": {
            "warmup_misses": misses_warm,
            "steady_state_misses": misses_steady,
        },
        "response_cache": dict(cache_snap, byte_identical=cache_identical),
        "bf16": {"parity": parity, "service_s": svc},
    }
    tag = _METRIC_NAMES[network].replace("_e2e", "")
    records = [
        {"metric": f"serve_slo_interactive_p99_ms_baseline_{tag}",
         "value": p99_base, "unit": "ms", "vs_baseline": None},
        {"metric": f"serve_slo_interactive_p99_ms_two_lane_{tag}",
         "value": p99_two, "unit": "ms", "vs_baseline": None},
        {"metric": f"serve_slo_interactive_p99_speedup_{tag}",
         "value": speedup, "unit": "x", "vs_baseline": None},
        {"metric": f"serve_slo_bulk_imgs_per_sec_baseline_{tag}",
         "value": baseline["bulk_imgs_per_sec"], "unit": "imgs/sec",
         "vs_baseline": None},
        {"metric": f"serve_slo_bulk_imgs_per_sec_two_lane_{tag}",
         "value": two_lane["bulk_imgs_per_sec"], "unit": "imgs/sec",
         "vs_baseline": None},
        {"metric": f"serve_slo_bulk_retention_{tag}",
         "value": retention, "unit": "fraction", "vs_baseline": None},
        {"metric": f"serve_slo_preemptions_{tag}",
         "value": two_lane["scheduler"]["preemptions"], "unit": "count",
         "vs_baseline": None},
        {"metric": f"serve_slo_cache_hit_rate_{tag}",
         "value": cache_snap["hit_rate"], "unit": "fraction",
         "vs_baseline": None},
        {"metric": f"serve_slo_steady_state_compile_misses_{tag}",
         "value": misses_steady, "unit": "compiles", "vs_baseline": None},
        {"metric": f"serve_slo_lost_requests_{tag}",
         "value": baseline["lost_requests"] + two_lane["lost_requests"],
         "unit": "count", "vs_baseline": None},
        {"metric": f"serve_slo_bf16_parity_max_box_delta_px_{tag}",
         "value": parity.get("max_box_delta_px"), "unit": "px",
         "vs_baseline": None},
    ]
    return records, report


# serve-fault scenario grid: one MX_RCNN_FAULTS spec per scenario.
# Ordinal 0 on every replica is its initial warmup probe, so injected
# ordinals start at 1 to land on live traffic, not warmup.
_FAULT_SCENARIOS = {
    # clean pool: the reference run the faulted runs are diffed against
    "healthy": "",
    # hard wedge past the stall watchdog on replica 1: trips DRAINING,
    # the in-flight batch requeues, the replica rewarms and rejoins
    "wedged": "replica_wedge@1.3:10",
    # replica 2 flaps: four consecutive dispatches/probes fail, tripping
    # the breaker twice (backoff doubling) before the pool readmits it
    "flapping": ("predict_fail@2.1,predict_fail@2.2,"
                 "predict_fail@2.3,predict_fail@2.4"),
}


def _recovery_s(pool_snap: dict) -> float:
    """Max DRAINING→HEALTHY-rejoin span across replicas, from the
    transition log (None when nothing tripped)."""
    spans = []
    for rep in pool_snap.get("replicas", []):
        drain_t = None
        for tr in rep["transitions"]:
            if tr["to"] == "draining" and drain_t is None:
                drain_t = tr["t"]
            elif drain_t is not None and tr["to"] == "healthy":
                spans.append(tr["t"] - drain_t)
                drain_t = None
    return round(max(spans), 3) if spans else None


def bench_serve_fault(
    network: str,
    requests: int,
    concurrency: int,
    max_batch: int,
    linger_ms: float,
    replicas: int = 3,
    small: bool = True,
) -> tuple:
    """Fault-matrix serving bench: the same deterministic load against a
    ≥3-replica pool under each ``_FAULT_SCENARIOS`` spec.

    Proves the ISSUE 6 acceptance criteria outside the unit suite: zero
    lost requests under every scenario (ok + deadline + error ==
    submitted), detections byte-identical to the healthy run for every
    index that succeeded in both, and the wedged replica's
    drain→rewarm→rejoin visible as a measured recovery time.
    """
    import os

    from mx_rcnn_tpu.serve.engine import ServingEngine
    from mx_rcnn_tpu.serve.loadgen import run_load
    from mx_rcnn_tpu.serve.replica import HealthPolicy
    from mx_rcnn_tpu.serve.router import ReplicaPool
    from mx_rcnn_tpu.utils import faults

    replicas = max(3, replicas)
    _, _, _, sizes, factory = _serve_model(
        network, small, max_batch
    )
    # timeouts sized to CPU service times (~1-3 s/batch on the small
    # config): hedge before the watchdog, watchdog well under the wedge
    policy = HealthPolicy(stall_timeout=6.0, breaker_backoff=0.25,
                          breaker_max_backoff=4.0)
    scenarios = {}
    baseline_ok = None
    prior = os.environ.get(faults.ENV_VAR)
    try:
        for name, spec in _FAULT_SCENARIOS.items():
            if spec:
                os.environ[faults.ENV_VAR] = spec
            else:
                os.environ.pop(faults.ENV_VAR, None)
            faults.reset()
            pool = ReplicaPool(
                factory, n_replicas=replicas, policy=policy,
                hedge_timeout=3.0,
            )
            engine = ServingEngine(
                pool, max_linger=linger_ms / 1000.0, in_flight=replicas
            )
            with engine:
                report = run_load(
                    engine, num_requests=requests,
                    concurrency=concurrency, sizes=sizes, seed=0,
                    collect=True,
                )
            # A tripped replica's drain→recompile→rewarm→rejoin usually
            # outlives the load itself on CPU (rewarm recompiles the
            # whole ladder), so wait it out — bounded — before the final
            # snapshot; otherwise recovery_s is null, not measured.
            if spec:
                t_wait = time.time()
                while time.time() - t_wait < 120.0:
                    reps = pool.snapshot()["replicas"]
                    tripped = any(
                        tr["to"] == "draining"
                        for r in reps for tr in r["transitions"]
                    )
                    if tripped and all(
                        r["state"] == "healthy" for r in reps
                    ):
                        break
                    if not tripped and time.time() - t_wait > 20.0:
                        break  # fault never fired this run
                    time.sleep(0.5)
            pool_snap = pool.snapshot()
            pool.close()
            results = report.pop("_results")
            ok = {i: r for i, (kind, r) in results.items() if kind == "ok"}
            if name == "healthy":
                baseline_ok = ok
                identical = True
            else:
                identical = all(
                    _dets_equal(baseline_ok[i], ok[i])
                    for i in ok if i in baseline_ok
                )
            out = report["outcomes"]
            resolved = out["ok"] + out["deadline"] + out["error"]
            scenarios[name] = {
                "spec": spec,
                "p50_ms": report["engine"]["latency"]["e2e"]["p50_ms"],
                "p99_ms": report["engine"]["latency"]["e2e"]["p99_ms"],
                "imgs_per_sec": report["imgs_per_sec"],
                "outcomes": out,
                "lost_requests": requests - resolved,
                "detections_match_healthy": identical,
                "recovery_s": _recovery_s(pool_snap),
                "shed": report["engine"]["requests"]["shed"],
                "routing": pool_snap["routing"],
                "transitions": {
                    rep["index"]: rep["transitions"]
                    for rep in pool_snap["replicas"]
                },
            }
    finally:
        if prior is None:
            os.environ.pop(faults.ENV_VAR, None)
        else:
            os.environ[faults.ENV_VAR] = prior
        faults.reset()

    tag = _METRIC_NAMES[network].replace("_e2e", "")
    records = []
    for name, s in scenarios.items():
        records.append({
            "metric": f"serve_fault_{name}_p99_ms_{tag}",
            "value": s["p99_ms"], "unit": "ms", "vs_baseline": None,
        })
        records.append({
            "metric": f"serve_fault_{name}_lost_requests_{tag}",
            "value": s["lost_requests"], "unit": "requests",
            "vs_baseline": None,
        })
    records.append({
        "metric": f"serve_fault_wedged_recovery_s_{tag}",
        "value": scenarios["wedged"]["recovery_s"], "unit": "seconds",
        "vs_baseline": None,
    })
    records.append({
        "metric": f"serve_fault_detections_match_{tag}",
        "value": int(all(
            s["detections_match_healthy"] for s in scenarios.values()
        )),
        "unit": "bool", "vs_baseline": None,
    })
    report = {
        "replicas": replicas,
        "requests": requests,
        "concurrency": concurrency,
        "policy": {"stall_timeout": policy.stall_timeout,
                   "hedge_timeout": 3.0,
                   "breaker_backoff": policy.breaker_backoff},
        "scenarios": scenarios,
    }
    return records, report


def _dets_equal(a, b) -> bool:
    """Per-class detection lists compare bitwise."""
    if len(a) != len(b):
        return False
    return all(
        np.asarray(x).shape == np.asarray(y).shape
        and np.asarray(x).tobytes() == np.asarray(y).tobytes()
        for x, y in zip(a, b)
    )


def bench_poison(
    network: str,
    requests: int,
    concurrency: int,
    max_batch: int,
    linger_ms: float,
    replicas: int = 2,
    k: int = 2,
    small: bool = True,
) -> tuple:
    """Query-of-death containment bench (ISSUE 12 acceptance evidence).

    A 2-replica pool serves a deterministic mix of ~5% well-formed
    poison (the per-size :func:`qod_image`, whose digests the fault spec
    wires to ``poison_fail``) inside healthy traffic.  One clean run
    (no faults, no quarantine) provides the byte-identity baseline; the
    poisoned run must then show the four containment claims:

    * zero healthy losses — every non-poison request resolves ok;
    * healthy detections byte-identical to the unfaulted run;
    * every poison digest quarantined after <= K independent trips
      (global trip count bounded by ``digests * (k + 1)``, the +1
      absorbing a concurrent-trip race across replicas);
    * all replicas HEALTHY at the end — the pool outlives the poison.
    """
    import os

    from mx_rcnn_tpu.serve.engine import ServingEngine
    from mx_rcnn_tpu.serve.loadgen import qod_image, run_load
    from mx_rcnn_tpu.serve.quarantine import QuarantineTable, request_digest
    from mx_rcnn_tpu.serve.replica import HealthPolicy
    from mx_rcnn_tpu.serve.router import ReplicaPool
    from mx_rcnn_tpu.utils import faults

    replicas = max(2, replicas)
    seed = 0
    mix = [None] * 17 + ["qod"]  # ~5% poison
    _, _, _, sizes, factory = _serve_model(
        network, small, max_batch
    )
    # fail_threshold=1: a single predict failure trips the replica, so
    # every poison execution becomes an attributable trip — the regime
    # the K-trip quarantine bound is stated against (the default lenient
    # threshold lets interleaved healthy successes reset the consecutive
    # count and a qod then burns retry budget without ever tripping)
    policy = HealthPolicy(stall_timeout=6.0, fail_threshold=1,
                          breaker_backoff=0.25, breaker_max_backoff=4.0)

    # replicate run_load's rng discipline (sizes then poison, no models/
    # lanes) to learn which sizes the poisoned indices land on — that is
    # the set of digests the fault spec must target
    rng = np.random.RandomState(seed)
    req_sizes = [sizes[rng.randint(len(sizes))] for _ in range(requests)]
    req_poison = [mix[rng.randint(len(mix))] for _ in range(requests)]
    healthy_idx = [i for i, fl in enumerate(req_poison) if fl is None]
    digests = sorted({
        request_digest(qod_image(h, w, seed))
        for (h, w), fl in zip(req_sizes, req_poison) if fl == "qod"
    })
    spec = ",".join(f"poison_fail@{d[:12]}" for d in digests)

    def one_run(poisoned: bool):
        if poisoned:
            os.environ[faults.ENV_VAR] = spec
        else:
            os.environ.pop(faults.ENV_VAR, None)
        faults.reset()
        qt = QuarantineTable(k=k, ttl_s=600.0) if poisoned else None
        # budget x no_healthy_wait is the pool-outage tolerance: with 2
        # replicas and fail_threshold=1 both can be rewarming at once (a
        # full ladder recompile on CPU), and a healthy request spends one
        # resubmit per NoHealthyReplica lap — 32 laps x 5 s outlasts the
        # worst dual-rewarm window while still bounding a true qod to a
        # handful of spends before quarantine ends its circulation
        pool = ReplicaPool(
            factory, n_replicas=replicas, policy=policy,
            hedge_timeout=3.0, no_healthy_wait=5.0, quarantine=qt,
        )
        engine = ServingEngine(
            pool, max_linger=linger_ms / 1000.0, in_flight=replicas,
            retry_budget=32,
        )
        with engine:
            report = run_load(
                engine, num_requests=requests, concurrency=concurrency,
                sizes=sizes, seed=seed, collect=True, poison_mix=mix,
            )
        if poisoned:
            # wait out the tripped replicas' drain->rewarm->rejoin so
            # "all replicas healthy" is measured, not raced
            t_wait = time.time()
            while time.time() - t_wait < 120.0:
                reps = pool.snapshot()["replicas"]
                if all(r["state"] == "healthy" for r in reps):
                    break
                time.sleep(0.5)
        pool_snap = pool.snapshot()
        pool.close()
        return report, pool_snap, (qt.snapshot() if qt else None)

    prior = os.environ.get(faults.ENV_VAR)
    try:
        base_report, _, _ = one_run(poisoned=False)
        poi_report, pool_snap, q_snap = one_run(poisoned=True)
    finally:
        if prior is None:
            os.environ.pop(faults.ENV_VAR, None)
        else:
            os.environ[faults.ENV_VAR] = prior
        faults.reset()

    base_res = base_report.pop("_results")
    poi_res = poi_report.pop("_results")
    base_report.pop("_times", None)
    poi_report.pop("_times", None)

    healthy_lost = sum(
        1 for i in healthy_idx if poi_res.get(i, ("lost",))[0] != "ok"
    )
    byte_identical = all(
        poi_res.get(i, ("lost",))[0] == "ok"
        and base_res.get(i, ("lost",))[0] == "ok"
        and _dets_equal(base_res[i][1], poi_res[i][1])
        for i in healthy_idx
    )
    all_healthy = all(
        r["state"] == "healthy" for r in pool_snap["replicas"]
    )
    quarantined = set(q_snap["quarantined"])
    within_k = (
        all(d[:12] in quarantined for d in digests)
        and q_snap["trips"] <= len(digests) * (k + 1)
    )
    claims = {
        "zero_healthy_lost": healthy_lost == 0,
        "healthy_byte_identical": byte_identical,
        "poison_quarantined_within_k": within_k,
        "all_replicas_healthy": all_healthy,
    }

    tag = _METRIC_NAMES[network].replace("_e2e", "")
    records = [
        {"metric": f"serve_poison_healthy_lost_{tag}",
         "value": healthy_lost, "unit": "requests", "vs_baseline": None},
        {"metric": f"serve_poison_healthy_byte_identical_{tag}",
         "value": int(byte_identical), "unit": "bool", "vs_baseline": None},
        {"metric": f"serve_poison_quarantined_within_k_{tag}",
         "value": int(within_k), "unit": "bool", "vs_baseline": None},
        {"metric": f"serve_poison_replicas_healthy_{tag}",
         "value": int(all_healthy), "unit": "bool", "vs_baseline": None},
        {"metric": f"serve_poison_trips_{tag}",
         "value": q_snap["trips"], "unit": "trips", "vs_baseline": None},
        {"metric": f"serve_poison_fastfail_hits_{tag}",
         "value": q_snap["fastfail_hits"], "unit": "requests",
         "vs_baseline": None},
    ]
    report = {
        "replicas": replicas,
        "requests": requests,
        "concurrency": concurrency,
        "k": k,
        "poison_mix_rate": mix.count("qod") / len(mix),
        "poison_requests": requests - len(healthy_idx),
        "digests": [d[:12] for d in digests],
        "fault_spec": spec,
        "claims": claims,
        "baseline": {"outcomes": base_report["outcomes"]},
        "poisoned": {
            "outcomes": poi_report["outcomes"],
            "poison_outcomes": poi_report.get("poison_outcomes"),
            "engine_requests": poi_report["engine"]["requests"],
            "quarantine": q_snap,
        },
    }
    return records, report


def bench_swap(
    network: str,
    requests: int,
    concurrency: int,
    max_batch: int,
    linger_ms: float,
    small: bool = True,
    replicas: int = 2,
) -> tuple:
    """Model-lifecycle bench (ISSUE 7): live hot-swap under load, the
    fault-rollback matrix, and two-family tenancy through one batcher.

    Three scenarios, results compared bitwise across waves and engines
    (same program, same batch size, same inputs):

    * ``hot_swap`` — one engine serves three load waves: wave A pins the
      v1 reference detections, wave B runs with a background
      ``engine.swap`` firing mid-load (blocking through commit + canary),
      wave C pins v2.  Wave B requests are classified against the swap
      window via per-request timestamps: done-before must match v1
      byte-for-byte, submitted-after must match v2, straddlers must
      match one of the two.  Zero lost/failed requests and ZERO compile
      misses from warmup through the swap (the candidate warms through
      the already-compiled executables — params are a jit argument).
    * ``rollback`` — one registry takes three swap attempts faulted (by
      registry-wide swap ordinal) at verify, warm, and canary; after
      every rollback a load wave must still serve v1 bytes, and the 4th
      (unfaulted) swap must land v2.
    * ``tenancy`` — a second model family rides the same batcher/ladder;
      two identical mixed-model waves prove per-(model, bucket) compile
      hits after warmup: zero steady-state recompiles.
    """
    import os
    import tempfile
    import threading

    import jax

    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.core.checkpoint import save_checkpoint
    from mx_rcnn_tpu.models import build_model
    from mx_rcnn_tpu.serve.engine import ServingEngine
    from mx_rcnn_tpu.serve.loadgen import DEFAULT_SIZES, run_load
    from mx_rcnn_tpu.serve.registry import (
        DEFAULT_MODEL,
        ModelRegistry,
        SwapRolledBack,
    )
    from mx_rcnn_tpu.serve.router import ReplicaPool, make_replica_factory
    from mx_rcnn_tpu.serve.runner import ServeRunner
    from mx_rcnn_tpu.tools.serve import small_config
    from mx_rcnn_tpu.utils import faults

    if small:
        cfg = small_config(network)
        sizes = ((72, 96), (96, 128), (64, 80))
    else:
        cfg = generate_config(network, "PascalVOC")
        sizes = DEFAULT_SIZES
    model = build_model(cfg)
    h, w = cfg.SHAPE_BUCKETS[0]

    def init_params(seed):
        return model.init(
            {"params": jax.random.key(seed)},
            np.zeros((1, h, w, 3), np.float32),
            np.array([[h, w, 1.0]], np.float32),
            train=False,
        )["params"]

    params_v1 = init_params(0)
    # same structure/shapes, different values: the signature gate admits
    # it and the swap visibly changes detections
    ckpt_v2 = save_checkpoint(
        os.path.join(tempfile.mkdtemp(prefix="bench-swap-"), "v2"),
        {"params": init_params(1)}, 1,
    )

    def make_engine(n_replicas):
        reg = ModelRegistry()
        reg.register(DEFAULT_MODEL, model, cfg, params_v1)
        if n_replicas > 1:
            factory = make_replica_factory(
                lambda registry, device: ServeRunner(
                    registry=registry, device=device, max_batch=max_batch,
                ),
                registry=reg,
            )
            runner = ReplicaPool(factory, n_replicas=n_replicas)
        else:
            runner = ServeRunner(
                registry=reg, max_batch=max_batch
            )
        eng = ServingEngine(
            runner, max_linger=linger_ms / 1000.0,
            in_flight=max(2, n_replicas),
        )
        return eng, runner

    def load(eng, n=requests, models=None):
        return run_load(
            eng, num_requests=n, concurrency=concurrency, sizes=sizes,
            seed=0, collect=True, models=models,
        )

    def ok_dets(report):
        return {
            i: r for i, (kind, r) in report["_results"].items() if kind == "ok"
        }

    def wave_summary(report):
        out = report["outcomes"]
        resolved = out["ok"] + out["deadline"] + out["error"]
        return {
            "outcomes": out,
            "lost_requests": report["requests"] - resolved,
            "imgs_per_sec": report["imgs_per_sec"],
            "wall_s": report["wall_s"],
        }

    # ------------------------------------------- scenario 1: hot_swap
    # the swap wave runs 2x requests: the blocking swap (dominated by
    # the host-side checkpoint restore on CPU) must RETURN while load is
    # still flowing, or no request lands entirely after the window
    n_swap = 2 * requests
    eng, runner = make_engine(max(1, replicas))
    swap_out = {}
    with eng:
        rep_a = load(eng, n=n_swap)
        ref_v1 = ok_dets(rep_a)
        misses_warm = eng.snapshot()["compile"]["misses"]
        base_done = eng.metrics.completed

        def fire_swap():
            # wait until wave B is genuinely mid-flight, then block
            # through the full verify → warm → commit → canary pipeline
            t_end = time.time() + 120.0
            while (eng.metrics.completed - base_done < max(1, requests // 3)
                   and time.time() < t_end):
                time.sleep(0.002)
            swap_out["t0"] = time.monotonic()
            try:
                swap_out["result"] = eng.swap(
                    DEFAULT_MODEL, ckpt_v2, block=True, timeout=300
                )
            except Exception as e:  # noqa: BLE001 — recorded as evidence
                swap_out["error"] = repr(e)
            swap_out["t1"] = time.monotonic()

        th = threading.Thread(target=fire_swap, name="bench-swap")
        th.start()
        rep_b = load(eng, n=n_swap)
        th.join()
        rep_c = load(eng, n=n_swap)
        ref_v2 = ok_dets(rep_c)
        snap = eng.snapshot()
    if hasattr(runner, "close"):
        runner.close()

    misses_end = snap["compile"]["misses"]
    dets_b, times_b = ok_dets(rep_b), rep_b["_times"]
    t0, t1 = swap_out.get("t0"), swap_out.get("t1")
    pre = post = straddle = 0
    pre_ok = post_ok = straddle_ok = True
    for i, (ts, td) in times_b.items():
        if i not in dets_b or t0 is None:
            continue
        if td <= t0:
            pre += 1
            pre_ok &= _dets_equal(dets_b[i], ref_v1[i])
        elif ts >= t1:
            post += 1
            post_ok &= _dets_equal(dets_b[i], ref_v2[i])
        else:
            straddle += 1
            straddle_ok &= (
                _dets_equal(dets_b[i], ref_v1[i])
                or _dets_equal(dets_b[i], ref_v2[i])
            )
    versions_changed_output = sum(
        1 for i in ref_v1 if i in ref_v2 and not _dets_equal(ref_v1[i], ref_v2[i])
    )
    waves = [wave_summary(r) for r in (rep_a, rep_b, rep_c)]
    hot_swap = {
        "replicas": max(1, replicas),
        "wave_requests": n_swap,
        "waves": waves,
        "lost_requests": sum(wv["lost_requests"] for wv in waves),
        "failed_requests": sum(
            wv["outcomes"]["error"] + wv["outcomes"]["deadline"]
            for wv in waves
        ),
        "swap": swap_out.get("result", swap_out.get("error")),
        "swap_block_wall_s": (
            round(t1 - t0, 3) if t0 is not None else None
        ),
        "window": {
            "pre": pre, "post": post, "straddle": straddle,
            "pre_byte_identical_v1": bool(pre_ok),
            "post_byte_identical_v2": bool(post_ok),
            "straddle_one_of_two": bool(straddle_ok),
        },
        "versions_changed_output": versions_changed_output,
        "compile_misses_after_warmup": misses_warm,
        "compile_misses_final": misses_end,
        "recompiles_through_swap": misses_end - misses_warm,
        "registry": snap.get("registry"),
    }

    # ------------------------------------------- scenario 2: rollback
    prior = os.environ.get(faults.ENV_VAR)
    rollback = {}
    n_check = max(8, requests // 4)
    try:
        # keyed by registry-wide swap ordinal: attempt 1 dies at verify,
        # 2 at warm, 3 at canary; attempt 4 finds no matching fault
        os.environ[faults.ENV_VAR] = (
            "swap_verify_fail@1,swap_warm_fail@2,canary_fail@3"
        )
        faults.reset()
        eng2, runner2 = make_engine(1)
        with eng2:
            for stage in ("verify", "warm", "canary"):
                entry = {"rolled_back": False}
                try:
                    eng2.swap(DEFAULT_MODEL, ckpt_v2, block=True, timeout=300)
                except SwapRolledBack as e:
                    entry["rolled_back"] = True
                    entry["stage"] = e.stage
                rep = load(eng2, n=n_check)
                dets = ok_dets(rep)
                entry["still_serving_v1_bytes"] = bool(dets) and all(
                    _dets_equal(dets[i], ref_v1[i]) for i in dets
                )
                entry.update(wave_summary(rep))
                rollback[stage] = entry
            final = eng2.swap(DEFAULT_MODEL, ckpt_v2, block=True, timeout=300)
            rep = load(eng2, n=n_check)
            dets = ok_dets(rep)
            rollback["final_swap"] = {
                "result": final,
                "serving_v2_bytes": bool(dets) and all(
                    _dets_equal(dets[i], ref_v2[i]) for i in dets
                ),
                **wave_summary(rep),
            }
            rollback["registry"] = eng2.snapshot().get("registry")
        if hasattr(runner2, "close"):
            runner2.close()
    finally:
        if prior is None:
            os.environ.pop(faults.ENV_VAR, None)
        else:
            os.environ[faults.ENV_VAR] = prior
        faults.reset()

    # ------------------------------------------- scenario 3: tenancy
    tenant_net = "vgg" if network != "vgg" else "resnet50"
    t_cfg = small_config(tenant_net) if small else generate_config(
        tenant_net, "PascalVOC"
    )
    t_model = build_model(t_cfg)
    th_, tw_ = t_cfg.SHAPE_BUCKETS[0]
    t_params = t_model.init(
        {"params": jax.random.key(0)},
        np.zeros((1, th_, tw_, 3), np.float32),
        np.array([[th_, tw_, 1.0]], np.float32),
        train=False,
    )["params"]
    reg3 = ModelRegistry()
    reg3.register(DEFAULT_MODEL, model, cfg, params_v1)
    reg3.register("tenant", t_model, t_cfg, t_params)
    runner3 = ServeRunner(
        registry=reg3, max_batch=max_batch
    )
    eng3 = ServingEngine(
        runner3, max_linger=linger_ms / 1000.0, in_flight=2
    )
    mix = [None, "tenant"]
    with eng3:
        rep1 = load(eng3, models=mix)
        m1 = eng3.snapshot()["compile"]["misses"]
        rep2 = load(eng3, models=mix)
        snap3 = eng3.snapshot()
    tenancy = {
        "families": {DEFAULT_MODEL: network, "tenant": tenant_net},
        "waves": [wave_summary(rep1), wave_summary(rep2)],
        "per_model": snap3.get("models"),
        "compile_misses_after_first_wave": m1,
        "compile_misses_final": snap3["compile"]["misses"],
        "steady_state_recompiles": snap3["compile"]["misses"] - m1,
        "compile_hits": snap3["compile"]["hits"],
    }

    tag = _METRIC_NAMES[network].replace("_e2e", "")
    rollback_ok = all(
        rollback[s]["rolled_back"] and rollback[s]["still_serving_v1_bytes"]
        for s in ("verify", "warm", "canary")
    ) and rollback["final_swap"]["serving_v2_bytes"]
    records = [
        {
            "metric": f"swap_lost_requests_{tag}",
            "value": hot_swap["lost_requests"], "unit": "requests",
            "vs_baseline": None,
        },
        {
            "metric": f"swap_failed_requests_{tag}",
            "value": hot_swap["failed_requests"], "unit": "requests",
            "vs_baseline": None,
        },
        {
            "metric": f"swap_pre_window_byte_identical_{tag}",
            "value": int(pre_ok and pre > 0), "unit": "bool",
            "vs_baseline": None,
        },
        {
            "metric": f"swap_post_window_byte_identical_{tag}",
            "value": int(post_ok and post > 0), "unit": "bool",
            "vs_baseline": None,
        },
        {
            "metric": f"swap_recompiles_through_swap_{tag}",
            "value": hot_swap["recompiles_through_swap"], "unit": "compiles",
            "vs_baseline": None,
        },
        {
            "metric": f"swap_block_wall_s_{tag}",
            "value": hot_swap["swap_block_wall_s"], "unit": "seconds",
            "vs_baseline": None,
        },
        {
            "metric": f"swap_rollback_matrix_ok_{tag}",
            "value": int(rollback_ok), "unit": "bool", "vs_baseline": None,
        },
        {
            "metric": f"swap_tenancy_steady_state_recompiles_{tag}",
            "value": tenancy["steady_state_recompiles"], "unit": "compiles",
            "vs_baseline": None,
        },
    ]
    report = {
        "requests": requests,
        "concurrency": concurrency,
        "max_batch": max_batch,
        "hot_swap": hot_swap,
        "rollback": rollback,
        "tenancy": tenancy,
    }
    return records, report


def bench_rollout(
    network: str,
    requests: int,
    concurrency: int,
    max_batch: int,
    linger_ms: float,
    small: bool = True,
    distill_steps: int = 2,
) -> tuple:
    """Progressive-rollout bench (ISSUE 17): the full candidate
    lifecycle on the real serve stack, CPU-runnable.

    Three scenarios, detections compared bitwise across waves (same
    program, same batch size, same inputs):

    * ``split_promote`` — a faithful candidate (byte-identical weights,
      new version) rolls out under live load with a 30% traffic split
      and shadow scoring; the evaluator must promote it with zero lost
      requests, zero failed requests, every response byte-identical to
      the v1 reference, and ZERO compile misses from warmup onward
      (candidate warms through the already-compiled executables).
    * ``shadow_rollback`` — a divergent candidate (different random
      init) runs in pure shadow mode (0% split): live traffic must stay
      byte-identical to the incumbent for the whole rollout, the shadow
      comparisons must trip the divergence bounds, and the controller
      must auto-roll-back leaving v1 LIVE and the candidate RETIRED.
    * ``closed_loop`` — served detections are harvested with
      ``tools/distill.py`` into synthetic-schema records, fine-tuned
      with the existing trainer, and the resulting checkpoint is
      submitted back through the rollout — serve→train→serve, ending
      with the distilled model promoted to LIVE.
    """
    import os
    import tempfile

    import jax

    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.core.checkpoint import save_checkpoint
    from mx_rcnn_tpu.models import build_model
    from mx_rcnn_tpu.serve.engine import ServingEngine
    from mx_rcnn_tpu.serve.loadgen import DEFAULT_SIZES, run_load
    from mx_rcnn_tpu.serve.registry import DEFAULT_MODEL, ModelRegistry
    from mx_rcnn_tpu.serve.rollout import RolloutAborted, RolloutPolicy
    from mx_rcnn_tpu.serve.runner import ServeRunner
    from mx_rcnn_tpu.tools import distill
    from mx_rcnn_tpu.tools.serve import small_config

    if small:
        cfg = small_config(network)
        sizes = ((72, 96), (96, 128), (64, 80))
    else:
        cfg = generate_config(network, "PascalVOC")
        sizes = DEFAULT_SIZES
    model = build_model(cfg)
    h, w = cfg.SHAPE_BUCKETS[0]

    def init_params(seed):
        return model.init(
            {"params": jax.random.key(seed)},
            np.zeros((1, h, w, 3), np.float32),
            np.array([[h, w, 1.0]], np.float32),
            train=False,
        )["params"]

    params_v1 = init_params(0)
    tmp = tempfile.mkdtemp(prefix="bench-rollout-")
    # faithful candidate: byte-identical weights under a new version —
    # shadow divergence is exactly zero, the promote path is pure
    # lifecycle mechanics
    ckpt_faithful = save_checkpoint(
        os.path.join(tmp, "faithful"), {"params": params_v1}, 1
    )
    # divergent candidate: a different random init — same structure
    # (admitted by the verify gate) but wildly different detections
    ckpt_divergent = save_checkpoint(
        os.path.join(tmp, "divergent"), {"params": init_params(1)}, 1
    )

    def make_engine():
        reg = ModelRegistry()
        reg.register(DEFAULT_MODEL, model, cfg, params_v1)
        runner = ServeRunner(
            registry=reg, max_batch=max_batch
        )
        eng = ServingEngine(
            runner, max_linger=linger_ms / 1000.0, in_flight=2
        )
        return eng, reg

    def load(eng, n=requests):
        return run_load(
            eng, num_requests=n, concurrency=concurrency, sizes=sizes,
            seed=0, collect=True,
        )

    def ok_dets(report):
        return {
            i: r for i, (kind, r) in report["_results"].items() if kind == "ok"
        }

    def wave_summary(report):
        out = report["outcomes"]
        resolved = out["ok"] + out["deadline"] + out["error"]
        return {
            "outcomes": out,
            "lost_requests": report["requests"] - resolved,
            "imgs_per_sec": report["imgs_per_sec"],
        }

    def wait_state(ro, timeout=180.0):
        t_end = time.time() + timeout
        while time.time() < t_end:
            if ro.state == "evaluating" or ro.done():
                return
            time.sleep(0.01)

    # all waves share seed=0, so detections are comparable by index
    n_wave = 2 * requests

    # -------------------------------------- scenario 1: split_promote
    eng, reg = make_engine()
    with eng:
        ctl = eng.attach_rollout()
        rep_ref = load(eng, n=n_wave)
        ref_v1 = ok_dets(rep_ref)
        misses_warm = eng.snapshot()["compile"]["misses"]
        ro = ctl.start(DEFAULT_MODEL, ckpt_faithful, policy=RolloutPolicy(
            split_pct=30.0, shadow=True, min_compared=4,
            min_served=max(4, requests // 8),
            min_error_samples=10**6, min_latency_samples=10**6,
            hold_s=0.2, eval_interval_s=0.02, score_thresh=0.01,
        ))
        wait_state(ro)
        rep_b = load(eng, n=n_wave)
        promote = ro.result(300)
        rep_c = load(eng, n=n_wave)
        snap = eng.snapshot()
    misses_end = snap["compile"]["misses"]
    dets_b, dets_c = ok_dets(rep_b), ok_dets(rep_c)
    # faithful weights: EVERY response — either arm, before or after
    # the flip — must match the v1 reference byte-for-byte
    split_identical = bool(dets_b) and all(
        _dets_equal(dets_b[i], ref_v1[i]) for i in dets_b
    )
    post_identical = bool(dets_c) and all(
        _dets_equal(dets_c[i], ref_v1[i]) for i in dets_c
    )
    waves = [wave_summary(r) for r in (rep_ref, rep_b, rep_c)]
    promote_lost = sum(wv["lost_requests"] for wv in waves)
    promote_failed = sum(
        wv["outcomes"]["error"] + wv["outcomes"]["deadline"] for wv in waves
    )
    split_promote = {
        "wave_requests": n_wave,
        "waves": waves,
        "lost_requests": promote_lost,
        "failed_requests": promote_failed,
        "promote": promote,
        "split_served": promote.get("split_served"),
        "split_identical_bytes": split_identical,
        "post_promote_identical_bytes": post_identical,
        "live_version": reg.live(DEFAULT_MODEL).version,
        "compile_misses_after_warmup": misses_warm,
        "compile_misses_final": misses_end,
        "recompiles_through_rollout": misses_end - misses_warm,
    }

    # ------------------------------------ scenario 2: shadow_rollback
    eng2, reg2 = make_engine()
    with eng2:
        ctl2 = eng2.attach_rollout()
        rep_ref2 = load(eng2, n=n_wave)
        ref2_v1 = ok_dets(rep_ref2)
        ro2 = ctl2.start(DEFAULT_MODEL, ckpt_divergent, policy=RolloutPolicy(
            split_pct=0.0, shadow=True, min_compared=4,
            min_error_samples=10**6, min_latency_samples=10**6,
            hold_s=3600.0, eval_interval_s=0.02, score_thresh=0.01,
        ))
        wait_state(ro2)
        rep_b2 = load(eng2, n=n_wave)
        rollback = {"aborted": False}
        try:
            ro2.result(300)
        except RolloutAborted as e:
            rollback["aborted"] = True
            rollback["stage"] = e.stage
            rollback["cause"] = str(e.cause)
        rep_c2 = load(eng2, n=n_wave)
        ctl2.stop()
    divergence = ro2.report.snapshot()
    dets_b2, dets_c2 = ok_dets(rep_b2), ok_dets(rep_c2)
    incumbent_identical = (
        bool(dets_b2) and bool(dets_c2)
        and all(_dets_equal(dets_b2[i], ref2_v1[i]) for i in dets_b2)
        and all(_dets_equal(dets_c2[i], ref2_v1[i]) for i in dets_c2)
    )
    rollback.update({
        "waves": [wave_summary(r) for r in (rep_ref2, rep_b2, rep_c2)],
        "incumbent_identical_bytes": incumbent_identical,
        "live_version": reg2.live(DEFAULT_MODEL).version,
        "divergence": divergence,
    })

    # --------------------------------------- scenario 3: closed_loop
    eng3, reg3 = make_engine()
    with eng3:
        ctl3 = eng3.attach_rollout()
        rep_h = load(eng3, n=n_wave)
        # regenerate the loadgen size stream (same rng discipline as
        # run_load) so each harvested response carries its true (h, w)
        size_rng = np.random.RandomState(0)
        req_sizes = [
            sizes[size_rng.randint(len(sizes))] for _ in range(n_wave)
        ]
        harvested = ok_dets(rep_h)
        records_in = distill.harvest(
            [(harvested[i], req_sizes[i]) for i in sorted(harvested)],
            min_score=0.05,
            num_classes=cfg.dataset.NUM_CLASSES,
        )
        rec_path = os.path.join(tmp, "distilled.jsonl")
        distill.write_records(records_in, rec_path)
        loop = {"harvested_records": len(records_in)}
        if records_in:
            ckpt_distilled = distill.fine_tune(
                distill.read_records(rec_path), network=network,
                steps=distill_steps, seed=0,
                out_dir=os.path.join(tmp, "loop"),
                init_donor=params_v1,
            )
            # a genuinely retrained candidate diverges by design: the
            # loop's gate is lifecycle evidence (split health), with the
            # divergence bounds opened up by the operator
            ro3 = ctl3.start(DEFAULT_MODEL, ckpt_distilled, policy=RolloutPolicy(
                split_pct=30.0, shadow=False, min_compared=0,
                min_served=4,
                max_box_delta_px=1e9, max_score_delta=1e9,
                max_unmatched=10**6, max_count_drift=1e9,
                min_error_samples=10**6, min_latency_samples=10**6,
                hold_s=0.2, eval_interval_s=0.02,
            ))
            wait_state(ro3)
            rep_l = load(eng3, n=n_wave)
            loop_promote = ro3.result(300)
            loop.update({
                "checkpoint": ckpt_distilled,
                "promote": loop_promote,
                "waves": [wave_summary(r) for r in (rep_h, rep_l)],
                "lost_requests": sum(
                    wave_summary(r)["lost_requests"] for r in (rep_h, rep_l)
                ),
                "live_version": reg3.live(DEFAULT_MODEL).version,
            })

    tag = _METRIC_NAMES[network].replace("_e2e", "")
    claims = {
        "zero_lost_requests": bool(
            promote_lost == 0 and promote_failed == 0
            and loop.get("lost_requests") == 0
        ),
        "control_arm_byte_identical": bool(
            split_identical and incumbent_identical
        ),
        "divergence_auto_rollback": bool(
            rollback["aborted"] and rollback.get("stage") == "evaluate"
            and rollback["live_version"] == 1
            and incumbent_identical
        ),
        "zero_steady_state_recompiles": bool(
            split_promote["recompiles_through_rollout"] == 0
        ),
        "closed_loop_promoted": bool(
            loop.get("harvested_records", 0) > 0
            and loop.get("live_version") == 2
        ),
    }
    records = [
        {
            "metric": f"rollout_split_served_{tag}",
            "value": split_promote["split_served"], "unit": "requests",
            "vs_baseline": None,
        },
        {
            "metric": f"rollout_shadow_compared_{tag}",
            "value": divergence["compared"], "unit": "comparisons",
            "vs_baseline": None,
        },
        {
            "metric": f"rollout_promote_lost_requests_{tag}",
            "value": promote_lost, "unit": "requests", "vs_baseline": None,
        },
        {
            "metric": f"rollout_rollback_incumbent_identical_{tag}",
            "value": int(incumbent_identical), "unit": "bool",
            "vs_baseline": None,
        },
        {
            "metric": f"rollout_steady_state_recompiles_{tag}",
            "value": split_promote["recompiles_through_rollout"],
            "unit": "compiles", "vs_baseline": None,
        },
        {
            "metric": f"rollout_distill_records_{tag}",
            "value": loop.get("harvested_records", 0), "unit": "records",
            "vs_baseline": None,
        },
        {
            "metric": f"rollout_loop_promoted_version_{tag}",
            "value": loop.get("live_version"), "unit": "version",
            "vs_baseline": None,
        },
    ]
    report = {
        "requests": requests,
        "concurrency": concurrency,
        "max_batch": max_batch,
        "split_promote": split_promote,
        "shadow_rollback": rollback,
        "closed_loop": loop,
        "divergence": divergence,
        "claims": claims,
    }
    return records, report


def _smoke_config(batch_images: int):
    """Tiny CPU-runnable train config (96×96 bucket, shrunk RPN/ROI
    budgets) — the same shrink the CLI smoke tests use, so the pipeline
    bench measures loop mechanics, not model size."""
    from mx_rcnn_tpu.config import generate_config

    cfg = generate_config("resnet50", "PascalVOC")
    return cfg.replace(
        SHAPE_BUCKETS=((96, 96),),
        TRAIN=dataclasses.replace(
            cfg.TRAIN,
            RPN_PRE_NMS_TOP_N=256,
            RPN_POST_NMS_TOP_N=32,
            BATCH_ROIS=16,
            RPN_BATCH_SIZE=32,
            BATCH_IMAGES=batch_images,
        ),
        dataset=dataclasses.replace(
            cfg.dataset, SCALES=((96, 96),), MAX_GT_BOXES=8
        ),
    )


def _eval_records(report: dict) -> list:
    """Eval data-plane report (``tools/bench_eval.py ::
    data_plane_report``) → the JSON-line records (pure; the bench schema
    test builds a synthetic report and asserts the throughput, stage
    counters, and bitwise-equivalence fields are present without running
    the benchmark).

    ``vs_baseline`` on the throughput record is the overlapped/serial
    ratio measured IN THE SAME PROCESS over the identical seeded stream —
    reportable only because ``byte_identical`` holds.
    """
    over = report["overlapped"]
    assembly = over.get("assembly", {})
    completion = over.get("completion", {})
    cache = report.get("prepared_cache_stats", {})

    def rec(metric, value, unit, vs=None):
        return {"metric": metric, "value": value, "unit": unit,
                "vs_baseline": vs}

    return [
        rec("eval_data_plane_imgs_per_sec",
            report["overlapped_imgs_per_sec"], "imgs/sec",
            vs=report["speedup"]),
        rec("eval_data_plane_serial_imgs_per_sec",
            report["baseline_imgs_per_sec"], "imgs/sec"),
        rec("eval_assembly_occupancy",
            assembly.get("occupancy", 0.0), "fraction"),
        rec("eval_assembly_queue_depth_max",
            assembly.get("queue_depth_max", 0), "batches"),
        rec("eval_completion_inflight_max",
            completion.get("inflight_max", 0), "tasks"),
        rec("eval_completion_block_s",
            completion.get("block_s", 0.0), "seconds"),
        rec("eval_in_flight_window", report["in_flight"], "batches"),
        rec("eval_prepared_cache_hits", cache.get("hits", 0), "hits"),
        rec("eval_byte_identical", int(report["byte_identical"]), "bool"),
    ]


def _pipeline_records(report: dict) -> list:
    """Pipeline report → the JSON-line records (pure; the bench schema
    test builds a synthetic report and asserts the feed-occupancy and
    fetch-stall fields are present without running the model)."""
    feed = report["feed"]
    loop = report["loop"]
    def rec(metric, value, unit):
        return {"metric": metric, "value": value, "unit": unit,
                "vs_baseline": None}
    return [
        rec("pipeline_feed_occupancy", feed["occupancy"], "fraction"),
        rec("pipeline_feed_starved_steps",
            feed["feed_starved_after_first"], "steps"),
        rec("pipeline_min_staged_ahead", report["min_staged_ahead"],
            "batches"),
        rec("pipeline_aux_fetches", loop["fetches"], "fetches"),
        rec("pipeline_fetch_stalls", loop["fetch_stalls"], "stalls"),
        rec("pipeline_fetch_stall_ms", loop["fetch_stall_ms"], "ms"),
        rec("pipeline_interflush_blocking_fetches",
            report["interflush_blocking_fetches"], "fetches"),
        rec("pipeline_k1_byte_identical",
            int(report["k1_byte_identical"]), "bool"),
        rec("pipeline_train_imgs_per_sec_cpu_smoke",
            report["imgs_per_sec"], "imgs/sec"),
    ]


def bench_pipeline(
    steps: int, aux_interval: int, feed_depth: int, batch_images: int
) -> tuple:
    """Measure the device-resident step pipeline on the CPU smoke config.

    Three runs over the identical (seeded) batch stream with ONE shared
    compiled step: a synchronous GuardedLoop baseline, a PipelinedLoop
    at K=1 (byte-identical check: donation + feed must not perturb a
    single bit of the final state), and the measured PipelinedLoop at
    K=``aux_interval`` behind a depth-``feed_depth`` DeviceFeed.
    → (records, report).  CPU smoke numbers prove the MECHANISM (overlap
    counters, zero inter-flush fetches); device wins ride the next TPU
    round (ROOFLINE "host gap, revisited").
    """
    import jax

    from mx_rcnn_tpu.core.pipeline import DeviceFeed, PipelinedLoop
    from mx_rcnn_tpu.core.resilience import GuardedLoop, host_copy
    from mx_rcnn_tpu.core.train import (
        create_train_state,
        make_optimizer,
        make_train_step,
    )
    from mx_rcnn_tpu.data.loader import TrainLoader
    from mx_rcnn_tpu.models import build_model
    from mx_rcnn_tpu.utils.load_data import load_gt_roidb

    cfg = _smoke_config(batch_images)
    _, roidb = load_gt_roidb(
        cfg, None, flip=False, synthetic_size=max(8, 4 * batch_images)
    )
    model = build_model(cfg)
    h, w = cfg.SHAPE_BUCKETS[0]
    params = model.init(
        {"params": jax.random.key(0), "sampling": jax.random.key(1)},
        images=np.zeros((1, h, w, 3), np.float32),
        im_info=np.array([[h, w, 1.0]], np.float32),
        gt_boxes=np.zeros((1, cfg.dataset.MAX_GT_BOXES, 5), np.float32),
        gt_valid=np.zeros((1, cfg.dataset.MAX_GT_BOXES), bool),
        train=True,
    )["params"]
    tx = make_optimizer(cfg, lambda s: cfg.TRAIN.LEARNING_RATE)
    step_fn = make_train_step(model, tx, donate=True)
    # owning copy, not a device_get view: both runs re-place from
    # host_params while the donating step recycles device buffers
    host_params = host_copy(params)

    def batch_stream(n):
        loader = TrainLoader(
            roidb, cfg, batch_images, shuffle=True, seed=0
        )
        got = 0
        while got < n:
            for b in loader:
                yield b
                got += 1
                if got >= n:
                    return

    def state_bytes(state):
        return b"".join(
            np.asarray(x).tobytes()
            for x in jax.tree_util.tree_leaves(jax.device_get(state))
        )

    rng = jax.random.key(0)

    def run_sync(n):
        state = create_train_state(host_params, tx)
        guard = GuardedLoop(step_fn)
        for b in batch_stream(n):
            state, _aux, _ok = guard.step(state, b, rng)
        return state_bytes(state)

    def run_pipelined(n, k):
        state = create_train_state(host_params, tx)
        loop = PipelinedLoop(step_fn, aux_interval=k)
        feed = DeviceFeed(batch_stream(n), depth=feed_depth)
        t0 = time.perf_counter()
        try:
            for b in feed:
                state, _ready, _ok = loop.step(state, b, rng)
        finally:
            stats = feed.stats()
            feed.close()
        state, _ready, _ok = loop.flush(state)
        dt = time.perf_counter() - t0
        return state_bytes(state), stats, loop, dt

    sync_bytes = run_sync(steps)  # also: compile warmup for all runs
    k1_bytes, _, _, _ = run_pipelined(steps, 1)
    _, feed_stats, loop_k, dt = run_pipelined(steps, aux_interval)

    loop_stats = loop_k.stats()
    report = {
        "steps": steps,
        "batch_images": batch_images,
        "aux_interval": aux_interval,
        "feed_depth": feed_depth,
        "feed": feed_stats,
        "loop": loop_stats,
        # every non-boundary step had >= 1 batch staged ahead iff no
        # post-first get ever blocked on the worker
        "min_staged_ahead": int(feed_stats["feed_starved_after_first"] == 0),
        # the sink only fetches inside flush(): any excess fetch over the
        # flush count would be a blocking fetch between flush points
        "interflush_blocking_fetches": max(
            0, loop_stats["fetches"] - loop_stats["flushes"]
        ),
        "k1_byte_identical": k1_bytes == sync_bytes,
        "imgs_per_sec": round(batch_images * steps / dt, 3),
    }
    return _pipeline_records(report), report


def _elastic_records(report: dict) -> list:
    """Elastic chaos report → JSON-line records (pure; the bench schema
    test builds a synthetic report and asserts the per-scenario
    zero-lost/bit-identical/recovery fields without running the matrix)."""
    def rec(metric, value, unit):
        return {"metric": metric, "value": value, "unit": unit,
                "vs_baseline": None}

    recs = [
        rec("elastic_devices", report["devices"], "replicas"),
        rec("elastic_steps", report["steps"], "steps"),
    ]
    for name, s in report["scenarios"].items():
        recs += [
            rec(f"elastic_{name}_zero_lost_steps",
                int(s["zero_lost_steps"]), "bool"),
            rec(f"elastic_{name}_bit_identical",
                int(s["bit_identical"]), "bool"),
            rec(f"elastic_{name}_recovery_s", s["recovery_s"], "seconds"),
            rec(f"elastic_{name}_final_replicas",
                s["final_replicas"], "replicas"),
        ]
    return recs


def bench_elastic(steps: int, batch_images: int) -> tuple:
    """Chaos matrix for elastic training on 8 virtual CPU devices.

    Four deterministic fault scenarios (``MX_RCNN_FAULTS`` device-phase
    injectors keyed step×replica — no sleeps-and-hope) over the same
    seeded batch stream and ONE pair of compiled executables (8-replica
    and 7-replica mesh, warmed before timing so ``recovery_s`` measures
    the drain/checkpoint/reshard path, as on a pod with a hot compile
    cache):

    - ``lose_1_of_8``: a replica dies mid-step and stays dead — the run
      shrinks to 7 and completes; its final state is compared BITWISE to
      a fresh 7-replica run restored from the emergency checkpoint and
      fed the remaining stream (the shrink-equivalence bar).
    - ``wedge``: a wedged (not dead) replica — same shrink mechanics;
      final state must equal the lose case bitwise (the loop cannot tell
      the difference, by design).
    - ``lose_then_regrow``: the wedge heals; at the next checkpoint
      boundary the mesh regrows to 8.  Run twice — recovery must be
      bit-reproducible end to end.
    - ``preempt_during_shrink``: the emergency save itself is killed
      mid-write (``save_crash``); the restarted run resumes from the
      last committed dump, hits the same fault, and must land on the
      lose case's exact bytes (resumed stream identical).

    Every scenario asserts zero lost steps beyond the pipeline window:
    each stream index's aux is delivered exactly once.
    """
    import os
    import tempfile

    import jax

    from mx_rcnn_tpu.core.checkpoint import (
        is_committed,
        load_restorable,
        save_checkpoint,
    )
    from mx_rcnn_tpu.core.resilience import host_copy
    from mx_rcnn_tpu.core.train import create_train_state, make_optimizer
    from mx_rcnn_tpu.data.loader import TrainLoader
    from mx_rcnn_tpu.models import build_model
    from mx_rcnn_tpu.parallel.elastic import ElasticLoop, make_elastic_factory
    from mx_rcnn_tpu.utils import faults
    from mx_rcnn_tpu.utils.load_data import load_gt_roidb

    base = 8
    if len(jax.devices()) < base:
        raise RuntimeError(
            f"elastic bench needs {base} devices, got {len(jax.devices())}"
        )
    if batch_images % base:
        raise ValueError("batch_images must divide by 8 replicas")
    fault_step, victim, wedge_dur = 3, 2, 2
    boundary_at = max(fault_step + wedge_dur + 1, steps - 2)
    survivors = tuple(o for o in range(base) if o != victim)

    cfg = _smoke_config(batch_images)
    _, roidb = load_gt_roidb(
        cfg, None, flip=False, synthetic_size=max(8, 2 * batch_images)
    )
    model = build_model(cfg)
    h, w = cfg.SHAPE_BUCKETS[0]
    params = model.init(
        {"params": jax.random.key(0), "sampling": jax.random.key(1)},
        images=np.zeros((1, h, w, 3), np.float32),
        im_info=np.array([[h, w, 1.0]], np.float32),
        gt_boxes=np.zeros((1, cfg.dataset.MAX_GT_BOXES, 5), np.float32),
        gt_valid=np.zeros((1, cfg.dataset.MAX_GT_BOXES), bool),
        train=True,
    )["params"]
    tx = make_optimizer(cfg, lambda s: cfg.TRAIN.LEARNING_RATE)
    host_params = host_copy(params)

    # the stream is precomputed so every scenario (and every fresh-mesh
    # equivalence run) consumes literally the same host arrays
    loader = TrainLoader(roidb, cfg, batch_images, shuffle=True, seed=0)
    batches = []
    while len(batches) < steps:
        for b in loader:
            batches.append(b)
            if len(batches) >= steps:
                break

    # one context per active set, shared across scenarios: the 7-mesh
    # executable compiles once, like a pod reusing its compile cache
    base_factory = make_elastic_factory(model, tx)
    ctx_cache: dict = {}

    def factory(active):
        key = tuple(active)
        if key not in ctx_cache:
            ctx_cache[key] = base_factory(key)
        return ctx_cache[key]

    rng = jax.random.key(0)

    def fresh_state():
        # host_copy, not device_get: donated steps would corrupt a CPU
        # zero-copy view of these buffers
        return host_copy(create_train_state(host_params, tx))

    def state_bytes(state):
        return b"".join(
            np.asarray(x).tobytes()
            for x in jax.tree_util.tree_leaves(jax.device_get(state))
        )

    compile_s = {}
    for act in (tuple(range(base)), survivors):
        ctx = factory(act)
        st = ctx.place_state(fresh_state())
        t0 = time.perf_counter()
        ctx.step_fn(st, ctx.place_batch(batches[0]), rng)
        compile_s[len(act)] = round(time.perf_counter() - t0, 3)

    def run(prefix, spec, *, resume=False, boundary=None, reset=True):
        os.environ[faults.ENV_VAR] = spec
        if reset:
            faults.reset()

        def ckpt_fn(host_state, idx, meta):
            return save_checkpoint(prefix, host_state, 0, idx, meta=meta)

        loop = ElasticLoop(factory, base, checkpoint_fn=ckpt_fn)
        state = fresh_state()
        start = 0
        if resume:
            got = load_restorable(prefix, state)
            assert got is not None, "restart found nothing restorable"
            (_epoch, start), state = got
            assert start == 0, "bench restart resumes the epoch head"
        state = loop.ctx.place_state(state)
        delivered = []
        t0 = time.perf_counter()
        for i in range(start, steps):
            state, ready, _ok = loop.step(state, batches[i], rng)
            delivered += [idx for idx, _aux in ready]
            if boundary is not None and i == boundary - 1:
                state, ready, _ok = loop.flush(state)
                delivered += [idx for idx, _aux in ready]
                save_checkpoint(prefix, host_copy(state), 1, 0)
                state, _regrown = loop.checkpoint_boundary(state)
        state, ready, _ok = loop.flush(state)
        delivered += [idx for idx, _aux in ready]
        wall = time.perf_counter() - t0
        return {
            "loop": loop,
            "bytes": state_bytes(state),
            "delivered": delivered,
            "wall_s": round(wall, 3),
        }

    def summarize(r, bit_identical):
        loop = r["loop"]
        uniq = set(r["delivered"])
        return {
            "final_replicas": len(loop.active),
            "shrinks": loop.monitor.shrinks,
            "regrows": loop.monitor.regrows,
            "emergency_checkpoints": len(loop.emergency_ckpts),
            "emergency_committed": all(
                is_committed(p) for p in loop.emergency_ckpts
            ),
            "replayed_steps": loop.replayed_steps,
            "lost_steps": steps - len(uniq),
            "duplicate_deliveries": len(r["delivered"]) - len(uniq),
            "zero_lost_steps": (
                sorted(uniq) == list(range(steps))
                and len(r["delivered"]) == steps
            ),
            "recovery_s": round(loop.recovery_s, 4),
            "wall_s": r["wall_s"],
            "bit_identical": bool(bit_identical),
            "transitions": loop.monitor.transitions,
        }

    scenarios = {}

    # -- lose 1 of 8, down forever ------------------------------------
    with tempfile.TemporaryDirectory() as td:
        r1 = run(td, f"device_lost@{fault_step}.{victim}")
        # fresh-mesh equivalence: restore the EMERGENCY checkpoint, build
        # a 7-replica substrate from scratch, feed the remaining stream
        got = load_restorable(td, fresh_state())
        assert got is not None, "emergency checkpoint not restorable"
        (_e, anchor), anchor_state = got
        ctx = factory(survivors)
        st = ctx.place_state(anchor_state)
        for i in range(anchor, steps):
            st, _aux = ctx.step_fn(st, ctx.place_batch(batches[i]), rng)
        scenarios["lose_1_of_8"] = summarize(
            r1, state_bytes(st) == r1["bytes"]
        )
        scenarios["lose_1_of_8"]["emergency_anchor_step"] = anchor

    # -- wedged replica (indistinguishable from lost, by design) ------
    with tempfile.TemporaryDirectory() as td:
        r2 = run(td, f"device_wedge@{fault_step}.{victim}:{steps}")
        scenarios["wedge"] = summarize(r2, r2["bytes"] == r1["bytes"])

    # -- wedge heals -> regrow at the checkpoint boundary; run twice ---
    spec3 = f"device_wedge@{fault_step}.{victim}:{wedge_dur}"
    with tempfile.TemporaryDirectory() as td:
        r3a = run(td, spec3, boundary=boundary_at)
    with tempfile.TemporaryDirectory() as td:
        r3b = run(td, spec3, boundary=boundary_at)
    scenarios["lose_then_regrow"] = summarize(
        r3a, r3a["bytes"] == r3b["bytes"]
    )

    # -- the emergency save itself is killed mid-write ----------------
    with tempfile.TemporaryDirectory() as td:
        save_checkpoint(td, fresh_state(), 0, 0)  # last committed dump
        spec4 = f"device_lost@{fault_step}.{victim},save_crash@1"
        os.environ[faults.ENV_VAR] = spec4
        faults.reset()
        crashed = False
        loop_x = ElasticLoop(
            factory, base,
            checkpoint_fn=lambda s, i, m: save_checkpoint(
                td, s, 0, i, meta=m
            ),
        )
        state = loop_x.ctx.place_state(fresh_state())
        try:
            for i in range(steps):
                state, _ready, _ok = loop_x.step(state, batches[i], rng)
        except faults.SimulatedCrash:
            crashed = True
        orphan = any(d.endswith(".tmp") for d in os.listdir(td))
        # restart in the same fault registry: save_crash@1 is consumed,
        # the device fault is still live — the resumed run re-hits it,
        # shrinks cleanly, and must land on the lose case's exact bytes
        r4 = run(td, spec4, resume=True, reset=False)
        s4 = summarize(r4, r4["bytes"] == r1["bytes"])
        s4["crashed_mid_shrink"] = crashed
        s4["orphan_tmp_left"] = orphan
        scenarios["preempt_during_shrink"] = s4

    os.environ.pop(faults.ENV_VAR, None)
    faults.reset()

    report = {
        "devices": base,
        "steps": steps,
        "batch_images": batch_images,
        "fault_step": fault_step,
        "victim": victim,
        "wedge_duration": wedge_dur,
        "boundary_at": boundary_at,
        "pipeline_window": 1,
        "compile_s": compile_s,
        "scenarios": scenarios,
    }
    return _elastic_records(report), report


# -------------------------------------------------- tenant-fair front door
class _ScalePool:
    """Signal-only pool stand-in for the trace-convergence legs: the
    autoscaler sees a replicas list and add/remove with the real
    copy-on-write contract, without paying replica threads for a
    decision-loop simulation."""

    def __init__(self, n: int):
        self.replicas = [object() for _ in range(n)]

    def add_replica(self):
        r = object()
        self.replicas = self.replicas + [r]
        return r

    def remove_replica(self, replica=None, timeout=5.0):
        if len(self.replicas) <= 1:
            return None
        victim = self.replicas[-1]
        self.replicas = self.replicas[:-1]
        return victim


def _drive_trace(scaler, depths, dt: float = 0.1):
    """Feed a queue-depth series through synchronous ticks (injected
    clock — wall time never enters the convergence legs)."""
    now = 1000.0
    for d in depths:
        scaler._signal_fn = lambda d=d: {
            "queue_depth": d,
            "healthy": len(scaler.pool.replicas),
            "p99_ms": None,
        }
        scaler.tick(now=now)
        now += dt


def bench_serve_scale(
    requests: int = 60,
    aggressor_factor: int = 4,
    service_ms: float = 3.0,
) -> tuple:
    """Tenant-fair front door bench (ISSUE 16 acceptance evidence).

    Four claims over the calibrated digest-stub runner family:

    1. ``tenant_isolation`` — the victim's p99 with an aggressor
       blasting at ``aggressor_factor``x its token-bucket rate stays
       within 10% (+2ms measurement floor) of the victim-solo run,
       because the excess is rejected at the door, never queued;
    2. ``zero_loss_shrink`` — an AUTOSCALER-initiated scale-down in the
       middle of live pool load completes every request with detections
       byte-identical to a fixed-size control run;
    3. ``no_flap`` — the controller converges on a diurnal trace with a
       bounded event count and zero flaps, and the breaker engages
       (flaps detected, events suppressed) on an adversarial
       oscillating trace;
    4. ``zero_steady_state_recompiles`` — compile misses across the
       shrink leg stay at warmup level for every pool size, and a
       scale-up costs exactly one ladder warmup, never more.
    """
    from mx_rcnn_tpu.serve.autoscaler import AutoScaler, ScalePolicy
    from mx_rcnn_tpu.serve.engine import ServingEngine
    from mx_rcnn_tpu.serve.loadgen import diurnal_arrivals
    from mx_rcnn_tpu.serve.router import ReplicaPool
    from mx_rcnn_tpu.serve.tenancy import TenantOverBudget, TenantTable

    tag = "cpu"
    service_s = service_ms / 1000.0

    def stub_factory(index: int):
        return _OverlapStubRunner(index, h2d_ms=0.0,
                                  device_ms=service_ms, fetch_ms=0.0)

    def images(n):
        return [
            np.random.RandomState(3000 + i).rand(24, 24, 3).astype(
                np.float32
            )
            for i in range(n)
        ]

    # ---- leg 1: aggressor/victim isolation -----------------------------
    def victim_run(with_aggressor: bool):
        tenants = TenantTable(strict=True)
        tenants.register("victim", weight=1.0)
        tenants.register("aggressor", weight=1.0, rate=50.0, burst=5.0)
        engine = ServingEngine(
            _OverlapStubRunner(0, h2d_ms=0.0, device_ms=service_ms,
                               fetch_ms=0.0),
            max_linger=0.0, max_queue=256, in_flight=1, tenants=tenants,
        )
        shed = 0
        lats_ms = []
        with engine:
            futs = []
            # warm phase (unmeasured): drain the aggressor's one-time
            # token-bucket burst so the measured window is the steady
            # state the isolation claim is about — the aggressor held
            # to its refill rate, the excess shed at the door
            for i, im in enumerate(images(8)):
                if with_aggressor:
                    for _ in range(aggressor_factor):
                        try:
                            futs.append(
                                engine.submit(im, tenant="aggressor",
                                              lane="bulk")
                            )
                        except TenantOverBudget:
                            shed += 1
                engine.submit(im, tenant="victim",
                              lane="interactive").result(timeout=30.0)
            for i, im in enumerate(images(requests)):
                if with_aggressor:
                    for _ in range(aggressor_factor):
                        try:
                            futs.append(
                                engine.submit(im, tenant="aggressor",
                                              lane="bulk")
                            )
                        except TenantOverBudget:
                            shed += 1
                t0 = time.monotonic()
                vf = engine.submit(im, tenant="victim",
                                   lane="interactive")
                vf.result(timeout=30.0)
                lats_ms.append((time.monotonic() - t0) * 1000.0)
            for f in futs:
                f.result(timeout=30.0)
        return _pctl_ms(lats_ms, 99), shed, engine.snapshot()

    solo_p99, _, _ = victim_run(with_aggressor=False)
    duo_p99, agg_shed, duo_snap = victim_run(with_aggressor=True)
    # the 10% bar plus one device-service quantum: the WFQ guarantees
    # at most one aggressor batch ahead of a victim release, and CPU
    # wall-clock needs a jitter floor on top of the ratio
    isolation_bar = 1.10 * solo_p99 + service_ms + 2.0
    tenant_isolation = bool(duo_p99 <= isolation_bar and agg_shed > 0)

    # ---- leg 2: autoscaler-initiated zero-loss scale-down --------------
    ims = images(requests)

    ladder_len = len(_OverlapStubRunner.LADDER)

    def pool_run(autoscale: bool):
        pool = ReplicaPool(stub_factory, 2)
        engine = ServingEngine(pool, max_linger=0.0, max_queue=256,
                               in_flight=1)
        try:
            with engine:
                futs = [engine.submit(im) for im in ims]
                scaler = None
                if autoscale:
                    # shrink-biased policy: the controller pulls the
                    # pool to min_replicas while the load is in flight
                    scaler = engine.attach_autoscaler(
                        policy=ScalePolicy(
                            min_replicas=1, max_replicas=2,
                            interval=0.005, samples=2, cooldown=0.0,
                            up_queue=1e9, down_queue=1e9,
                        )
                    )
                results = [f.result(timeout=60.0) for f in futs]
                # steady state at whatever size the pool landed on:
                # every surviving replica carries exactly its warmup
                # compiles, nothing from traffic
                extra = sum(
                    r.runner.compile_cache.misses - ladder_len
                    for r in pool.replicas
                )
                down_events = scaler.scale_downs if scaler else 0
                n_after = len(pool.replicas)
            snap = engine.snapshot()
        finally:
            pool.close()
        return results, snap, extra, down_events, n_after

    fixed_res, fixed_snap, fixed_extra, _, _ = pool_run(autoscale=False)
    (scaled_res, scaled_snap, scaled_extra,
     down_events, n_after) = pool_run(autoscale=True)
    identical = all(
        _dets_equal(a, b) for a, b in zip(fixed_res, scaled_res)
    )
    zero_loss = bool(
        identical
        and down_events >= 1
        and n_after == 1
        and scaled_snap["requests"]["completed"] == requests
        and scaled_snap["requests"]["failed"] == 0
    )
    # steady state must not compile at either pool size; a grow costs
    # exactly one ladder warmup
    shrink_recompiles = scaled_extra + fixed_extra
    pool2 = ReplicaPool(stub_factory, 1)
    try:
        pool2.warmup()
        grow_before = pool2.compile_cache.misses
        r = pool2.add_replica()
        t_end = time.monotonic() + 10.0
        while not r.routable and time.monotonic() < t_end:
            time.sleep(0.01)
        grow_delta = pool2.compile_cache.misses - grow_before
    finally:
        pool2.close()
    zero_recompiles = bool(
        shrink_recompiles == 0 and grow_delta == ladder_len
    )

    # ---- leg 3: trace convergence + breaker ----------------------------
    # diurnal day: arrivals binned to ticks -> queue-depth series
    arr = np.asarray(
        diurnal_arrivals(2000, lo_rps=4.0, hi_rps=40.0, seed=7)
    )
    bins = np.histogram(arr, bins=120)[0]  # ~arrivals per tick
    pol = ScalePolicy(min_replicas=1, max_replicas=4, samples=3,
                      up_queue=10.0, down_queue=2.0,
                      cooldown=0.5, flap_window=2.0, max_backoff=4.0)
    diurnal_pool = _ScalePool(1)
    diurnal_scaler = AutoScaler(diurnal_pool, policy=pol)
    _drive_trace(diurnal_scaler, bins.tolist(), dt=0.5)
    diurnal_events = diurnal_scaler.scale_ups + diurnal_scaler.scale_downs
    diurnal_flaps = diurnal_scaler.breaker.flaps

    osc_pool = _ScalePool(2)
    osc_scaler = AutoScaler(osc_pool, policy=ScalePolicy(
        min_replicas=1, max_replicas=4, samples=2,
        cooldown=0.5, flap_window=100.0, max_backoff=4.0,
    ))
    osc = ([100.0] * 3 + [0.0] * 3) * 10  # adversarial square wave
    _drive_trace(osc_scaler, osc, dt=0.1)
    osc_events = osc_scaler.scale_ups + osc_scaler.scale_downs
    osc_snap = osc_scaler.snapshot()["breaker"]
    no_flap = bool(
        diurnal_flaps == 0
        and 2 <= diurnal_events <= 10
        and osc_events <= 6
        and osc_snap["flaps"] >= 1
        and osc_snap["suppressed"] >= 5
    )

    records = [
        {"metric": f"serve_scale_victim_solo_p99_ms_{tag}",
         "value": solo_p99, "unit": "ms"},
        {"metric": f"serve_scale_victim_contended_p99_ms_{tag}",
         "value": duo_p99, "unit": "ms"},
        {"metric": f"serve_scale_aggressor_shed_{tag}",
         "value": agg_shed, "unit": "requests"},
        {"metric": f"serve_scale_shrink_lost_requests_{tag}",
         "value": requests - scaled_snap["requests"]["completed"],
         "unit": "requests"},
        {"metric": f"serve_scale_shrink_scale_downs_{tag}",
         "value": down_events, "unit": "events"},
        {"metric": f"serve_scale_detections_match_{tag}",
         "value": 1 if identical else 0, "unit": "bool"},
        {"metric": f"serve_scale_shrink_recompiles_{tag}",
         "value": shrink_recompiles, "unit": "compiles"},
        {"metric": f"serve_scale_grow_warmup_compiles_{tag}",
         "value": grow_delta, "unit": "compiles"},
        {"metric": f"serve_scale_diurnal_events_{tag}",
         "value": diurnal_events, "unit": "events"},
        {"metric": f"serve_scale_diurnal_flaps_{tag}",
         "value": diurnal_flaps, "unit": "flaps"},
        {"metric": f"serve_scale_oscillating_events_{tag}",
         "value": osc_events, "unit": "events"},
        {"metric": f"serve_scale_oscillating_suppressed_{tag}",
         "value": osc_snap["suppressed"], "unit": "ticks"},
    ]
    report = {
        "requests": requests,
        "aggressor_factor": aggressor_factor,
        "service_ms": service_ms,
        "isolation_bar_ms": round(isolation_bar, 3),
        "victim": {"solo_p99_ms": solo_p99, "contended_p99_ms": duo_p99},
        "aggressor": duo_snap["tenants"]["aggressor"],
        "tenancy": duo_snap["tenancy"],
        "shrink": {
            "scale_downs": down_events,
            "replicas_after": n_after,
            "completed": scaled_snap["requests"]["completed"],
            "autoscaler": scaled_snap.get("autoscaler"),
        },
        "diurnal": {"events": diurnal_events, "flaps": diurnal_flaps,
                    "replicas_final": len(diurnal_pool.replicas)},
        "oscillating": {"events": osc_events, "breaker": osc_snap},
        "claims": {
            "tenant_isolation": tenant_isolation,
            "zero_loss_shrink": zero_loss,
            "no_flap": no_flap,
            "zero_steady_state_recompiles": zero_recompiles,
        },
    }
    return records, report


def _cascade_tiny_cfg(network: str):
    """One-bucket config for the per-rung parity matrix — the smallest
    geometry each real model compiles AND executes at, so six warmups
    (2 families x 3 precisions) stay CPU-tractable.  The mask-FPN
    family takes 96x96: its batch-1 64x64 serve graph trips a oneDNN
    convolution-primitive crash on this host, 96x96 does not."""
    from mx_rcnn_tpu.config import generate_config

    cfg = generate_config(network, "PascalVOC")
    bucket = (96, 96) if cfg.network.USE_MASK else (64, 64)
    net_over = {"FIXED_PARAMS": ()}
    if not cfg.network.USE_FPN:
        net_over["ANCHOR_SCALES"] = (2, 4, 8)
    if cfg.network.depth > 50 and cfg.network.name == "resnet":
        net_over["depth"] = 50
    test_over = {
        "RPN_PRE_NMS_TOP_N": 100,
        "RPN_POST_NMS_TOP_N": 16,
        "SCORE_THRESH": 0.05,
    }
    if cfg.network.USE_MASK:
        test_over.update(DET_PER_CLASS=8, MAX_PER_IMAGE=8)
    return cfg.replace(
        SHAPE_BUCKETS=(bucket,),
        network=dataclasses.replace(cfg.network, **net_over),
        dataset=dataclasses.replace(
            cfg.dataset, NUM_CLASSES=4, SCALES=((bucket[0] - 16, bucket[0]),)
        ),
        TEST=dataclasses.replace(cfg.TEST, **test_over),
    )


def _cascade_rung_matrix() -> tuple:
    """Per-rung parity matrix: {box, mask} x {f32, bf16, int8} on REAL
    tiny models.  bf16/int8 warmups run the f32 detection-parity gate
    (mask parity included for the mask family) and raise on drift, so
    every row returned here passed the same gate serving would; f32
    rows are the reference rung (trivially ok, nothing to check).
    Also proves zero post-warmup compile-miss growth per rung."""
    import jax

    from mx_rcnn_tpu.core.quantize import quantization_stats
    from mx_rcnn_tpu.models import build_model
    from mx_rcnn_tpu.serve.loadgen import synthetic_image
    from mx_rcnn_tpu.serve.registry import ModelRegistry
    from mx_rcnn_tpu.serve.runner import ServeRunner

    matrix = []
    compression = {}
    steady_misses = 0
    for family, network in (("box", "resnet50"),
                            ("mask", "mask_resnet_fpn")):
        cfg = _cascade_tiny_cfg(network)
        h, w = cfg.SHAPE_BUCKETS[0]
        model = build_model(cfg)
        params = model.init(
            {"params": jax.random.key(0)},
            np.zeros((1, h, w, 3), np.float32),
            np.array([[h, w, 1.0]], np.float32),
            train=False,
        )["params"]
        # keep the raw random init: its saturated scores rank proposals
        # with wide margins, so the parity gate measures numeric drift,
        # not NMS tie-flips between near-equal scores
        registry = ModelRegistry()
        registry.register(family, model, cfg, params)
        im = synthetic_image(17, h - 8, w, seed=4)
        for precision in ("f32", "bfloat16", "int8"):
            runner = ServeRunner(
                registry=registry, max_batch=1,
                precision=precision,
            )
            runner.warmup()
            tag = runner._precision_for(family)
            row = {"family": family, "precision": tag}
            report = runner.parity.get(f"{family}:{tag}")
            if report is None:  # the f32 reference rung
                row.update(ok=True, checked=False)
            else:
                row.update(
                    ok=bool(report["ok"]), checked=bool(report["checked"]),
                    max_box_delta_px=report["max_box_delta_px"],
                    max_score_delta=report["max_score_delta"],
                    unmatched_confident=report["unmatched_confident"],
                )
            # post-warmup serving must not add a single jit signature
            misses0 = runner.compile_cache.misses
            runner.run(runner.assemble([runner.make_request(im)]))
            steady_misses += runner.compile_cache.misses - misses0
            matrix.append(row)
            if tag == "int8":
                compression[family] = quantization_stats(
                    registry.live(family).params,
                    registry.quantized_tree(family),
                )
    return matrix, compression, steady_misses


def bench_cascade(requests: int = 80, hard_pct: float = 30.0) -> tuple:
    """Compression ladder + confidence-gated cascade (ISSUE 18).

    Two legs:

    1. **threshold sweep** — a two-family registry (cheap/flagship)
       behind the REAL engine + cascade router, with a stub predict
       whose per-batch device cost is MODELED (booked into the
       runner's ``device_ms_by_model`` counters, no sleeps): cheap 15
       ms/image, flagship 60 ms/image.  ``hard_pct`` of images are
       "hard": the cheap family answers them wrong and scores them low
       (0.3 vs 0.9), the flagship always answers right.  Sweeping the
       escalation threshold traces the cost-per-image vs accuracy
       curve: never-escalate (cheapest, wrong on hard images),
       escalate-on-doubt (matched accuracy at a fraction of the cost —
       THE claim), and 100% escalation (the byte-identity control arm
       vs flagship-only serving).

    2. **per-rung parity matrix** — {box, mask} x {f32, bf16, int8} on
       real tiny models: every reduced-precision rung passes the same
       f32 detection/mask-parity gate serving enforces, int8
       compression is ~4x, and no rung adds a post-warmup compile.
    """
    from mx_rcnn_tpu.serve.batcher import Request
    from mx_rcnn_tpu.serve.buckets import BucketLadder, CompileCache
    from mx_rcnn_tpu.serve.engine import ServingEngine
    from mx_rcnn_tpu.serve.registry import ModelRegistry

    CHEAP_MS, FLAG_MS = 15.0, 60.0

    class _CascadeStubRunner:
        """Registry-backed stub: detections are a pure function of the
        image index (encoded in the corner pixel) and the family;
        device cost per batch is booked, not slept."""

        def __init__(self, registry):
            self.registry = registry
            self.default_model = registry.default_model
            self.ladder = BucketLadder(((32, 32),))
            self.max_batch = 1  # exact per-request cost attribution
            self.cfg = None
            self.compile_cache = CompileCache()
            self.device_ms_total = 0.0
            self.device_ms_by_model = {}

        def warmup(self) -> int:
            for mid in self.registry.model_ids():
                self.compile_cache.record((mid, (1, 32, 32, 3), "f32"))
            return self.compile_cache.misses

        def make_request(self, im, deadline=None, model=None) -> Request:
            h, w = im.shape[:2]
            bh, bw = self.ladder.select(h, w)
            canvas = np.zeros((bh, bw, 3), np.float32)
            canvas[:h, :w] = im
            return Request(
                image=canvas,
                im_info=np.array([h, w, 1.0], np.float32),
                orig_hw=(h, w),
                bucket=(bh, bw),
                deadline=deadline,
                model=model,
            )

        def assemble(self, requests_):
            return {"images": np.stack([r.image for r in requests_])}

        def run(self, batch, model=None):
            mid = model or self.default_model
            self.compile_cache.record(
                (mid, batch["images"].shape, "f32")
            )
            cost = (CHEAP_MS if mid == "cheap" else FLAG_MS) * len(
                batch["images"]
            )
            self.device_ms_total += cost
            self.device_ms_by_model[mid] = (
                self.device_ms_by_model.get(mid, 0.0) + cost
            )
            # the image index rides the corner pixel (see _image)
            idx = np.round(batch["images"][:, 0, 0, 0]).astype(int)
            return {"idx": idx, "mid": mid}

        def detections_for(self, out, batch, index, orig_hw=None,
                           thresh=None, model=None):
            i = int(out["idx"][index])
            hard = _is_hard(i)
            gt_x = float(5 + (i % 13))
            if out["mid"] == "flag":
                x, score = gt_x, 0.95
            else:
                x = gt_x + (20.0 if hard else 0.0)  # wrong box when hard
                score = 0.3 if hard else 0.9
            return [
                None,
                np.array([[x, 2.0, x + 10.0, 12.0, score]], np.float32),
            ]

    def _is_hard(i: int) -> bool:
        return (i % 100) < hard_pct

    def _image(i: int) -> np.ndarray:
        im = np.full((24, 24, 3), 0.5, np.float32)
        im[0, 0, 0] = float(i)  # index channel the stub decodes
        return im

    def _accuracy(dets_list) -> float:
        good = 0
        for i, dets in enumerate(dets_list):
            gt_x = float(5 + (i % 13))
            good += int(abs(float(dets[1][0, 0]) - gt_x) < 1.0)
        return good / len(dets_list)

    def _run_leg(min_score):
        reg = ModelRegistry()
        reg.register("cheap", model=None, cfg=None, params={"w": 1})
        reg.register("flag", model=None, cfg=None, params={"w": 2})
        runner = _CascadeStubRunner(reg)
        eng = ServingEngine(runner, max_linger=0.0, max_queue=256)
        with eng:
            if min_score is not None:
                eng.attach_cascade({
                    "cheap": "cheap", "flagship": "flag",
                    "min_score": min_score,
                })
            warm_misses = runner.compile_cache.misses
            dets = [eng.submit(_image(i), model="flag").result(30)
                    for i in range(requests)]
            snap = eng.snapshot()
        casc = snap.get("cascade", {})
        return {
            "min_score": min_score,
            "accuracy": round(_accuracy(dets), 4),
            "cost_ms_per_image": round(
                runner.device_ms_total / requests, 3
            ),
            "device_ms_by_model": {
                k: round(v, 1)
                for k, v in runner.device_ms_by_model.items()
            },
            "escalations": casc.get("escalations", 0),
            "escalation_rate": casc.get("escalation_rate", 0.0),
            "first_pass_sufficient": casc.get("first_pass_sufficient", 0),
            "steady_state_compile_misses":
                runner.compile_cache.misses - warm_misses,
            "completed": snap["requests"]["completed"],
        }, [d[1].tobytes() for d in dets]

    flagship_only, base_bytes = _run_leg(None)
    sweep = []
    full_bytes = None
    for thresh in (0.0, 0.6, 1.01):
        leg, leg_bytes = _run_leg(thresh)
        sweep.append(leg)
        if thresh == 1.01:
            full_bytes = leg_bytes
    # best rung: cheapest sweep point within 1% of flagship accuracy
    matched = [s for s in sweep
               if s["accuracy"] >= flagship_only["accuracy"] - 0.01]
    best = min(matched, key=lambda s: s["cost_ms_per_image"])
    cost_reduction = round(
        flagship_only["cost_ms_per_image"] / best["cost_ms_per_image"], 2
    )
    zero_recompiles = (
        flagship_only["steady_state_compile_misses"] == 0
        and all(s["steady_state_compile_misses"] == 0 for s in sweep)
    )

    matrix, compression, rung_misses = _cascade_rung_matrix()
    int8_rows = [r for r in matrix if r["precision"] == "int8"]
    bf16_rows = [r for r in matrix if r["precision"] == "bf16"]
    claims = {
        "cost_reduction_ge_1p3x_at_matched_accuracy": bool(
            cost_reduction >= 1.3
        ),
        "full_escalation_byte_identical": bool(full_bytes == base_bytes),
        "zero_steady_state_recompiles": bool(
            zero_recompiles and rung_misses == 0
        ),
        "int8_parity_ok_box_and_mask": bool(
            len(int8_rows) == 2
            and all(r["ok"] and r["checked"] for r in int8_rows)
        ),
        "bf16_parity_ok_box_and_mask": bool(
            len(bf16_rows) == 2
            and all(r["ok"] and r["checked"] for r in bf16_rows)
        ),
    }
    report = {
        "claims": claims,
        "config": {
            "requests": requests,
            "hard_pct": hard_pct,
            "cheap_ms_per_image": CHEAP_MS,
            "flagship_ms_per_image": FLAG_MS,
        },
        "flagship_only": flagship_only,
        "sweep": sweep,
        "best": dict(best, cost_reduction_x=cost_reduction),
        "parity_matrix": matrix,
        "int8_compression": compression,
    }
    records = [
        {"metric": "serve_cascade_cost_ms_per_image_flagship_only",
         "value": flagship_only["cost_ms_per_image"], "unit": "ms",
         "vs_baseline": None},
        {"metric": "serve_cascade_cost_ms_per_image_matched",
         "value": best["cost_ms_per_image"], "unit": "ms",
         "vs_baseline": None},
        {"metric": "serve_cascade_cost_reduction_x",
         "value": cost_reduction, "unit": "x", "vs_baseline": None},
        {"metric": "serve_cascade_accuracy_flagship_only",
         "value": flagship_only["accuracy"], "unit": "fraction",
         "vs_baseline": None},
        {"metric": "serve_cascade_accuracy_matched",
         "value": best["accuracy"], "unit": "fraction",
         "vs_baseline": None},
        {"metric": "serve_cascade_escalation_rate_matched",
         "value": best["escalation_rate"], "unit": "fraction",
         "vs_baseline": None},
        {"metric": "serve_cascade_parity_rungs_ok",
         "value": sum(int(r["ok"]) for r in matrix), "unit": "rungs",
         "vs_baseline": None},
        {"metric": "serve_cascade_int8_compression_x_box",
         "value": compression["box"]["compression_x"], "unit": "x",
         "vs_baseline": None},
        {"metric": "serve_cascade_int8_compression_x_mask",
         "value": compression["mask"]["compression_x"], "unit": "x",
         "vs_baseline": None},
        {"metric": "serve_cascade_steady_state_compile_misses",
         "value": (flagship_only["steady_state_compile_misses"]
                   + sum(s["steady_state_compile_misses"] for s in sweep)
                   + rung_misses),
         "unit": "compiles", "vs_baseline": None},
    ]
    return records, report


# ------------------------------------------------------------- streaming
# chaos matrix for the streaming ordering bench (ISSUE 20): a mid-run
# predict failure (the failed batch's frames requeue off the tripped
# replica while LATER frames of the same streams are already dispatched
# elsewhere) and a stall long enough to fire the hedge — the two seams
# where a frame's result can come back out of stream order without the
# settlement gate.
_STREAM_FAULT_SCENARIOS = {
    "healthy": "",
    # unbounded fail on replica 0's batch 3: in-dispatch retries
    # exhaust, the replica trips and the batch REQUEUES onto a sibling
    # while later frames of the same streams keep dispatching — the
    # ISSUE 20 mid-stream-requeue chaos case
    "replica_trip": "predict_fail@0.3",
    # 1.5 s stall on replica 0's batch 5: past the hedge timeout
    # (0.75 s) so the duplicate dispatch wins, far under the stall
    # watchdog so nothing trips — the hedge-win ordering case
    "stall_hedge": "predict_stall@0.5:1.5",
}

# calibrated-stub priming budgets, smallest first — the sweep must show
# recall monotone in budget (latency is monotone by construction)
_PRIMING_BUDGETS = (25, 50, 100, 200, 400)


def _paste_stub_outputs(seed: int, rois_n: int, num_classes: int,
                        mask_size: int, hc: int, wc: int):
    """Flagship-shaped stub head outputs for the paste comparison.

    No backbone: the host-paste-vs-device-paste question is entirely a
    property of the fused postprocess program plus survivor geometry,
    so the stub fabricates the head tensors the program consumes —
    large instances (the workload where paste cost dominates; small
    boxes make BOTH paths RLE-bound) with mixed class scores so a
    realistic survivor population clears NMS."""
    rng = np.random.RandomState(seed)
    x1 = rng.uniform(0, wc * 0.25, rois_n).astype(np.float32)
    y1 = rng.uniform(0, hc * 0.25, rois_n).astype(np.float32)
    x2 = np.minimum(
        x1 + rng.uniform(wc * 0.5, wc * 0.75, rois_n), wc - 1.0
    ).astype(np.float32)
    y2 = np.minimum(
        y1 + rng.uniform(hc * 0.5, hc * 0.75, rois_n), hc - 1.0
    ).astype(np.float32)
    rois = np.stack([x1, y1, x2, y2], axis=1)
    cls_prob = rng.dirichlet(
        np.full(num_classes, 0.3), size=rois_n
    ).astype(np.float32)
    deltas = np.zeros((rois_n, 4 * num_classes), np.float32)
    logits = rng.uniform(
        -4.0, 4.0, (rois_n, mask_size, mask_size, num_classes)
    ).astype(np.float32)
    return {
        "rois": rois[None],
        "roi_valid": np.ones((1, rois_n), np.float32),
        "cls_prob": cls_prob[None],
        "bbox_deltas": deltas[None],
        "mask_logits": logits[None],
    }


def _stream_paste_stub(frames: int = 5, rois_n: int = 192,
                       max_det: int = 32, canvas_hw=(608, 800)) -> dict:
    """Calibrated-stub paste comparison at mask-flagship geometry.

    Runs the REAL fused postprocess program (``make_test_postprocess``)
    twice over identical stub head tensors — once with ``paste=True``
    (device canvas, host keeps only RLE) and once without (host runs
    the numpy fixed-point paste) — at flagship shapes (K=21, S=28,
    ~600×800 canvas, ``max_det`` survivors).  Per frame it measures the
    HOST wall time of the paste+RLE stage on each path and checks every
    survivor's RLE for byte identity; both jits must hold at one cached
    executable across all frames (zero steady-state recompiles)."""
    import dataclasses as _dc

    import jax

    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.eval.segm import paste_mask_canvas
    from mx_rcnn_tpu.native import rle as rle_mod
    from mx_rcnn_tpu.ops.postprocess import make_test_postprocess

    cfg = generate_config("mask_resnet_fpn", "PascalVOC")
    cfg = cfg.replace(TEST=_dc.replace(cfg.TEST, MAX_PER_IMAGE=max_det))
    num_classes = 21
    mask_size = cfg.TRAIN.MASK_SIZE
    hc, wc = canvas_hw
    max_out = 100
    pp_paste = make_test_postprocess(
        cfg, num_classes, thresh=0.05, max_out=max_out, paste=True
    )
    pp_host = make_test_postprocess(
        cfg, num_classes, thresh=0.05, max_out=max_out, paste=False
    )
    im_info = np.array([[hc, wc, 1.0]], np.float32)
    orig_hw = np.array([[hc, wc]], np.float32)
    dev_fn = jax.jit(
        lambda out, info, ohw: pp_paste(out, info, ohw, (hc, wc))
    )
    host_fn = jax.jit(pp_host)

    # The RLE encode stage is COMMON to both paths (same canvases in,
    # same counts out — that is the byte-identity bar) and unchanged by
    # this PR, so the paste-stage and RLE-stage walls are timed
    # separately: the reduction claim is about the paste stage the PR
    # moves on device ("the host keeps only RLE"); the total window is
    # reported alongside for the end-to-end picture.
    dev_paste_ms, host_paste_ms = [], []
    dev_rle_ms, host_rle_ms, dets_per_frame = [], [], []
    identical = True
    for f in range(frames):
        out = _paste_stub_outputs(f, rois_n, num_classes, mask_size, hc, wc)
        outd = jax.tree_util.tree_map(
            np.asarray, dev_fn(out, im_info, orig_hw)
        )
        outh = jax.tree_util.tree_map(
            np.asarray, host_fn(out, im_info, orig_hw)
        )
        midx = outd["det_mask_idx"][0]
        boxes = outd["det_boxes"][0]          # (K-1, max_out, 4)
        survivors = [
            (p, int(fl)) for p, fl in enumerate(midx) if fl >= 0
        ]
        dets_per_frame.append(len(survivors))

        # device leg paste stage: the canvases were pasted in the jit —
        # the remaining host-side work is materializing each survivor's
        # canvas slice for the encoder
        canvas = outd["det_canvas"][0]
        t0 = time.monotonic()
        dev_canvases = [
            np.ascontiguousarray(canvas[p]) for p, _fl in survivors
        ]
        dev_paste_ms.append((time.monotonic() - t0) * 1000.0)
        t0 = time.monotonic()
        dev_rles = [rle_mod.encode(cv) for cv in dev_canvases]
        dev_rle_ms.append((time.monotonic() - t0) * 1000.0)

        # host leg paste stage: numpy fixed-point paste per survivor
        grids = outh["det_masks"][0]
        t0 = time.monotonic()
        host_canvases = [
            paste_mask_canvas(grids[p], boxes[fl // max_out, fl % max_out],
                              hc, wc)                         # scale = 1.0
            for p, fl in survivors
        ]
        host_paste_ms.append((time.monotonic() - t0) * 1000.0)
        t0 = time.monotonic()
        host_rles = [rle_mod.encode(cv) for cv in host_canvases]
        host_rle_ms.append((time.monotonic() - t0) * 1000.0)

        identical &= len(dev_rles) == len(host_rles) and all(
            a["size"] == b["size"] and a["counts"] == b["counts"]
            for a, b in zip(dev_rles, host_rles)
        )

    # first frame pays lazy native-lib / allocator warmup on both
    # paths; the steady-state claim is the per-frame cost after it
    def _steady(xs):
        return float(np.mean(xs[1:])) if frames > 1 else xs[0]

    dev_paste = _steady(dev_paste_ms)
    host_paste = _steady(host_paste_ms)
    dev_total = dev_paste + _steady(dev_rle_ms)
    host_total = host_paste + _steady(host_rle_ms)
    return {
        "canvas_hw": [hc, wc],
        "mask_size": mask_size,
        "num_classes": num_classes,
        "rois": rois_n,
        "max_det": max_det,
        "frames": frames,
        "survivors_per_frame": dets_per_frame,
        "device_paste_ms_per_frame": round(dev_paste, 3),
        "host_paste_ms_per_frame": round(host_paste, 3),
        "reduction_x": round(host_paste / max(dev_paste, 1e-9), 2),
        "device_total_ms_per_frame": round(dev_total, 3),
        "host_total_ms_per_frame": round(host_total, 3),
        "total_reduction_x": round(host_total / max(dev_total, 1e-9), 2),
        "rle_byte_identical": bool(identical),
        "device_jit_executables": int(dev_fn._cache_size()),
        "host_jit_executables": int(host_fn._cache_size()),
    }


def _stub_rpn_proposals(rec: dict, rng, n: int = 400) -> np.ndarray:
    """Deliberately weak RPN stub for the priming sweep: per gt box a
    handful of jittered candidates buried among uniform-random boxes
    with overlapping score ranges, so small budgets genuinely miss
    objects — the regime where a frame-(N−1) seed can help."""
    h, w = float(rec["height"]), float(rec["width"])
    gts = np.asarray(rec["boxes"], np.float32)
    cand, scores = [], []
    for g in gts:
        bw, bh = g[2] - g[0] + 1.0, g[3] - g[1] + 1.0
        for _ in range(4):
            jit = rng.normal(0.0, 0.3, 4) * np.array([bw, bh, bw, bh])
            b = g + jit.astype(np.float32)
            cand.append([
                np.clip(b[0], 0, w - 1), np.clip(b[1], 0, h - 1),
                np.clip(b[2], 0, w - 1), np.clip(b[3], 0, h - 1),
            ])
            scores.append(rng.uniform(0.2, 0.9))
    n_rand = max(n - len(cand), 0)
    x1 = rng.uniform(0, w * 0.8, n_rand)
    y1 = rng.uniform(0, h * 0.8, n_rand)
    x2 = np.minimum(x1 + rng.uniform(20, w * 0.5, n_rand), w - 1)
    y2 = np.minimum(y1 + rng.uniform(20, h * 0.5, n_rand), h - 1)
    for i in range(n_rand):
        cand.append([x1[i], y1[i], x2[i], y2[i]])
        scores.append(rng.uniform(0.0, 0.7))
    props = np.concatenate(
        [np.asarray(cand, np.float32),
         np.asarray(scores, np.float32)[:, None]], axis=1
    )
    return props[np.argsort(-props[:, 4], kind="stable")]


def _proposal_stage_ms(budget: int, reps: int = 15) -> float:
    """Measured second-stage cost model for the priming latency axis: a
    (budget, 256)×(256, 256) feature transform plus a score sort — the
    per-proposal work whose linear scaling is what the budget buys
    back.  A calibrated stub (real measured wall, stub computation):
    the tradeoff table needs relative latencies, not absolute ones.
    Min-of-reps: at small budgets one timing is overhead-dominated and
    a scheduler hiccup can invert the budget ordering."""
    rng = np.random.RandomState(0)
    feats = rng.rand(budget, 256).astype(np.float32)
    w = rng.rand(256, 256).astype(np.float32)
    t = []
    for _ in range(reps + 1):
        t0 = time.monotonic()
        s = feats @ w
        np.argsort(-s[:, 0], kind="stable")
        t.append((time.monotonic() - t0) * 1000.0)
    return float(np.min(t[1:]))  # first rep pays allocator warmup


def _priming_sweep(num_streams: int = 3, frames: int = 12) -> dict:
    """Temporal proposal priming sweep over deterministic moving scenes
    (``data/synthetic.py::moving_scene``): frame N's proposal pool is
    the weak RPN stub either alone (unprimed) or seeded with frame
    N−1's detections (``serve/streams.py::prime_proposals``), recall
    via ``eval/recall.py::proposal_recall`` at each budget.  The
    simulated frame-(N−1) detector output is the previous gt lightly
    jittered with one stochastic miss — an imperfect tracker, not an
    oracle.  Frame 0 of each stream has no previous frame and is
    excluded (both arms would be identical there)."""
    from mx_rcnn_tpu.data.synthetic import moving_scene
    from mx_rcnn_tpu.eval.recall import proposal_recall
    from mx_rcnn_tpu.serve.streams import prime_proposals

    roidb, raw_props, prev_dets = [], [], []
    for s in range(num_streams):
        recs = moving_scene(1000 + s, frames, image_size=(480, 640),
                            num_objects=4)
        rng = np.random.RandomState(7000 + s)
        for f, rec in enumerate(recs):
            if f == 0:
                continue
            roidb.append(rec)
            raw_props.append(_stub_rpn_proposals(rec, rng))
            prev = np.asarray(recs[f - 1]["boxes"], np.float32)
            keep = rng.rand(len(prev)) > 0.15      # tracker misses ~15%
            jit = rng.normal(0.0, 2.0, prev.shape).astype(np.float32)
            prev_dets.append((prev + jit)[keep])

    table = []
    for budget in _PRIMING_BUDGETS:
        unprimed = [p[:budget] for p in raw_props]
        primed = [
            prime_proposals(p, d, budget)
            for p, d in zip(raw_props, prev_dets)
        ]
        r_un = proposal_recall(unprimed, roidb, top_ns=(budget,))
        r_pr = proposal_recall(primed, roidb, top_ns=(budget,))
        table.append({
            "budget": budget,
            "latency_ms": round(_proposal_stage_ms(budget), 4),
            "recall_unprimed": round(r_un[f"recall@{budget}"], 4),
            "recall_primed": round(r_pr[f"recall@{budget}"], 4),
        })

    def _monotone(key):
        vals = [row[key] for row in table]
        return all(b >= a - 1e-9 for a, b in zip(vals, vals[1:]))

    return {
        "streams": num_streams,
        "frames_per_stream": frames,
        "evaluated_frames": len(roidb),
        "table": table,
        "monotone_recall_unprimed": _monotone("recall_unprimed"),
        "monotone_recall_primed": _monotone("recall_primed"),
        "monotone_latency": _monotone("latency_ms"),
        "primed_never_worse": all(
            row["recall_primed"] >= row["recall_unprimed"] - 1e-9
            for row in table
        ),
    }


def bench_streaming(
    network: str = "resnet50",
    num_streams: int = 3,
    frames_per_stream: int = 8,
    max_batch: int = 2,
    linger_ms: float = 5.0,
) -> tuple:
    """Streaming-serve bench (ISSUE 20 acceptance evidence).

    Four phases:

    1. **paste stub** — the fused postprocess program at mask-flagship
       geometry over stub head tensors: device-canvas vs host-paste
       host ms/frame, RLE byte identity, one jit executable per path.
    2. **mask streaming serve** — the small mask family with
       ``MASK_CANVAS`` on, served as ordered streams through a
       2-replica pool with a blocking hot-swap fired mid-load; a
       host-paste comparator runner (same model/params, canvas off)
       pins RLE byte identity and the real-model paste-ms ratio.
       Zero steady-state recompiles through warmup + swap + load.
    3. **chaos ordering** — the box family on a 3-replica pool under
       ``_STREAM_FAULT_SCENARIOS``; every scenario must deliver every
       stream in frame order with zero lost frames, and ok-frame
       detections must be byte-identical to the healthy run.
    4. **priming sweep** — the train-free recall/latency tradeoff
       table (monotone in budget, primed never worse).
    """
    import os
    import tempfile

    import jax

    from mx_rcnn_tpu.core.checkpoint import save_checkpoint
    from mx_rcnn_tpu.models import build_model
    from mx_rcnn_tpu.serve.engine import ServingEngine
    from mx_rcnn_tpu.serve.loadgen import run_stream_load
    from mx_rcnn_tpu.serve.registry import ModelRegistry
    from mx_rcnn_tpu.serve.replica import HealthPolicy
    from mx_rcnn_tpu.serve.router import ReplicaPool, make_replica_factory
    from mx_rcnn_tpu.serve.runner import ServeRunner
    from mx_rcnn_tpu.utils import faults

    # ---------------------------------------- phase 1: paste stub
    stub = _stream_paste_stub()

    # ---------------------------------------- phase 2: mask streaming
    cfg = _mask_serve_cfg()
    cfg = cfg.replace(TEST=dataclasses.replace(cfg.TEST, MASK_CANVAS=True))
    sizes = ((72, 96), (96, 128))
    model = build_model(cfg)
    h0, w0 = cfg.SHAPE_BUCKETS[0]

    def init_params(seed):
        p = model.init(
            {"params": jax.random.key(seed)},
            np.zeros((1, h0, w0, 3), np.float32),
            np.array([[h0, w0, 1.0]], np.float32),
            train=False,
        )["params"]

        def _damp(path, leaf):
            name = "/".join(str(getattr(q, "key", q)) for q in path)
            for frag in ("rpn_cls_score", "rpn_bbox_pred", "cls_score",
                         "bbox_pred", "mask_logits"):
                if frag in name:
                    return leaf * 1e-2
            return leaf

        return jax.tree_util.tree_map_with_path(_damp, p)

    params = init_params(0)
    ckpt_v2 = save_checkpoint(
        os.path.join(tempfile.mkdtemp(prefix="bench-streaming-"), "v2"),
        {"params": init_params(1)}, 1,
    )
    registry = ModelRegistry()
    registry.register("masks", model, cfg, params)
    factory = make_replica_factory(
        lambda registry, device: ServeRunner(
            registry=registry, device=device, max_batch=max_batch,
        ),
        registry=registry,
    )
    pool = ReplicaPool(factory, n_replicas=2, inflight_depth=2)
    rungs = pool.warmup()

    # host-paste comparator: same model/params/cfg, canvas OFF — the
    # pre-ISSUE-20 mask serving path (grids fetched, numpy paste)
    host = ServeRunner(
        model, params, cfg, max_batch=max_batch,
        mask_canvas=False,
    )
    host.warmup()
    dev = pool.replicas[0].runner
    parity = []
    parity_ok = True
    from mx_rcnn_tpu.serve.loadgen import synthetic_image
    for i, (ih, iw) in enumerate(sizes):
        im = synthetic_image(i, ih, iw, seed=0)
        dreq = dev.make_request(im, model="masks")
        hreq = host.make_request(im)
        dout = dev.run(dev.assemble([dreq]), model="masks")
        hout = host.run(host.assemble([hreq]))
        d_dets, d_rles = dev.mask_rles_for(
            dout, {"im_info": [dreq.im_info],
                   "images": np.zeros((1,) + dreq.bucket + (3,))},
            0, orig_hw=(ih, iw), model="masks",
        )
        h_dets, h_rles = host.mask_rles_for(
            hout, {"im_info": [hreq.im_info],
                   "images": np.zeros((1,) + hreq.bucket + (3,))},
            0, orig_hw=(ih, iw),
        )
        eq = _rles_equal(d_rles, h_rles)
        parity_ok &= eq
        parity.append({
            "size": [ih, iw], "bucket": list(dreq.bucket),
            "detections": int(sum(
                len(d) for d in d_dets[1:] if d is not None
            )),
            "rles_byte_identical": eq,
        })
    model_reduction = (
        (host.paste_ms_total / max(host.pastes, 1))
        / max(dev.paste_ms_total / max(dev.pastes, 1), 1e-9)
    )

    swap_out = {}
    eng = ServingEngine(pool, max_linger=linger_ms / 1000.0, in_flight=2)
    with eng:
        base_done = eng.metrics.completed

        def fire_swap():
            t_end = time.time() + 120.0
            while (eng.metrics.completed - base_done < 4
                   and time.time() < t_end):
                time.sleep(0.01)
            try:
                swap_out["result"] = repr(eng.swap(
                    "masks", ckpt_v2, block=True, timeout=300
                ))
            except Exception as e:  # noqa: BLE001 — recorded as evidence
                swap_out["error"] = repr(e)

        swapper = threading.Thread(target=fire_swap, daemon=True)
        swapper.start()
        mask_rep = run_stream_load(
            eng, num_streams=num_streams,
            frames_per_stream=frames_per_stream, fps=2.0, sizes=sizes,
            seed=3, model="masks", masks=True, collect=False,
        )
        swapper.join(timeout=300)
    mask_snap = pool.snapshot()
    pool.close()
    steady_misses = mask_snap["compile"]["misses"] - rungs
    swap_landed = "result" in swap_out and "error" not in swap_out
    eng_snap = mask_rep["engine"]

    # ---------------------------------------- phase 3: chaos ordering
    _, _, _, box_sizes, box_factory = _serve_model(
        network, True, max_batch
    )
    # generous watchdog: CPU oversubscription (3 resnet replicas plus
    # the injected stall) must not cascade into watchdog trips — the
    # only trips in this matrix are the ones the fault spec asks for
    policy = HealthPolicy(stall_timeout=30.0, breaker_backoff=0.25,
                          breaker_max_backoff=4.0)
    scenarios = {}
    healthy_ok = None
    prior = os.environ.get(faults.ENV_VAR)
    try:
        for name, spec in _STREAM_FAULT_SCENARIOS.items():
            if spec:
                os.environ[faults.ENV_VAR] = spec
            else:
                os.environ.pop(faults.ENV_VAR, None)
            faults.reset()
            cpool = ReplicaPool(box_factory, n_replicas=3, policy=policy,
                                hedge_timeout=0.75)
            cengine = ServingEngine(
                cpool, max_linger=linger_ms / 1000.0, in_flight=3
            )
            with cengine:
                rep = run_stream_load(
                    cengine, num_streams=4, frames_per_stream=8,
                    fps=4.0, sizes=box_sizes, seed=0, collect=True,
                )
            cpool.close()
            results = rep.pop("_results")
            rep.pop("_completion_seq", None)
            ok = {k: r for k, (kind, r) in results.items() if kind == "ok"}
            if name == "healthy":
                healthy_ok = ok
                identical = True
            else:
                identical = all(
                    _dets_equal(healthy_ok[k], ok[k])
                    for k in ok if k in healthy_ok
                )
            scenarios[name] = {
                "spec": spec,
                "in_order": rep["in_order"],
                "lost_frames": rep["lost_frames"],
                "outcomes": rep["outcomes"],
                "detections_match_healthy": identical,
                "streams": rep["engine"].get("streams"),
                "stream_reinserts":
                    rep["engine"]["scheduler"].get("stream_reinserts"),
            }
    finally:
        if prior is None:
            os.environ.pop(faults.ENV_VAR, None)
        else:
            os.environ[faults.ENV_VAR] = prior
        faults.reset()

    chaos_in_order = all(s["in_order"] for s in scenarios.values())
    chaos_lost = sum(s["lost_frames"] for s in scenarios.values())
    chaos_identical = all(
        s["detections_match_healthy"] for s in scenarios.values()
    )

    # ---------------------------------------- phase 4: priming sweep
    priming = _priming_sweep()

    claims = {
        "paste_rle_byte_identical": bool(
            stub["rle_byte_identical"] and parity_ok
        ),
        "paste_reduction_ge_5x": bool(stub["reduction_x"] >= 5.0),
        "zero_steady_state_recompiles": bool(
            steady_misses == 0 and swap_landed
            and stub["device_jit_executables"] == 1
            and stub["host_jit_executables"] == 1
        ),
        "stream_in_order_under_chaos": bool(
            chaos_in_order and chaos_lost == 0
        ),
        "chaos_bytes_identical": bool(chaos_identical),
        "priming_monotone_tradeoff": bool(
            priming["monotone_recall_primed"]
            and priming["monotone_recall_unprimed"]
            and priming["monotone_latency"]
            and priming["primed_never_worse"]
        ),
    }
    report = {
        "claims": claims,
        "paste": {
            "stub": stub,
            "model_parity": parity,
            "model_reduction_x": round(model_reduction, 2),
            "engine_paste": eng_snap.get("paste"),
            "pool_paste_ms": mask_snap["overlap"].get("paste_ms"),
            "pool_paste_bytes": mask_snap["overlap"].get("paste_bytes"),
        },
        "mask_stream": {
            "in_order": mask_rep["in_order"],
            "lost_frames": mask_rep["lost_frames"],
            "outcomes": mask_rep["outcomes"],
            "frames_per_sec": mask_rep["frames_per_sec"],
            "streams": eng_snap.get("streams"),
            "swap": swap_out,
            "steady_state_compile_misses": steady_misses,
            "ladder_rungs": rungs,
        },
        "chaos": scenarios,
        "priming": priming,
    }
    records = [
        {"metric": "streaming_paste_host_ms_per_frame",
         "value": stub["host_paste_ms_per_frame"], "unit": "ms",
         "vs_baseline": None},
        {"metric": "streaming_paste_device_ms_per_frame",
         "value": stub["device_paste_ms_per_frame"], "unit": "ms",
         "vs_baseline": None},
        {"metric": "streaming_paste_reduction_x",
         "value": stub["reduction_x"], "unit": "x", "vs_baseline": None},
        {"metric": "streaming_paste_rle_byte_identical",
         "value": 1.0 if claims["paste_rle_byte_identical"] else 0.0,
         "unit": "bool", "vs_baseline": None},
        {"metric": "streaming_steady_state_compile_misses",
         "value": steady_misses, "unit": "compiles", "vs_baseline": None},
        {"metric": "streaming_chaos_lost_frames",
         "value": chaos_lost, "unit": "frames", "vs_baseline": None},
        {"metric": "streaming_chaos_in_order",
         "value": 1.0 if chaos_in_order else 0.0, "unit": "bool",
         "vs_baseline": None},
        {"metric": "streaming_mask_frames_per_sec",
         "value": mask_rep["frames_per_sec"], "unit": "frames/sec",
         "vs_baseline": None},
        {"metric": "streaming_priming_recall_gain_at_50",
         "value": round(
             priming["table"][1]["recall_primed"]
             - priming["table"][1]["recall_unprimed"], 4
         ),
         "unit": "recall", "vs_baseline": None},
    ]
    return records, report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--network", default="resnet",
        choices=sorted(_METRIC_NAMES),
    )
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument(
        "--steps_per_call", type=int, default=1,
        help="K train steps per dispatch (device-side lax.scan loop)",
    )
    ap.add_argument(
        "--all", action="store_true",
        help="bench every family; one JSON line each",
    )
    ap.add_argument(
        "--serve", action="store_true",
        help="bench the online serving engine instead of training",
    )
    # defaults chosen to SATURATE the engine (concurrency > in_flight *
    # max_batch, linger visible next to CPU service times) so the
    # occupancy number is a statement about the batcher, not the load
    ap.add_argument("--serve_requests", type=int, default=64)
    ap.add_argument("--serve_concurrency", type=int, default=16)
    ap.add_argument("--serve_max_batch", type=int, default=4)
    ap.add_argument("--serve_linger_ms", type=float, default=25.0)
    ap.add_argument("--serve_replicas", type=int, default=1,
                    help="replica-pool size for --serve (1 = the "
                         "no-regression case) / --serve_fault (min 3)")
    ap.add_argument("--inflight_depth", type=int, default=2,
                    help="per-replica in-flight dispatch window for "
                         "--serve (1 = the serial path; results are "
                         "byte-identical at any depth)")
    ap.add_argument(
        "--serve_overlap", action="store_true",
        help="overlapped-serving bench on a calibrated stub device "
             "stall: depth=1 vs depth=2 throughput + byte-identity, "
             "device-busy fraction, and the depth=2 fault matrix "
             "(zero lost, zero steady-state recompiles)",
    )
    ap.add_argument("--overlap_device_ms", type=float, default=60.0,
                    help="stub device compute per batch for "
                         "--serve_overlap")
    ap.add_argument("--overlap_fetch_ms", type=float, default=25.0,
                    help="stub D2H fetch + host postprocess per batch "
                         "for --serve_overlap")
    ap.add_argument(
        "--serve_fleet", action="store_true",
        help="multi-host fleet bench (ISSUE 19): wire-protocol gateway "
             "over N backend engine processes — N=1 byte-identity vs "
             "the direct engine, weak-scaling imgs/s at 1/2/4 backends, "
             "and a SIGKILL chaos phase (zero lost requests, surviving "
             "responses byte-identical to an unfaulted run)",
    )
    ap.add_argument("--fleet_requests", type=int, default=120,
                    help="requests PER BACKEND for --serve_fleet")
    ap.add_argument("--fleet_concurrency", type=int, default=32,
                    help="client concurrency per backend for "
                         "--serve_fleet")
    ap.add_argument("--fleet_service_ms", type=float, default=50.0,
                    help="stub backend device stall per batch for "
                         "--serve_fleet")
    ap.add_argument(
        "--serve_scale", action="store_true",
        help="tenant-fair front door bench (ISSUE 16): aggressor/victim "
             "isolation under a 4x rate-limit blast, autoscaler-"
             "initiated zero-loss scale-down (byte-identical to a "
             "fixed-size control), diurnal/oscillating trace "
             "convergence through the flap breaker, and zero steady-"
             "state recompiles at every pool size",
    )
    ap.add_argument(
        "--serve_mask", action="store_true",
        help="mask-family serving bench (ISSUE 14): device-side mask "
             "selection vs the raw-head path — per-batch fetch bytes "
             "before/after, RLE byte-identity across every bucket and "
             "padding config, p50/p99 through the replica pool, and "
             "zero steady-state recompiles",
    )
    ap.add_argument(
        "--streaming", action="store_true",
        help="streaming-serve bench (ISSUE 20): device-side mask paste "
             "vs host paste (ms/frame + RLE byte-identity at flagship "
             "geometry on the calibrated stub), per-stream in-order "
             "completion under the chaos matrix with a mid-load hot-"
             "swap, and the temporal-priming recall/latency sweep",
    )
    ap.add_argument("--stream_count", type=int, default=3,
                    help="streams in the mask streaming leg")
    ap.add_argument("--stream_frames", type=int, default=8,
                    help="frames per stream in the mask streaming leg")
    ap.add_argument(
        "--cascade", action="store_true",
        help="compression ladder + confidence-gated cascade bench "
             "(ISSUE 18): escalation-threshold sweep tracing cost-per-"
             "image vs accuracy on a modeled two-family registry "
             "(matched-accuracy cost reduction + 100%%-escalation "
             "byte-identity), plus the {box,mask} x {f32,bf16,int8} "
             "parity matrix on real tiny models",
    )
    ap.add_argument("--cascade_requests", type=int, default=80)
    ap.add_argument("--cascade_hard_pct", type=float, default=30.0,
                    help="percent of images the cheap family answers "
                         "wrong (and scores low) in --cascade")
    ap.add_argument(
        "--serve_fault", action="store_true",
        help="fault-matrix serving bench: healthy vs wedged vs flapping "
             "replica scenarios on a >=3-replica pool (zero-lost + "
             "byte-identical + recovery-time evidence)",
    )
    ap.add_argument(
        "--poison", action="store_true",
        help="query-of-death containment bench: ~5%% deterministic "
             "poison inside healthy traffic on a 2-replica pool with "
             "quarantine on (zero healthy losses, byte-identical "
             "healthy detections, <=K trips per poison digest, all "
             "replicas healthy at the end)",
    )
    ap.add_argument("--poison_k", type=int, default=2,
                    help="quarantine trip threshold K for --poison")
    ap.add_argument(
        "--slo", action="store_true",
        help="SLO-tier serving bench: sparse interactive probes vs a "
             "saturating bulk backlog, single-lane baseline vs two-lane "
             "(interactive p99 + bulk-throughput retention + zero "
             "recompiles), plus response-cache byte-identity and the "
             "bf16 serve-graph parity gate",
    )
    ap.add_argument("--slo_probes", type=int, default=5)
    ap.add_argument("--slo_probe_spacing", type=float, default=10.0)
    ap.add_argument("--slo_bulk_concurrency", type=int, default=32)
    ap.add_argument(
        "--swap", action="store_true",
        help="model-lifecycle serving bench: live hot-swap under load "
             "(zero lost, byte-identical outside the swap window, zero "
             "recompiles), verify/warm/canary rollback matrix, and "
             "two-family tenancy through one batcher",
    )
    ap.add_argument(
        "--rollout", action="store_true",
        help="progressive-rollout bench (ISSUE 17): traffic-split canary "
             "promote under load (zero lost, zero recompiles), shadow-mode "
             "divergence auto-rollback with a byte-identical incumbent, "
             "and the closed serve->distill->fine-tune->promote loop",
    )
    ap.add_argument("--distill_steps", type=int, default=2,
                    help="fine-tune steps for the closed-loop scenario")
    ap.add_argument(
        "--serve_full", action="store_true",
        help="serve at the full config (default: tiny CPU-runnable one)",
    )
    ap.add_argument(
        "--pipeline", action="store_true",
        help="bench the device-resident step pipeline (feed occupancy, "
             "fetch stalls, K=1 byte-identical check) on the CPU smoke "
             "config",
    )
    ap.add_argument(
        "--eval", dest="eval_plane", action="store_true",
        help="bench the eval host data plane (parallel assembly + "
             "prepared cache + completion pool) around a stub device at "
             "flagship image size; serial vs overlapped, bitwise check",
    )
    ap.add_argument("--eval_images", type=int, default=64)
    ap.add_argument("--eval_batch", type=int, default=8)
    ap.add_argument("--stub_device_ms", type=float, default=110.0,
                    help="stub device stall per batch (110 ms = the "
                         "73 img/s device ceiling at b8, ROOFLINE r5)")
    ap.add_argument("--assembly_workers", type=int, default=2)
    ap.add_argument("--postprocess_workers", type=int, default=2)
    ap.add_argument("--prepared_cache", type=int, default=128)
    ap.add_argument("--pipeline_steps", type=int, default=16)
    ap.add_argument("--aux_interval", type=int, default=4,
                    help="K: train aux fetched every K steps")
    ap.add_argument("--feed_depth", type=int, default=2,
                    help="device-feed double-buffer depth")
    ap.add_argument("--pipeline_batch", type=int, default=2)
    ap.add_argument(
        "--elastic", action="store_true",
        help="chaos matrix for elastic training on 8 virtual CPU devices "
             "(lose-1-of-8 / wedge / lose-then-regrow / preempt-during-"
             "shrink; zero-lost + bitwise shrink-equivalence + recovery "
             "seconds)",
    )
    ap.add_argument("--elastic_steps", type=int, default=8)
    ap.add_argument("--elastic_batch", type=int, default=8,
                    help="global batch for --elastic (must divide by 8)")
    ap.add_argument(
        "--out", default=None,
        help="also write the records as a JSON array artifact",
    )
    args = ap.parse_args()

    from mx_rcnn_tpu.utils.platform import enable_compile_cache

    if args.elastic:
        # env-only, and BEFORE enable_compile_cache touches jax: the 8
        # virtual devices must exist at backend init, and the compile
        # cache subdir is keyed on the XLA_FLAGS this sets
        from mx_rcnn_tpu.utils.platform import set_cpu_platform

        set_cpu_platform(8)

    enable_compile_cache()

    if args.elastic:
        records, report = bench_elastic(args.elastic_steps,
                                        args.elastic_batch)
        for rec in records:
            print(json.dumps(rec), flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"records": records, "report": report}, f, indent=1)
        return

    if args.eval_plane:
        from mx_rcnn_tpu.tools.bench_eval import data_plane_report

        report = data_plane_report(
            images=args.eval_images,
            batch=args.eval_batch,
            stub_device_ms=args.stub_device_ms,
            assembly_workers=args.assembly_workers,
            postprocess_workers=args.postprocess_workers,
            prepared_cache=args.prepared_cache,
        )
        records = _eval_records(report)
        for rec in records:
            print(json.dumps(rec), flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"records": records, "report": report}, f, indent=1)
        return

    if args.pipeline:
        records, report = bench_pipeline(
            args.pipeline_steps, args.aux_interval, args.feed_depth,
            args.pipeline_batch,
        )
        for rec in records:
            print(json.dumps(rec), flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"records": records, "report": report}, f, indent=1)
        return

    if args.slo:
        network = "resnet50" if args.network == "resnet" else args.network
        records, report = bench_serve_slo(
            network, probes=args.slo_probes,
            probe_spacing_s=args.slo_probe_spacing,
            bulk_concurrency=args.slo_bulk_concurrency,
            max_batch=args.serve_max_batch // 2 or 1,
        )
        for rec in records:
            print(json.dumps(rec), flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"records": records, "report": report}, f, indent=1)
        return

    if args.rollout:
        network = "resnet50" if args.network == "resnet" else args.network
        records, report = bench_rollout(
            network, args.serve_requests, args.serve_concurrency,
            args.serve_max_batch, args.serve_linger_ms,
            small=not args.serve_full, distill_steps=args.distill_steps,
        )
        for rec in records:
            print(json.dumps(rec), flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"records": records, "report": report}, f, indent=1)
        return

    if args.swap:
        network = "resnet50" if args.network == "resnet" else args.network
        records, report = bench_swap(
            network, args.serve_requests, args.serve_concurrency,
            args.serve_max_batch, args.serve_linger_ms,
            small=not args.serve_full, replicas=args.serve_replicas,
        )
        for rec in records:
            print(json.dumps(rec), flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"records": records, "report": report}, f, indent=1)
        return

    if args.poison:
        network = "resnet50" if args.network == "resnet" else args.network
        records, report = bench_poison(
            network, args.serve_requests, args.serve_concurrency,
            args.serve_max_batch, args.serve_linger_ms,
            replicas=max(2, args.serve_replicas), k=args.poison_k,
            small=not args.serve_full,
        )
        for rec in records:
            print(json.dumps(rec), flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"records": records, "report": report}, f, indent=1)
        return

    if args.serve_overlap:
        records, report = bench_serve_overlap(
            requests=args.serve_requests,
            concurrency=args.serve_concurrency // 2 or 8,
            device_ms=args.overlap_device_ms,
            fetch_ms=args.overlap_fetch_ms,
        )
        for rec in records:
            print(json.dumps(rec), flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"records": records, "report": report}, f, indent=1)
        return

    if args.cascade:
        records, report = bench_cascade(
            requests=args.cascade_requests,
            hard_pct=args.cascade_hard_pct,
        )
        for rec in records:
            print(json.dumps(rec), flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"records": records, "report": report}, f, indent=1)
        return

    if args.serve_fleet:
        records, report = bench_serve_fleet(
            requests_per_backend=args.fleet_requests,
            concurrency_per_backend=args.fleet_concurrency,
            service_ms=args.fleet_service_ms,
        )
        for rec in records:
            print(json.dumps(rec), flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"records": records, "report": report}, f, indent=1)
        return

    if args.serve_scale:
        records, report = bench_serve_scale(
            requests=args.serve_requests,
        )
        for rec in records:
            print(json.dumps(rec), flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"records": records, "report": report}, f, indent=1)
        return

    if args.streaming:
        network = "resnet50" if args.network == "resnet" else args.network
        records, report = bench_streaming(
            network, num_streams=args.stream_count,
            frames_per_stream=args.stream_frames,
            max_batch=args.serve_max_batch // 2 or 1,
            linger_ms=args.serve_linger_ms,
        )
        for rec in records:
            print(json.dumps(rec), flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"records": records, "report": report}, f, indent=1)
        return

    if args.serve_mask:
        records, report = bench_serve_mask(
            args.serve_requests, args.serve_concurrency,
            args.serve_max_batch, args.serve_linger_ms,
            replicas=args.serve_replicas,
            inflight_depth=args.inflight_depth,
        )
        for rec in records:
            print(json.dumps(rec), flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"records": records, "report": report}, f, indent=1)
        return

    if args.serve_fault:
        network = "resnet50" if args.network == "resnet" else args.network
        records, report = bench_serve_fault(
            network, args.serve_requests, args.serve_concurrency,
            args.serve_max_batch, args.serve_linger_ms,
            replicas=args.serve_replicas, small=not args.serve_full,
        )
        for rec in records:
            print(json.dumps(rec), flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"records": records, "report": report}, f, indent=1)
        return

    if args.serve:
        network = "resnet50" if args.network == "resnet" else args.network
        records, report = bench_serve(
            network, args.serve_requests, args.serve_concurrency,
            args.serve_max_batch, args.serve_linger_ms,
            small=not args.serve_full, replicas=args.serve_replicas,
            inflight_depth=args.inflight_depth,
        )
        for rec in records:
            print(json.dumps(rec), flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"records": records, "report": report}, f, indent=1)
        return

    families = _ALL_FAMILIES if args.all else (args.network,)
    records = []
    for network in families:
        rec = bench_one(network, args.batch, args.iters, args.steps_per_call)
        records.append(rec)
        print(json.dumps(rec), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)


if __name__ == "__main__":
    main()
