"""Device-facing serving tests: runner, engine end-to-end, and the
padding-invariance guarantee (bucketed == unbucketed: same kept set,
coordinates to the last ulps).

One tiny module-scoped model; every forward in this file uses batch
``MAX_BATCH`` (the runner pads all batches to it), so the whole module
compiles exactly ``len(buckets)`` XLA programs — asserted via the
runner's CompileCache, which is the same mechanism the production
engine uses to prove zero recompiles after warmup.

NOTE the invariance comparisons hold the BATCH SIZE fixed: XLA CPU's
conv algorithm choice differs across batch sizes (~1e-3, see
test_eval.py); at fixed batch the convolution differs across canvas
sizes only by reduction order (last ulps) — which is exactly the
serving situation (one padded batch size per bucket).
"""

import dataclasses
import time

import jax
import numpy as np
import pytest

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.models import build_model
from mx_rcnn_tpu.serve.buckets import BucketLadder, BucketOverflow
from mx_rcnn_tpu.serve.engine import DeadlineExceeded, ServingEngine
from mx_rcnn_tpu.serve.runner import ServeRunner, prepare_request

MAX_BATCH = 2
BUCKETS = ((64, 64), (96, 96))


def _tiny_cfg():
    cfg = generate_config("resnet50", "PascalVOC")
    return cfg.replace(
        SHAPE_BUCKETS=BUCKETS,
        network=dataclasses.replace(
            cfg.network, ANCHOR_SCALES=(2, 4, 8), FIXED_PARAMS=()
        ),
        dataset=dataclasses.replace(
            cfg.dataset, NUM_CLASSES=4, SCALES=((64, 96),)
        ),
        TEST=dataclasses.replace(
            cfg.TEST,
            RPN_PRE_NMS_TOP_N=100,
            RPN_POST_NMS_TOP_N=16,
            SCORE_THRESH=0.05,
        ),
    )


def _init(cfg):
    model = build_model(cfg)
    h, w = cfg.SHAPE_BUCKETS[0]
    params = model.init(
        {"params": jax.random.key(0)},
        np.zeros((1, h, w, 3), np.float32),
        np.array([[h, w, 1.0]], np.float32),
        train=False,
    )["params"]
    return model, params


@pytest.fixture(scope="module")
def box_env():
    cfg = _tiny_cfg()
    model, params = _init(cfg)
    return {"cfg": cfg, "model": model, "params": params}


@pytest.fixture(scope="module")
def runner(box_env):
    r = ServeRunner(box_env["model"], box_env["params"], box_env["cfg"],
                    max_batch=MAX_BATCH)
    assert r.warmup() == len(BUCKETS)
    return r


def _image(seed: int, h: int = 64, w: int = 64) -> np.ndarray:
    return np.random.RandomState(seed).randint(
        0, 256, (h, w, 3)
    ).astype(np.float32)


def _dets_equal(a, b) -> bool:
    return len(a) == len(b) and all(
        (x is None and y is None) or np.array_equal(x, y)
        for x, y in zip(a, b)
    )


class TestServeRunner:
    def test_warmup_covers_ladder_then_zero_misses(self, runner):
        assert runner.compile_cache.misses == len(BUCKETS)
        out = runner.run(runner.assemble([runner.make_request(_image(0))]))
        assert "det_boxes" in out  # device postprocess active
        assert runner.compile_cache.misses == len(BUCKETS)  # no new compile

    def test_oversize_rejected_not_compiled(self, runner):
        # resized long side caps at 96 (SCALES), so only an absurd ladder
        # miss can overflow — force it with a one-rung ladder
        with pytest.raises(BucketOverflow):
            prepare_request(_image(0, 64, 64), runner.cfg,
                            BucketLadder([(32, 32)]))
        assert runner.compile_cache.misses == len(BUCKETS)

    def test_padding_invariance_across_buckets_exact(self, runner):
        """THE serving correctness property: the same image produces the
        same detections whether it pads into its exact-fit bucket or a
        strictly larger one (same batch size).  Four mechanisms compose:
        anchor-grid mask + valid_hw roi clamp (no padded anchors / no
        clip-to-canvas sampling), the pad-re-zeroing mask before every
        spatial op (frozen BN repaints padding with its bias, which edge
        convs would otherwise read), and the ladder-wide feature pad
        (one second-stage program for all buckets).

        What is EXACT: the kept set (per class, same count and order)
        across buckets, and every byte within a bucket (a repeated run).
        Box coordinates and scores across buckets agree to BOX_ATOL px /
        SCORE_ATOL: XLA:CPU picks its conv reduction order per canvas
        shape, so the valid pixels' features differ in the last ulp."""
        BOX_ATOL, SCORE_ATOL = 1e-3, 1e-5
        im = _image(1, 64, 64)  # resizes 1:1 → exact fit in (64, 64)
        per_bucket = []
        for bucket in BUCKETS:
            reqs = [
                prepare_request(im, runner.cfg, BucketLadder([bucket]))
                for _ in range(MAX_BATCH)
            ]
            assert reqs[0].bucket == bucket
            batch = runner.assemble(reqs)
            out = runner.run(batch)
            dets = [runner.detections_for(out, batch, k)
                    for k in range(MAX_BATCH)]
            again = runner.run(runner.assemble(reqs))
            for k in range(MAX_BATCH):
                assert _dets_equal(
                    dets[k], runner.detections_for(again, batch, k)
                ), f"bucket {bucket} slot {k}: repeated run not bitwise"
            per_bucket.append(dets)
        tight, padded = per_bucket
        n_dets = sum(len(d) for d in tight[0][1:])
        assert n_dets > 0  # the comparison below must compare real boxes
        for k in range(MAX_BATCH):
            assert len(tight[k]) == len(padded[k])
            for j in range(1, len(tight[k])):
                t, p = tight[k][j], padded[k][j]
                assert t.shape == p.shape, (
                    f"slot {k} class {j}: kept set differs between "
                    f"exact-fit {BUCKETS[0]} and padded {BUCKETS[1]} "
                    f"canvases ({len(t)} vs {len(p)} detections)"
                )
                np.testing.assert_allclose(
                    t[:, :4], p[:, :4], rtol=0, atol=BOX_ATOL
                )
                np.testing.assert_allclose(
                    t[:, 4], p[:, 4], rtol=0, atol=SCORE_ATOL
                )

    def test_layout_feed_stages_every_batch(self, runner):
        """The layout-matched feed (on by default on the TPU, forced on
        here): warmup captures each rung's compiled input formats and
        every batch after — the warm run included — is staged into them.
        It had died silently when ``Compiled.input_layouts`` was renamed
        and a blanket ``except`` hid the AttributeError (ISSUE 21)."""
        fed = ServeRunner(registry=runner.registry, max_batch=MAX_BATCH,
                          layout_feed=True, ladder=BucketLadder(BUCKETS[:1]))
        assert fed.warmup() == 1
        batch = fed.assemble([fed.make_request(_image(0))])
        out = fed.run(batch)
        assert fed.staged_batches == fed.layout_staged == 2
        plain = runner.run(runner.assemble([runner.make_request(_image(0))]))
        for key in ("det_boxes", "det_scores", "det_valid"):
            assert np.array_equal(np.asarray(out[key]), np.asarray(plain[key]))

    def test_detect_single_path_matches_engine_path(self, runner):
        """demo/eval and the engine share one predict path — same image,
        same runner, byte-identical output through either entry."""
        im = _image(2, 48, 80)
        direct = runner.detect(im)
        with ServingEngine(runner, max_linger=0.0) as eng:
            served = eng.submit(im).result(timeout=120)
        assert _dets_equal(direct, served)


# ------------------------------------------------------------- mask family
def _mask_cfg():
    """Tiny mask-FPN serving config (ISSUE 14), same ladder as the box
    module above so the bucket matrix is comparable."""
    cfg = generate_config("mask_resnet_fpn", "PascalVOC")
    return cfg.replace(
        SHAPE_BUCKETS=BUCKETS,
        network=dataclasses.replace(
            cfg.network, depth=50, FIXED_PARAMS=()
        ),
        dataset=dataclasses.replace(
            cfg.dataset, NUM_CLASSES=4, SCALES=((64, 96),)
        ),
        TEST=dataclasses.replace(
            cfg.TEST,
            RPN_PRE_NMS_TOP_N=100,
            RPN_POST_NMS_TOP_N=16,
            DET_PER_CLASS=8,
            MAX_PER_IMAGE=8,
            SCORE_THRESH=0.05,
        ),
    )


def _damped(params):
    """De-saturate the score/delta/mask heads: at random init the
    softmax scores every roi at EXACTLY 1.0, so host-vs-device keep
    order on those exact float ties is undefined and parity would
    measure tie-break luck."""
    def damp(path, leaf):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        if any(f in name for f in ("rpn_cls_score", "rpn_bbox_pred",
                                   "cls_score", "bbox_pred",
                                   "mask_logits")):
            return leaf * 1e-2
        return leaf

    return jax.tree_util.tree_map_with_path(damp, params)


@pytest.fixture(scope="module")
def mask_env():
    from mx_rcnn_tpu.serve.registry import ModelRegistry

    cfg = _mask_cfg()
    model, raw_params = _init(cfg)
    params = _damped(raw_params)
    registry = ModelRegistry()
    registry.register("masks", model, cfg, params)
    dev = ServeRunner(registry=registry, max_batch=MAX_BATCH)
    assert dev.warmup() == len(BUCKETS)
    raw = ServeRunner(model, params, cfg, max_batch=MAX_BATCH, device_postprocess=False)
    return {"cfg": cfg, "model": model, "params": params,
            "raw_params": raw_params,
            "registry": registry, "dev": dev, "raw": raw}


class TestDeviceMaskServing:
    """ISSUE 14 serving matrix: device-selected ``det_masks`` must
    reproduce the host raw-head path's RLEs byte-for-byte across every
    bucket and padding config, through the split dispatch/complete
    window, and through a live hot-swap."""

    def _rles(self, runner, out, req):
        from mx_rcnn_tpu.eval.segm import rles_for_detections

        h, w = req.orig_hw
        cls_dets, mask_probs = runner.detections_for(
            out, {"im_info": [req.im_info]}, 0, orig_hw=(h, w),
            with_masks=True,
        )
        return cls_dets, {
            j: rles_for_detections(mask_probs[j], cls_dets[j], h, w)
            for j in range(1, len(cls_dets))
        }

    def test_rle_byte_identity_across_buckets_and_fetch_reduction(
        self, mask_env
    ):
        dev, raw, cfg = mask_env["dev"], mask_env["raw"], mask_env["cfg"]
        im = _image(1, 64, 64)  # resizes 1:1 → exact fit in (64, 64)
        dev_masks_per_bucket = []
        for bucket in BUCKETS:
            dreq = prepare_request(im, cfg, BucketLadder([bucket]))
            rreq = prepare_request(im, cfg, BucketLadder([bucket]))
            assert dreq.bucket == bucket
            dout = dev.run(dev.assemble([dreq]))
            rout = raw.run(raw.assemble([rreq]))
            # the device path never ships the raw stack; the raw path
            # has no selected grids
            assert "det_masks" in dout and "mask_logits" not in dout
            assert "mask_logits" in rout and "det_masks" not in rout
            # the selected-grid fetch must be the small one (ISSUE 14
            # acceptance asks >= 5x; this geometry gives far more)
            assert dev.last_fetch_bytes * 5 <= raw.last_fetch_bytes
            d_dets, d_rles = self._rles(dev, dout, dreq)
            r_dets, r_rles = self._rles(raw, rout, rreq)
            assert sum(len(d) for d in r_dets[1:]) > 0
            for j in range(1, len(d_dets)):
                assert len(d_dets[j]) == len(r_dets[j]), f"cls {j}"
                if len(d_dets[j]):
                    assert (d_dets[j][:, 4].tobytes()
                            == r_dets[j][:, 4].tobytes())
                assert (
                    [(r["size"], r["counts"]) for r in d_rles[j]]
                    == [(r["size"], r["counts"]) for r in r_rles[j]]
                ), f"bucket {bucket} cls {j}: RLE bytes differ"
            dev_masks_per_bucket.append(np.asarray(dout["det_masks"]))
        # padding tolerance: the mask-FPN forward itself is only
        # ulp-invariant across canvases (raw-path rois drift ~1e-4 px,
        # mask_logits ~5e-6 between the exact-fit and padded buckets),
        # so the gathered grids inherit that — the bitwise bar is
        # device-vs-host WITHIN each bucket, asserted above
        tight, padded = dev_masks_per_bucket
        assert tight.shape == padded.shape and tight.dtype == padded.dtype
        np.testing.assert_allclose(tight, padded, atol=1e-4)
        assert set(dev.fetch_bytes_by_model) == {"masks"}
        assert dev.fetch_bytes_total > 0

    def test_split_window_byte_identical_masks(self, mask_env):
        """Depth-2 split (two dispatches in flight — the Replica
        inflight window's runner half) vs the serial depth-1 path."""
        dev = mask_env["dev"]
        b0 = dev.assemble([dev.make_request(_image(3, 64, 64))])
        b1 = dev.assemble([dev.make_request(_image(4, 64, 64))])
        serial = [dev.run(b0), dev.run(b1)]
        h0 = dev.dispatch(b0)
        h1 = dev.dispatch(b1)  # window of 2 before any complete
        split = [dev.complete(h0), dev.complete(h1)]
        for s, p in zip(serial, split):
            for key in ("det_masks", "det_mask_idx", "det_mask_valid",
                        "det_boxes", "det_scores", "det_valid"):
                assert (np.asarray(s[key]).tobytes()
                        == np.asarray(p[key]).tobytes()), key

    def test_hot_swap_no_stale_mask_shapes_no_recompile(
        self, mask_env, tmp_path
    ):
        from mx_rcnn_tpu.core.checkpoint import save_checkpoint

        dev, registry = mask_env["dev"], mask_env["registry"]
        batch = dev.assemble([dev.make_request(_image(5, 64, 64))])
        before = dev.run(batch)
        misses = dev.compile_cache.misses
        params2 = jax.tree_util.tree_map(
            lambda x: x * 1.01, mask_env["params"]
        )
        ck = save_checkpoint(str(tmp_path / "v2"), {"params": params2}, 1)
        registry.swap("masks", ck, dev, block=True, timeout=600)
        after = dev.run(batch)
        # the full load->verify->warm->commit->canary gate must not have
        # seeded a single new jit signature, and the swapped slot keeps
        # the fixed det_masks contract
        assert dev.compile_cache.misses == misses
        assert after["det_masks"].shape == before["det_masks"].shape
        assert np.asarray(after["det_masks"]).dtype == np.float32
        assert (np.asarray(after["det_scores"]).tobytes()
                != np.asarray(before["det_scores"]).tobytes())

    def test_bf16_mask_without_parity_gate_rejected(self, mask_env):
        with pytest.raises(ValueError, match="parity_check"):
            ServeRunner(
                mask_env["model"], mask_env["params"], mask_env["cfg"],
                max_batch=MAX_BATCH, precision="bfloat16",
                parity_check=False,
            )


class TestReducedPrecisionRungs:
    """The compression ladder's rungs on the two real tiny families: a
    bf16 or int8 runner's warm-up runs the f32 parity gate (boxes,
    scores and, for the mask family, mask probabilities), passes it,
    compiles one program a bucket and serving adds none."""

    @pytest.mark.parametrize("precision", ["bfloat16", "int8"])
    @pytest.mark.parametrize("family", ["box", "mask"])
    def test_rung_passes_the_parity_gate_and_adds_no_compile(
        self, family, precision, box_env, mask_env
    ):
        tag = {"bfloat16": "bf16", "int8": "int8"}[precision]
        env = box_env if family == "box" else mask_env
        # the raw random init: its saturated scores rank the proposals
        # with wide margins, so the gate reads numeric drift and not the
        # flip of an NMS tie between near-equal scores (which the damped
        # mask parameters are full of)
        params = env.get("raw_params", env["params"])
        r = ServeRunner(env["model"], params, env["cfg"],
                        max_batch=MAX_BATCH, precision=precision)
        assert r.warmup() == len(BUCKETS)
        report = r.parity[f"{r.default_model}:{tag}"]
        assert report["checked"] and report["ok"]
        assert report["precision"] == tag
        assert report["unmatched_confident"] == 0
        assert report["max_box_delta_px"] <= report["box_tol_px"]
        assert report["max_score_delta"] <= report["score_tol"]
        if family == "mask":
            assert report["mask_pairs"] > 0
            assert report["max_mask_prob_delta"] <= report["mask_tol"]
        r.run(r.assemble([r.make_request(_image(21, 64, 64))]))
        assert r.compile_cache.misses == len(BUCKETS)


class TestServingEngine:
    def test_end_to_end_mixed_sizes(self, runner):
        from mx_rcnn_tpu.serve.loadgen import run_load

        with ServingEngine(
            runner, max_linger=0.05, max_queue=16, in_flight=2
        ) as eng:
            rep = run_load(
                eng,
                num_requests=8,
                concurrency=4,
                sizes=((48, 64), (64, 90), (40, 56)),
                seed=0,
            )
        assert rep["outcomes"]["ok"] == 8
        assert rep["engine"]["requests"]["completed"] == 8
        assert rep["engine"]["compile"]["misses"] == len(BUCKETS)
        assert rep["engine"]["latency"]["e2e"]["p99_ms"] > 0
        # saturating closed loop (4 clients, batch 2): decent occupancy
        assert rep["engine"]["batches"]["occupancy"] >= 0.5

    def test_deadline_expiry_fails_fast_without_forward(self, runner):
        with ServingEngine(runner, max_linger=0.2) as eng:
            fut = eng.submit(_image(3), deadline_s=0.0)  # already expired
            with pytest.raises(DeadlineExceeded):
                fut.result(timeout=60)
            assert eng.metrics.expired == 1
        # the expired request never reached the device: no batch ran for it
        assert eng.metrics.failed == 0

    def test_backpressure_counts_rejections(self, runner):
        from mx_rcnn_tpu.serve.batcher import QueueFull

        eng = ServingEngine(runner, max_linger=5.0, max_queue=1)
        # don't start the engine: nothing drains, so the 2nd submit must
        # bounce — mirrors a wedged device under client pressure
        eng._started = True
        eng.submit(_image(4))
        with pytest.raises(QueueFull):
            eng.submit(_image(5))
        assert eng.metrics.rejected == 1
        assert eng.metrics.submitted == 1
        # resolve the orphaned request so nothing leaks between tests
        eng.batcher.close()
