"""Streaming serve tests (ISSUE 20): per-stream in-order delivery.

Three layers, cheapest first:

* :class:`~mx_rcnn_tpu.serve.streams.StreamTable` unit semantics —
  monotone registration, the ordering gate, exactly-once refusal,
  cancel/flush gap handling;
* engine end-to-end on the numpy FakeRunner (tests/test_replica.py
  shape): a gated replica FORCES frame N+1 to finish executing before
  frame N, and the table must still deliver in order; the chaos seam
  (ISSUE 20 satellite): a mid-stream frame requeued off a tripped
  replica while later frames dispatch, order preserved and bytes
  identical to the unfaulted run;
* the temporal-priming merge and the moving-scene renderer that feed
  the streaming bench's recall/latency table.

The device-paste canvas parity (jax) lives in TestCanvasParity at the
bottom — one tiny mask model, single bucket, device canvas vs host
numpy paste, byte-identical RLEs.
"""

import threading
import time

import numpy as np
import pytest

from mx_rcnn_tpu.data.synthetic import moving_scene
from mx_rcnn_tpu.serve.batcher import Request
from mx_rcnn_tpu.serve.buckets import BucketLadder, CompileCache
from mx_rcnn_tpu.serve.engine import ServingEngine
from mx_rcnn_tpu.serve.loadgen import run_stream_load, stream_arrivals
from mx_rcnn_tpu.serve.replica import HealthPolicy
from mx_rcnn_tpu.serve.router import ReplicaPool
from mx_rcnn_tpu.serve.streams import StreamTable, prime_proposals


@pytest.fixture(autouse=True)
def _lock_order_check(monkeypatch):
    from mx_rcnn_tpu.analysis import lockcheck

    monkeypatch.setenv("MX_RCNN_LOCK_CHECK", "1")
    lockcheck.reset()
    yield


LADDER = ((32, 32), (48, 64))

FAST = HealthPolicy(stall_timeout=0.5, fail_threshold=2,
                    breaker_backoff=0.05, breaker_max_backoff=0.2,
                    flap_window=10.0)

# generous watchdog for the gate test: the gated batch must NOT be
# rescued by the stall machinery — the reorder has to reach the table
PATIENT = HealthPolicy(stall_timeout=30.0)


class FakeRunner:
    """Runner-interface stub (tests/test_replica.py shape): real
    ladder/assembly semantics, numpy predict whose per-slot digest is a
    pure function of the slot pixels — so byte-identity across faulted
    and unfaulted runs is a meaningful assertion.  ``gate``: block any
    batch carrying the marker pixel until released.  ``fail_on``: raise
    on marker batches (per-replica — the trip/requeue seam)."""

    MARKER = 7.0

    def __init__(self, index: int = 0, service_s: float = 0.0,
                 gate=None, fail_holder=None):
        self.index = index
        self.service_s = service_s
        self.ladder = BucketLadder(LADDER)
        self.max_batch = 2
        self.cfg = None
        self.compile_cache = CompileCache()
        self.gate = gate
        # shared dict: the FIRST replica to see a marker batch claims it
        # and fails it on every attempt — retries exhaust, the replica
        # trips, the router requeues onto a sibling (which serves it)
        self.fail_holder = fail_holder

    def warmup(self) -> int:
        for bh, bw in self.ladder:
            self.compile_cache.record(((self.max_batch, bh, bw, 3), "f32"))
        return self.compile_cache.misses

    def make_request(self, im, deadline=None) -> Request:
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
        return {"images": np.stack(images)}

    def run(self, batch):
        marked = bool((batch["images"] == self.MARKER).any())
        if marked and self.fail_holder is not None:
            if self.fail_holder.setdefault("index", self.index) \
                    == self.index:
                raise RuntimeError("injected marker failure")
        if marked and self.gate is not None:
            self.gate.wait(timeout=30.0)
        if self.service_s:
            time.sleep(self.service_s)
        self.compile_cache.record((batch["images"].shape, "f32"))
        im = batch["images"].astype(np.float64)
        return {"digest": im.sum(axis=(1, 2, 3))}

    def detections_for(self, out, batch, index, orig_hw=None, thresh=None):
        return [np.array([out["digest"][index]])]


def image(i: int, h: int = 24, w: int = 24) -> np.ndarray:
    rng = np.random.RandomState(1000 + i)
    return rng.rand(h, w, 3).astype(np.float32)


def marked(im) -> np.ndarray:
    im = im.copy()
    im[0, 0, 0] = FakeRunner.MARKER
    return im


def wait_for(pred, timeout=10.0, msg="condition"):
    t_end = time.monotonic() + timeout
    while time.monotonic() < t_end:
        if pred():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {msg}")


# =============================================================== table
class TestStreamTable:
    def fired(self, log, tag):
        def fire():
            log.append(tag)
            return True

        return fire

    def test_register_validates_and_is_strictly_monotone(self):
        t = StreamTable()
        t.register("cam0", 0)
        t.register("cam0", 2)  # gaps at submit are fine (client drops)
        with pytest.raises(ValueError):
            t.register("cam0", 2)  # repeat
        with pytest.raises(ValueError):
            t.register("cam0", 1)  # reorder at submit
        with pytest.raises(ValueError):
            t.register("", 0)
        with pytest.raises(ValueError):
            t.register("cam0", -1)
        t.register("cam1", 0)  # other streams unaffected

    def test_in_order_settlement_fires_immediately(self):
        t, log = StreamTable(), []
        for f in range(3):
            t.register("s", f)
        for f in range(3):
            assert t.settle("s", f, self.fired(log, f)) is True
        assert log == [0, 1, 2]
        snap = t.snapshot()
        assert snap["delivered"] == 3
        assert snap["reordered"] == 0
        assert snap["buffered_peak"] == 0

    def test_out_of_order_buffers_then_drains_in_frame_order(self):
        t, log = StreamTable(), []
        for f in range(4):
            t.register("s", f)
        # frames 1..3 complete while 0 is still in flight
        for f in (2, 1, 3):
            assert t.settle("s", f, self.fired(log, f)) is True
        assert log == []  # gated on frame 0
        assert t.snapshot()["buffered_now"] == 3
        assert t.settle("s", 0, self.fired(log, 0)) is True
        assert log == [0, 1, 2, 3]
        snap = t.snapshot()
        assert snap["buffered_now"] == 0
        assert snap["buffered_peak"] == 3
        assert snap["reordered"] == 3
        assert snap["delivered"] == 4

    def test_double_settle_refused(self):
        t, log = StreamTable(), []
        t.register("s", 0)
        assert t.settle("s", 0, self.fired(log, "a")) is True
        # a second settlement of the same frame is the R5 surface
        assert t.settle("s", 0, self.fired(log, "b")) is False
        assert log == ["a"]
        # while buffered (not yet fired) a repeat is refused too
        t.register("s", 1)
        t.register("s", 2)
        assert t.settle("s", 2, self.fired(log, "c")) is True  # buffered
        assert t.settle("s", 2, self.fired(log, "d")) is False
        assert t.settle("s", 1, self.fired(log, 1)) is True
        assert log == ["a", 1, "c"]

    def test_unregistered_stream_fires_unordered(self):
        t, log = StreamTable(), []
        assert t.settle("ghost", 5, self.fired(log, 5)) is True
        assert log == [5]
        assert t.snapshot()["streams"] == 0

    def test_cancel_closes_the_gap(self):
        t, log = StreamTable(), []
        for f in range(3):
            t.register("s", f)
        assert t.settle("s", 1, self.fired(log, 1)) is True
        assert t.settle("s", 2, self.fired(log, 2)) is True
        assert log == []  # frame 0 outstanding
        t.cancel("s", 0)  # its submit failed synchronously
        assert log == [1, 2]
        assert t.snapshot()["cancelled"] == 1
        t.cancel("s", 7)  # unknown frame: no-op
        t.cancel("ghost", 0)  # unknown stream: no-op

    def test_flush_fires_buffered_in_frame_order(self):
        t, log = StreamTable(), []
        for f in range(4):
            t.register("s", f)
        assert t.settle("s", 3, self.fired(log, 3)) is True
        assert t.settle("s", 1, self.fired(log, 1)) is True
        assert log == []
        assert t.flush() == 2
        assert log == [1, 3]
        assert t.snapshot()["flushed"] == 2
        assert t.snapshot()["buffered_now"] == 0

    def test_callback_exception_does_not_wedge_the_drain(self):
        t, log = StreamTable(), []
        for f in range(3):
            t.register("s", f)

        def boom():
            raise RuntimeError("client callback blew up")

        assert t.settle("s", 1, self.fired(log, 1)) is True
        assert t.settle("s", 2, self.fired(log, 2)) is True
        assert t.settle("s", 0, boom) is True
        assert log == [1, 2]  # successors still delivered, in order
        assert t.snapshot()["delivered"] == 3

    def test_concurrent_settlers_one_stream_stay_ordered(self):
        t = StreamTable()
        n = 200
        log, lock = [], threading.Lock()
        for f in range(n):
            t.register("s", f)

        def fired(f):
            def fire():
                with lock:
                    log.append(f)
                return True

            return fire

        frames = list(range(n))
        rng = np.random.RandomState(0)
        rng.shuffle(frames)
        chunks = [frames[i::4] for i in range(4)]

        def settler(chunk):
            for f in chunk:
                t.settle("s", f, fired(f))

        threads = [threading.Thread(target=settler, args=(c,))
                   for c in chunks]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert log == list(range(n))
        snap = t.snapshot()
        assert snap["delivered"] == n and snap["buffered_now"] == 0


# ============================================================== engine
def submit_stream(engine, frames, stream="cam0", results=None, order=None,
                  lock=None):
    """Submit ``frames`` (list of images) in order; wire done-callbacks
    that record delivery order and payloads."""
    futs = []
    for f, im in enumerate(frames):
        fut = engine.submit(im, stream=stream, frame=f)
        if order is not None:
            def on_done(ft, f=f):
                with lock:
                    order.append(f)
                    if results is not None:
                        try:
                            results[f] = ft.result()
                        except Exception as e:  # noqa: BLE001
                            results[f] = e

            fut.add_done_callback(on_done)
        futs.append(fut)
    return futs


class TestEngineOrdering:
    def test_forced_reorder_is_delivered_in_order(self):
        """Frame 0 (gated on its replica) finishes EXECUTING after
        frame 1 (served by the idle sibling) — the table must hold
        frame 1's result until frame 0 lands."""
        gate = threading.Event()

        def factory(index):
            return FakeRunner(index, gate=gate)

        pool = ReplicaPool(factory, 2, policy=PATIENT)
        engine = ServingEngine(pool, max_linger=0.0, in_flight=2)
        order, results, lock = [], {}, threading.Lock()
        try:
            with engine:
                # different buckets → never co-batched; least-loaded
                # routing puts frame 1 on the idle sibling
                frames = [marked(image(0, 24, 24)), image(1, 40, 56)]
                futs = submit_stream(engine, frames, results=results,
                                     order=order, lock=lock)
                # frame 1 finishes executing and parks behind frame 0
                wait_for(
                    lambda: engine.snapshot().get("streams", {}).get(
                        "buffered_now") == 1,
                    msg="frame 1 buffered behind gated frame 0",
                )
                assert not futs[0].done() and not futs[1].done()
                gate.set()
                for f in futs:
                    f.result(timeout=10.0)
        finally:
            gate.set()
            pool.close()
        assert order == [0, 1]
        assert not isinstance(results[0], Exception)
        assert not isinstance(results[1], Exception)
        snap = engine.snapshot()["streams"]
        assert snap["reordered"] >= 1
        assert snap["delivered"] == 2
        assert snap["buffered_now"] == 0

    def test_chaos_requeue_preserves_order_and_bytes(self):
        """ISSUE 20 satellite: a mid-stream frame requeued off a
        tripped replica while later frames dispatch — delivery stays in
        frame order, zero lost frames, and every payload is
        byte-identical to the unfaulted control run."""
        frames = [image(i, 24, 24) for i in range(6)]
        frames[2] = marked(frames[2])  # the frame that trips replica 0

        def run(fail: bool):
            holder = {} if fail else None

            def factory(index):
                return FakeRunner(index, fail_holder=holder)

            pool = ReplicaPool(factory, 2, policy=FAST)
            engine = ServingEngine(pool, max_linger=0.0, in_flight=3)
            order, results, lock = [], {}, threading.Lock()
            try:
                with engine:
                    futs = submit_stream(engine, frames, results=results,
                                         order=order, lock=lock)
                    for f in futs:
                        f.result(timeout=30.0)
            finally:
                pool.close()
            snap = engine.snapshot()
            return order, results, snap

        order_c, results_c, _ = run(fail=False)
        order_f, results_f, snap = run(fail=True)
        assert order_c == list(range(6))
        assert order_f == list(range(6))
        for f in range(6):
            assert not isinstance(results_f[f], Exception), results_f[f]
            a, b = results_c[f], results_f[f]
            assert len(a) == len(b)
            for da, db in zip(a, b):
                assert np.asarray(da).tobytes() == np.asarray(db).tobytes()
        assert snap["streams"]["delivered"] == 6
        # the fault really exercised the redispatch seam
        routing = snap["pool"]["routing"]
        assert routing["requeued"] + routing["failovers"] >= 1

    def test_out_of_order_submit_is_rejected(self):
        from mx_rcnn_tpu.serve.buckets import BucketOverflow
        from mx_rcnn_tpu.serve.quarantine import InvalidRequest

        engine = ServingEngine(FakeRunner(), max_linger=0.0)
        with engine:
            engine.submit(image(0), stream="cam0", frame=0).result(
                timeout=10.0
            )
            with pytest.raises(InvalidRequest):
                engine.submit(image(1), stream="cam0", frame=0)
            with pytest.raises(InvalidRequest):
                engine.submit(image(2), frame=3)  # frame without stream
            # a synchronous reject AFTER registration (oversize image →
            # BucketOverflow in make_request) must cancel the
            # registration, or the gap would wedge the stream forever;
            # the rejected frame's index is burnt (monotone rule), the
            # client continues with the NEXT index
            with pytest.raises(BucketOverflow):
                engine.submit(image(1, 200, 200), stream="cam0", frame=1)
            engine.submit(image(1), stream="cam0", frame=2).result(
                timeout=10.0
            )
            snap = engine.snapshot()["streams"]
            assert snap["cancelled"] == 1
            assert snap["delivered"] == 2


# ============================================================= loadgen
class TestStreamLoad:
    def test_arrivals_are_monotone_within_stream(self):
        sched = stream_arrivals(3, 8, fps=30.0, stagger_s=0.01, seed=1)
        assert len(sched) == 24
        for s in range(3):
            offs = [sched[(s, f)] for f in range(8)]
            assert all(b > a for a, b in zip(offs, offs[1:]))
        again = stream_arrivals(3, 8, fps=30.0, stagger_s=0.01, seed=1)
        assert sched == again

    def test_run_stream_load_in_order_and_deterministic(self):
        def go():
            engine = ServingEngine(FakeRunner(), max_linger=0.0)
            with engine:
                rep = run_stream_load(
                    engine, num_streams=2, frames_per_stream=5,
                    fps=200.0, sizes=((24, 24), (40, 56)), seed=0,
                    collect=True,
                )
            return rep

        rep = go()
        assert rep["in_order"] is True
        assert rep["lost_frames"] == 0
        assert rep["resolved"] == rep["submitted"] == 10
        assert rep["outcomes"]["ok"] == 10
        assert sum(v for k, v in rep["outcomes"].items() if k != "ok") == 0
        assert rep["engine"]["streams"]["registered"] == 10
        assert rep["engine"]["streams"]["delivered"] == 10
        results = rep["_results"]
        rep2 = go()
        for key, (kind, payload) in results.items():
            kind2, payload2 = rep2["_results"][key]
            assert kind == kind2 == "ok"
            for da, db in zip(payload, payload2):
                assert np.asarray(da).tobytes() == np.asarray(db).tobytes()


# ============================================================= priming
class TestPriming:
    def props(self, n=10):
        rng = np.random.RandomState(0)
        boxes = rng.rand(n, 4).astype(np.float32) * 100
        scores = np.linspace(0.9, 0.1, n, dtype=np.float32)[:, None]
        return np.concatenate([boxes, scores], axis=1)

    def test_no_prev_returns_top_budget(self):
        p = self.props(10)
        out = prime_proposals(p, None, budget=4)
        assert out.shape == (4, 5)
        np.testing.assert_array_equal(out, p[:4])
        out = prime_proposals(p, np.zeros((0, 4), np.float32), budget=4)
        np.testing.assert_array_equal(out, p[:4])

    def test_seeds_rank_first_at_prime_score(self):
        p = self.props(10)
        prev = np.array([[1, 2, 3, 4, 0.99], [5, 6, 7, 8, 0.5]],
                        np.float32)
        out = prime_proposals(p, prev, budget=6)
        assert out.shape == (6, 5)
        np.testing.assert_array_equal(out[:2, :4], prev[:, :4])
        np.testing.assert_array_equal(out[:2, 4], [1.0, 1.0])
        np.testing.assert_array_equal(out[2:], p[:4])

    def test_budget_respected_when_seeds_overflow(self):
        p = self.props(10)
        prev = np.arange(5 * 4, dtype=np.float32).reshape(5, 4)
        out = prime_proposals(p, prev, budget=3)
        assert out.shape == (3, 5)
        np.testing.assert_array_equal(out[:, :4], prev[:3])


class TestMovingScene:
    def test_deterministic_and_roidb_shaped(self):
        a = moving_scene(7, 6, image_size=(160, 200), num_objects=3)
        b = moving_scene(7, 6, image_size=(160, 200), num_objects=3)
        assert len(a) == 6
        for ra, rb in zip(a, b):
            np.testing.assert_array_equal(ra["boxes"], rb["boxes"])
            np.testing.assert_array_equal(ra["gt_classes"],
                                          rb["gt_classes"])
            assert ra["synthetic_seed"] == rb["synthetic_seed"]
            assert ra["height"] == 160 and ra["width"] == 200
            assert ra["boxes"].shape == (3, 4)

    def test_boxes_stay_in_bounds_and_move(self):
        frames = moving_scene(3, 10, image_size=(140, 180),
                              num_objects=2, max_step=6.0)
        moved = 0.0
        for i, rec in enumerate(frames):
            b = rec["boxes"]
            assert (b[:, 0] >= 0).all() and (b[:, 1] >= 0).all()
            assert (b[:, 2] <= 179).all() and (b[:, 3] <= 139).all()
            assert (b[:, 2] > b[:, 0]).all() and (b[:, 3] > b[:, 1]).all()
            if i:
                moved += np.abs(b - frames[i - 1]["boxes"]).max()
        assert moved > 0.0  # objects genuinely move

    def test_with_masks_carries_segmentation(self):
        frames = moving_scene(5, 3, image_size=(128, 144), num_objects=2,
                              with_masks=True)
        for rec in frames:
            assert len(rec["segmentation"]) == 2
            for polys in rec["segmentation"]:
                assert len(polys) >= 1 and len(polys[0]) >= 6


# ======================================================== canvas parity
@pytest.fixture(scope="module")
def canvas_env():
    """One tiny mask model, single bucket: a device-canvas runner and a
    host-paste comparator over the same params."""
    import dataclasses

    import jax

    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.models import build_model
    from mx_rcnn_tpu.serve.runner import ServeRunner

    cfg = generate_config("mask_resnet_fpn", "PascalVOC")
    cfg = cfg.replace(
        SHAPE_BUCKETS=((64, 64),),
        network=dataclasses.replace(cfg.network, depth=50,
                                    FIXED_PARAMS=()),
        dataset=dataclasses.replace(cfg.dataset, NUM_CLASSES=4,
                                    SCALES=((64, 96),)),
        TEST=dataclasses.replace(
            cfg.TEST,
            RPN_PRE_NMS_TOP_N=100,
            RPN_POST_NMS_TOP_N=16,
            DET_PER_CLASS=8,
            MAX_PER_IMAGE=8,
            SCORE_THRESH=0.05,
        ),
    )
    model = build_model(cfg)
    h, w = cfg.SHAPE_BUCKETS[0]
    params = model.init(
        {"params": jax.random.key(0)},
        np.zeros((1, h, w, 3), np.float32),
        np.array([[h, w, 1.0]], np.float32),
        train=False,
    )["params"]

    # de-saturate the heads (as tests/test_serve_runner.py::_damped): at
    # random init every roi scores exactly 1.0 and keep order on exact
    # float ties would measure tie-break luck, not parity
    def damp(path, leaf):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        if any(f in name for f in ("rpn_cls_score", "rpn_bbox_pred",
                                   "cls_score", "bbox_pred",
                                   "mask_logits")):
            return leaf * 1e-2
        return leaf

    params = jax.tree_util.tree_map_with_path(damp, params)
    # batch 2: XLA CPU's oneDNN conv path rejects batch-1 primitives at
    # this geometry (same constraint as tests/test_serve_runner.py)
    dev = ServeRunner(model, params, cfg, max_batch=2, mask_canvas=True)
    host = ServeRunner(model, params, cfg, max_batch=2, mask_canvas=False)
    assert dev.warmup() == 1 and host.warmup() == 1
    return {"cfg": cfg, "dev": dev, "host": host}


def _canvas_image(i: int, h: int, w: int) -> np.ndarray:
    rng = np.random.RandomState(5000 + i)
    return (rng.rand(h, w, 3) * 255).astype(np.float32)


class TestCanvasParity:
    """Device-side paste (``det_canvas`` inside the jit) vs the numpy
    fixed-point mirror: RLEs byte-identical, canvases bitwise equal."""

    def test_device_canvas_matches_host_paste_bitwise(self, canvas_env):
        from mx_rcnn_tpu.eval.segm import paste_mask_canvas

        dev, host = canvas_env["dev"], canvas_env["host"]
        for i in (1, 2):
            im = _canvas_image(i, 64, 64)
            dreq = dev.make_request(im)
            hreq = host.make_request(im)
            dout = dev.run(dev.assemble([dreq]))
            hout = host.run(host.assemble([hreq]))
            assert "det_canvas" in dout and "det_canvas" not in hout
            canvas = np.asarray(dout["det_canvas"][0])
            hc, wc = canvas.shape[1:]
            assert (hc, wc) == dreq.bucket
            grids = np.asarray(hout["det_masks"][0])
            midx = np.asarray(hout["det_mask_idx"][0])
            boxes = np.asarray(hout["det_boxes"][0])
            max_out = hout["det_boxes"].shape[2]
            survivors = 0
            for p, fl in enumerate(midx):
                if fl < 0:
                    continue
                survivors += 1
                box = boxes[fl // max_out, fl % max_out]
                expect = paste_mask_canvas(grids[p], box, hc, wc)
                assert canvas[p].tobytes() == expect.tobytes(), (
                    f"image {i} survivor {p}: device canvas != numpy "
                    f"fixed-point mirror"
                )
            assert survivors > 0

    def test_mask_rles_for_byte_identical_and_counted(self, canvas_env):
        dev, host = canvas_env["dev"], canvas_env["host"]
        im = _canvas_image(3, 64, 64)
        dreq = dev.make_request(im)
        hreq = host.make_request(im)
        dbatch = dev.assemble([dreq])
        hbatch = host.assemble([hreq])
        dout = dev.run(dbatch)
        hout = host.run(hbatch)
        d_dets, d_rles = dev.mask_rles_for(dout, dbatch, 0,
                                           orig_hw=dreq.orig_hw)
        h_dets, h_rles = host.mask_rles_for(hout, hbatch, 0,
                                            orig_hw=hreq.orig_hw)
        assert sum(len(d) for d in d_dets[1:]) > 0
        for j in range(1, len(d_dets)):
            assert len(d_dets[j]) == len(h_dets[j])
            if len(d_dets[j]):
                assert (d_dets[j][:, 4].tobytes()
                        == h_dets[j][:, 4].tobytes())
            assert (
                [(r["size"], r["counts"]) for r in d_rles[j]]
                == [(r["size"], r["counts"]) for r in h_rles[j]]
            ), f"class {j}: canvas RLEs differ between device and host"
        # both paths account their paste cost for the pool merge
        for r in (dev, host):
            assert r.pastes >= 1
            assert r.paste_ms_total >= 0.0
            assert r.paste_bytes_total > 0

    def test_canvas_serving_adds_no_compile_after_warmup(self, canvas_env):
        """One canvas shape a (model, bucket) rung: frames of any size
        that resizes into the bucket run the warm-up's program, paste
        included."""
        dev, host = canvas_env["dev"], canvas_env["host"]
        for i, (h, w) in enumerate(((64, 64), (48, 48), (32, 32))):
            for r in (dev, host):
                r.run(r.assemble([r.make_request(_canvas_image(7 + i, h, w))]))
        assert dev.compile_cache.misses == 1
        assert host.compile_cache.misses == 1
