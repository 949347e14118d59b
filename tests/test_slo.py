"""SLO-tiered two-lane scheduling + inference-optimized serve graph
(ISSUE 11).

Three layers, cheapest first, matching the serve-stack test split:

* pure batcher policy (milliseconds, no engine): interactive preemption,
  the two-condition bulk-aging guard, the expired-request sweep;
* engine-level scheduling on a numpy runner stub: interactive latency
  bounded under a saturating bulk backlog, bulk never starved under an
  interactive flood, zero recompiles across lanes, registry SLO-class
  lane defaults, and the idempotent response cache (byte-identity, LRU,
  hot-swap invalidation through a REAL registry swap);
* one real tiny model: the bf16 serve-graph parity gate and its
  precision-tagged compile signatures.

Every test runs with the lock-order checker armed (graftlint R4's
runtime counterpart), same as tests/test_replica.py.
"""

import threading
import time

import numpy as np
import pytest

from mx_rcnn_tpu.core.checkpoint import save_checkpoint
from mx_rcnn_tpu.serve.batcher import (
    DEFAULT_LANE,
    DeadlineExceeded,
    DynamicBatcher,
    QueueFull,
    Request,
)
from mx_rcnn_tpu.serve.buckets import BucketLadder, CompileCache
from mx_rcnn_tpu.serve.engine import ServingEngine
from mx_rcnn_tpu.serve.registry import ModelRegistry
from mx_rcnn_tpu.serve.respcache import ResponseCache


@pytest.fixture(autouse=True)
def _lock_order_check(monkeypatch):
    from mx_rcnn_tpu.analysis import lockcheck

    monkeypatch.setenv("MX_RCNN_LOCK_CHECK", "1")
    lockcheck.reset()
    yield


LADDER = ((32, 32), (48, 64))


def _req(bucket=(32, 32), deadline=None, lane=DEFAULT_LANE, enqueue_t=0.0):
    return Request(
        image=np.zeros((1,), np.uint8),
        im_info=np.array([1.0, 1.0, 1.0], np.float32),
        orig_hw=(1, 1),
        bucket=bucket,
        deadline=deadline,
        lane=lane,
        enqueue_t=enqueue_t,
    )


def image(i: int, h: int = 24, w: int = 24) -> np.ndarray:
    rng = np.random.RandomState(1000 + i)
    return rng.rand(h, w, 3).astype(np.float32)


# ------------------------------------------------------- batcher lane policy
class TestLanePolicy:
    def test_interactive_preempts_waiting_bulk(self):
        b = DynamicBatcher(max_batch=4, max_linger=0.0)
        b.submit(_req(lane="bulk"))
        b.submit(_req(lane="interactive"))
        first = b.next_batch()
        assert [r.lane for r in first] == ["interactive"]
        second = b.next_batch()
        assert [r.lane for r in second] == ["bulk"]
        s = b.stats()
        assert s["preemptions"] == 1
        assert s["batches_by_lane"] == {"interactive": 1, "bulk": 1}

    def test_interactive_zero_linger_releases_batch_of_one(self):
        # bulk linger is huge; the interactive lane must not inherit it
        b = DynamicBatcher(max_batch=4, max_linger=10.0,
                           interactive_linger=0.0)
        b.submit(_req(lane="interactive"))
        t0 = time.monotonic()
        batch = b.next_batch()
        assert len(batch) == 1 and batch[0].lane == "interactive"
        assert time.monotonic() - t0 < 1.0

    def test_aging_guard_needs_head_age_and_release_gap(self):
        now = time.monotonic()
        # both conditions met → bulk takes the slot despite interactive
        b = DynamicBatcher(max_batch=4, max_linger=10.0, bulk_age_limit=0.1)
        b._last_bulk_release = now - 0.2
        b.submit(_req(lane="bulk", enqueue_t=now - 0.2))
        b.submit(_req(lane="interactive"))
        batch = b.next_batch()
        assert [r.lane for r in batch] == ["bulk"]
        assert b.stats()["aged_releases"] == 1

        # head old but bulk released recently (deep-backlog shape) →
        # interactive still wins: the guard is about starvation, and a
        # lane that just got a batch is not starved
        b2 = DynamicBatcher(max_batch=4, max_linger=10.0, bulk_age_limit=0.1)
        b2._last_bulk_release = time.monotonic()
        b2.submit(_req(lane="bulk", enqueue_t=time.monotonic() - 0.2))
        b2.submit(_req(lane="interactive"))
        assert [r.lane for r in b2.next_batch()] == ["interactive"]
        assert b2.stats()["aged_releases"] == 0
        assert b2.stats()["preemptions"] == 1

        # release gap old but head fresh → no starvation yet either
        b3 = DynamicBatcher(max_batch=4, max_linger=10.0, bulk_age_limit=0.1)
        b3._last_bulk_release = time.monotonic() - 0.2
        b3.submit(_req(lane="bulk"))
        b3.submit(_req(lane="interactive"))
        assert [r.lane for r in b3.next_batch()] == ["interactive"]
        assert b3.stats()["aged_releases"] == 0

    def test_unknown_lane_rejected(self):
        b = DynamicBatcher(max_batch=2)
        with pytest.raises(ValueError, match="unknown SLO lane"):
            b.submit(_req(lane="express"))
        assert b.pending() == 0


# --------------------------------------------------------- expired sweep
class TestExpiredSweep:
    def test_submit_sweep_frees_capacity_before_queuefull(self):
        b = DynamicBatcher(max_batch=2, max_linger=10.0, max_queue=1)
        dead = _req(deadline=time.monotonic() - 0.01)
        b.submit(dead)
        live = _req()  # queue is "full" of dead work — must still admit
        b.submit(live)
        assert b.pending() == 1
        assert b.stats()["expired_swept"] == 1
        with pytest.raises(DeadlineExceeded, match="swept from queue"):
            dead.future.result(timeout=0)
        assert not live.future.done()

    def test_next_batch_sweeps_other_groups(self):
        b = DynamicBatcher(max_batch=2, max_linger=0.0)
        dead = _req(bucket=(48, 64), deadline=time.monotonic() - 0.01)
        b.submit(dead)
        b.submit(_req(bucket=(32, 32)))
        batch = b.next_batch()
        assert [r.bucket for r in batch] == [(32, 32)]
        assert b.stats()["expired_swept"] == 1
        assert isinstance(dead.future.exception(timeout=0), DeadlineExceeded)
        assert b.pending() == 0

    def test_on_expired_hook_owns_resolution(self):
        seen = []
        b = DynamicBatcher(max_batch=2, max_linger=10.0, max_queue=4,
                           on_expired=lambda r, now: seen.append(r))
        dead = _req(deadline=time.monotonic() - 0.01)
        b.submit(dead)
        b.submit(_req())
        assert seen == [dead]
        assert not dead.future.done()  # the hook, not the batcher, resolves


# ------------------------------------------------------- engine-level lanes
class FakeRunner:
    """Runner-interface stub (same shape as tests/test_replica.py): real
    ladder/assembly semantics, numpy predict, configurable service time."""

    def __init__(self, service_s: float = 0.0, max_batch: int = 2):
        self.service_s = service_s
        self.ladder = BucketLadder(LADDER)
        self.max_batch = max_batch
        self.cfg = None
        self.compile_cache = CompileCache()
        self.run_calls = 0

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
        if self.service_s:
            time.sleep(self.service_s)
        self.compile_cache.record((batch["images"].shape, "f32"))
        self.run_calls += 1
        im = batch["images"].astype(np.float64)
        return {"digest": im.sum(axis=(1, 2, 3))}

    def detections_for(self, out, batch, index, orig_hw=None, thresh=None):
        return [np.array([out["digest"][index]])]


class TestEngineTwoLane:
    def test_interactive_bounded_under_saturating_bulk(self):
        # 20 queued bulk requests ≈ 10 batches of service; a tagged
        # probe must ride the next free slot, not the whole backlog
        runner = FakeRunner(service_s=0.03)
        engine = ServingEngine(runner, max_linger=0.0, max_queue=64,
                               in_flight=1, bulk_age_limit=30.0)
        with engine:
            bulk = [engine.submit(image(i)) for i in range(20)]
            probe = engine.submit(image(99), lane="interactive")
            probe.result(timeout=10.0)
            done_bulk = sum(f.done() for f in bulk)
            for f in bulk:
                f.result(timeout=10.0)
        # the probe overtook most of the backlog (generous CI bound: at
        # most half the bulk work may have drained first)
        assert done_bulk <= 10
        snap = engine.snapshot()
        assert snap["scheduler"]["preemptions"] >= 1
        assert snap["lanes"]["interactive"]["completed"] == 1
        assert snap["lanes"]["bulk"]["completed"] == 20

    def test_bulk_never_starved_under_interactive_flood(self):
        runner = FakeRunner(service_s=0.005)
        engine = ServingEngine(runner, max_linger=0.0, max_queue=256,
                               in_flight=1, bulk_age_limit=0.05)
        stop = threading.Event()

        def flood(base):
            # pipeline 8 outstanding per thread: the interactive queue
            # must never drain empty, or bulk could slip into a free
            # slot through the normal path and the aging guard would
            # (legitimately) never fire
            pending, i = [], base
            while not stop.is_set():
                try:
                    pending.append(engine.submit(image(i), lane="interactive"))
                except (QueueFull, RuntimeError):
                    time.sleep(0.002)
                i += 1
                if len(pending) >= 8:
                    try:
                        pending.pop(0).result(timeout=10.0)
                    except RuntimeError:
                        return

        with engine:
            threads = [threading.Thread(target=flood, args=(500 * k,),
                                        daemon=True)
                       for k in range(1, 5)]
            for t in threads:
                t.start()
            time.sleep(0.05)  # flood established before bulk arrives
            bulk = [engine.submit(image(i), lane="bulk") for i in range(6)]
            for f in bulk:
                f.result(timeout=10.0)  # would hang forever if starved
            stop.set()
            for t in threads:
                t.join(timeout=10.0)
        s = engine.snapshot()["scheduler"]
        assert s["aged_releases"] >= 1
        assert s["batches_by_lane"]["bulk"] >= 1
        assert s["batches_by_lane"]["interactive"] >= 1

    def test_zero_recompiles_across_lanes(self):
        runner = FakeRunner()
        warm = runner.warmup()
        engine = ServingEngine(runner, max_linger=0.0)
        with engine:
            futs = [
                engine.submit(image(i, *hw), lane=lane)
                for i, (hw, lane) in enumerate(
                    [((24, 24), "interactive"), ((24, 24), "bulk"),
                     ((32, 48), "interactive"), ((32, 48), "bulk"),
                     ((24, 24), None), ((32, 48), None)]
                )
            ]
            for f in futs:
                f.result(timeout=10.0)
        # lanes schedule batches; they must not mint jit signatures
        assert runner.compile_cache.misses == warm == len(runner.ladder)

    def test_registry_slo_class_sets_default_lane(self):
        reg = ModelRegistry()
        reg.register("det", model=None, cfg=None,
                     params={"w": np.zeros(1, np.float32)},
                     slo_class="interactive")
        runner = FakeRunner()
        runner.registry = reg
        engine = ServingEngine(runner)
        # untagged request inherits the model's registry SLO class;
        # an explicit tag still wins; unknown lanes are rejected
        assert engine._lane_for(None, None) == "interactive"
        assert engine._lane_for("det", "bulk") == "bulk"
        with pytest.raises(ValueError, match="unknown SLO lane"):
            engine._lane_for(None, "express")
        from mx_rcnn_tpu.serve.registry import RegistryError

        with pytest.raises(RegistryError, match="slo_class must be one of"):
            ModelRegistry().register("x", model=None, cfg=None, params={},
                                     slo_class="express")


# ---------------------------------------------------------- response cache
def params_tree(w: float):
    return {"w": np.array([w], np.float32)}


class FakeSwapRunner(FakeRunner):
    """Registry-backed stub with the swap target surface (subset of
    tests/test_registry.py): predict output depends on the live
    version's ``w``, so a stale cache hit would be visible in bytes."""

    def __init__(self, registry, service_s: float = 0.0):
        super().__init__(service_s=service_s)
        self.registry = registry
        self.default_model = registry.default_model
        self._staged = {}

    def warmup(self) -> int:
        # same key shape as run() below — (model, shape, dtype) — so the
        # cache's sorted-signature snapshot stays homogeneous
        for bh, bw in self.ladder:
            self.compile_cache.record(
                (self.default_model, (self.max_batch, bh, bw, 3), "f32")
            )
        return self.compile_cache.misses

    def run(self, batch, model=None):
        mid = model or self.default_model
        live = self.registry.live(mid)
        if self.service_s:
            time.sleep(self.service_s)
        self.compile_cache.record((mid, batch["images"].shape, "f32"))
        self.run_calls += 1
        w = float(np.asarray(live.params["w"]).ravel()[0])
        im = batch["images"].astype(np.float64)
        return {"digest": im.sum(axis=(1, 2, 3)) * (1.0 + w)}

    def detections_for(self, out, batch, index, orig_hw=None, thresh=None,
                       model=None):
        return [np.array([out["digest"][index]])]

    def make_request(self, im, deadline=None, model=None) -> Request:
        r = super().make_request(im, deadline=deadline)
        r.model = model
        return r

    # swap target surface
    def warm_version(self, model, version, params, buckets=None, abort=None):
        self._staged[(model, int(version))] = params
        return 1

    def canary(self, model=None):
        return 1

    def discard_version(self, model, version):
        self._staged.pop((model, int(version)), None)


class TestResponseCache:
    def test_digest_identity_covers_shape_and_dtype(self):
        c = ResponseCache()
        a = np.arange(16, dtype=np.float32)
        assert c.digest(a) == c.digest(a.copy())
        assert c.digest(a) != c.digest(a.reshape(4, 4))  # same bytes
        assert c.digest(a) != c.digest(a.astype(np.float64))
        assert c.digest(a) != c.digest(a + 1)
        assert c.key_for(a, "det", 3) == ("det", 3, "f32", c.digest(a))
        # precision joins the key (ISSUE 18): an int8 serving of the
        # same family/version can never share bytes with the f32 one
        assert c.key_for(a, "det", 3, "int8") == ("det", 3, "int8",
                                                  c.digest(a))
        assert c.key_for(a, "det", 3) != c.key_for(a, "det", 3, "int8")

    def test_lru_no_overwrite_invalidate(self):
        c = ResponseCache(capacity=2)
        c.put(("m", 1, "a"), "A")
        c.put(("m", 1, "a"), "A2")          # no-overwrite: first wins
        assert c.get(("m", 1, "a")) == "A"
        c.put(("m", 1, "b"), "B")
        assert c.get(("m", 1, "a")) == "A"  # refreshes recency
        c.put(("n", 1, "c"), "C")           # evicts LRU ("m",1,"b")
        assert c.get(("m", 1, "b")) is None
        assert c.invalidate_model("m") == 1
        assert c.get(("m", 1, "a")) is None
        assert c.get(("n", 1, "c")) == "C"
        snap = c.snapshot()
        assert snap["size"] == 1
        assert snap["invalidations"] == 1 and snap["evictions"] == 1

    def test_engine_hit_is_byte_identical_and_skips_device(self):
        reg = ModelRegistry()
        reg.register("det", model=None, cfg=None, params=params_tree(1.0))
        runner = FakeSwapRunner(reg)
        cache = ResponseCache(capacity=8)
        engine = ServingEngine(runner, max_linger=0.0, response_cache=cache)
        im = image(1)
        with engine:
            miss = engine.submit(im).result(timeout=10.0)
            calls = runner.run_calls
            hit = engine.submit(im).result(timeout=10.0)
            other = engine.submit(image(2)).result(timeout=10.0)
        assert runner.run_calls >= calls + 1  # the different image ran
        assert len(hit) == len(miss)
        assert all(
            x.tobytes() == y.tobytes() and x.dtype == y.dtype
            for x, y in zip(hit, miss)
        )
        assert not all(
            x.tobytes() == y.tobytes() for x, y in zip(other, miss)
        )
        snap = cache.snapshot()
        assert snap["hits"] == 1 and snap["misses"] == 2
        assert engine.snapshot()["response_cache"]["hits"] == 1

    def test_hot_swap_invalidates_cache(self, tmp_path):
        reg = ModelRegistry()
        reg.register("det", model=None, cfg=None, params=params_tree(1.0))
        runner = FakeSwapRunner(reg)
        cache = ResponseCache(capacity=8)
        engine = ServingEngine(runner, max_linger=0.0, response_cache=cache)
        ckpt = save_checkpoint(
            str(tmp_path / "v2"), {"params": params_tree(2.0)}, 1
        )
        im = image(3)
        with engine:
            v1 = engine.submit(im).result(timeout=10.0)
            assert cache.snapshot()["size"] == 1
            engine.swap("det", ckpt, block=True)
            # the registry's live-pointer hook dropped the entry: the
            # resubmit recomputes under v2 instead of serving stale v1
            assert cache.snapshot()["size"] == 0
            v2 = engine.submit(im).result(timeout=10.0)
            hit2 = engine.submit(im).result(timeout=10.0)
        assert v1[0].tobytes() != v2[0].tobytes()
        assert hit2[0].tobytes() == v2[0].tobytes()
        # the fresh entry is keyed by the NEW live version
        assert any(k[1] == 2 for k in cache._entries)


# -------------------------------------- reduced-precision serve-graph parity
def _tiny_box_model():
    """One real tiny box model (shared by the bf16 and int8 rung tests)."""
    import dataclasses

    import jax

    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.models import build_model

    cfg = generate_config("resnet50", "PascalVOC")
    cfg = cfg.replace(
        SHAPE_BUCKETS=((64, 64),),
        network=dataclasses.replace(
            cfg.network, ANCHOR_SCALES=(2, 4, 8), FIXED_PARAMS=()
        ),
        dataset=dataclasses.replace(
            cfg.dataset, NUM_CLASSES=4, SCALES=((48, 64),)
        ),
        TEST=dataclasses.replace(
            cfg.TEST,
            RPN_PRE_NMS_TOP_N=100,
            RPN_POST_NMS_TOP_N=16,
            SCORE_THRESH=0.05,
        ),
    )
    model = build_model(cfg)
    params = model.init(
        {"params": jax.random.key(0)},
        np.zeros((1, 64, 64, 3), np.float32),
        np.array([[64, 64, 1.0]], np.float32),
        train=False,
    )["params"]
    return model, params, cfg


def test_parity_reports_keyed_per_model_and_precision():
    """:attr:`ServeRunner.parity` is keyed ``"model:precision"`` (ISSUE
    18): an int8 report can never satisfy — or be clobbered by — the
    bf16 gate for the same family."""
    from mx_rcnn_tpu.serve.runner import ServeRunner

    r = ServeRunner.__new__(ServeRunner)  # key scheme needs no device
    assert r._parity_key("det", "bf16") == "det:bf16"
    assert r._parity_key("det", "int8") == "det:int8"
    assert r._parity_key("det", "bf16") != r._parity_key("det", "int8")
    assert r._parity_key("det", "bf16") != r._parity_key("seg", "bf16")


@pytest.mark.slow
def test_bf16_parity_gate_and_precision_signatures():
    """One real tiny model served at bf16: warmup must run the f32
    detection-parity gate, pass it, and tag every compile signature with
    the precision so f32/bf16 graphs can never collide in the cache."""
    from mx_rcnn_tpu.serve.runner import ServeRunner

    model, params, cfg = _tiny_box_model()
    runner = ServeRunner(model, params, cfg, max_batch=1, precision="bfloat16")
    runner.warmup()
    report = runner.parity[f"{runner.default_model}:bf16"]
    assert report["checked"] and report["ok"]
    assert report["precision"] == "bf16"
    assert report["max_box_delta_px"] <= report["box_tol_px"]
    assert report["max_score_delta"] <= report["score_tol"]
    sigs = runner.compile_cache.snapshot()["signatures"]
    assert sigs and all("bf16" in repr(s) for s in sigs)
    # an f32 runner over the same model tags differently — the two
    # serve graphs occupy disjoint compile-cache keys by construction
    f32 = ServeRunner(model, params, cfg, max_batch=1)
    f32.warmup()
    f32_sigs = f32.compile_cache.snapshot()["signatures"]
    assert all("f32" in repr(s) for s in f32_sigs)
    assert not set(map(repr, sigs)) & set(map(repr, f32_sigs))


@pytest.mark.slow
def test_int8_parity_gate_and_broken_scale_fold_refused():
    """The int8 rung on a real tiny model: warmup folds per-channel
    scales at registry load, runs the same f32 detection-parity gate as
    bf16, and tags compile signatures ``int8``; a deliberately broken
    scale fold must be REFUSED by the gate, not served."""
    import jax

    from mx_rcnn_tpu.core.quantize import is_quantized_leaf
    from mx_rcnn_tpu.serve.runner import PrecisionParityError, ServeRunner

    model, params, cfg = _tiny_box_model()
    runner = ServeRunner(model, params, cfg, max_batch=1, precision="int8")
    runner.warmup()
    report = runner.parity[f"{runner.default_model}:int8"]
    assert report["checked"] and report["ok"]
    assert report["precision"] == "int8"
    assert report["max_box_delta_px"] <= report["box_tol_px"]
    assert report["max_score_delta"] <= report["score_tol"]
    sigs = runner.compile_cache.snapshot()["signatures"]
    assert sigs and all("int8" in repr(s) for s in sigs)
    # the registry folds scales once per (model, version) and caches
    reg = runner.registry
    assert reg.quantized_tree(runner.default_model) is reg.quantized_tree(
        runner.default_model
    )
    # a corrupted scale fold (one leaf's scales x64) fails the gate
    broken = ServeRunner(model, params, cfg, max_batch=1, precision="int8")
    slot = broken._slot(broken.default_model)
    hit = [False]

    def corrupt(x):
        if is_quantized_leaf(x) and not hit[0]:
            hit[0] = True
            return {"int8_q": x["int8_q"],
                    "int8_scale": np.asarray(x["int8_scale"]) * 64.0}
        return x

    slot.predictor.params = jax.tree_util.tree_map(
        corrupt, jax.device_get(slot.predictor.params),
        is_leaf=is_quantized_leaf,
    )
    assert hit[0]
    with pytest.raises(PrecisionParityError, match="int8"):
        broken.check_parity()
