"""Elastic replica autoscaling (ISSUE 16): breaker, controller, pool
elasticity, zero-loss scale-down, stop ordering, trace shapes.

Layered cheapest-first:

* pure ScaleBreaker with an injected clock: cooldown, flap-doubling,
  age-out;
* AutoScaler driven synchronously (``tick(now=...)``) against a fake
  pool + injected signals: the samples streak, growth/shrink decisions,
  breaker suppression on an oscillating signal, bounded convergence;
* a REAL ReplicaPool on numpy runner stubs: copy-on-write add/remove
  semantics, the replicas[0] anchor, and the headline guarantee — a
  scale-down in the middle of live load loses zero requests and the
  responses are byte-identical to a fixed-size control run;
* engine integration: ``attach_autoscaler`` wiring, the stop-ordering
  regression (autoscaler joined BEFORE pool teardown), and the
  trace-driven loadgen shapes (diurnal + flash crowd).

Every test runs with the lock-order checker armed, same as
tests/test_replica.py.
"""

import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from mx_rcnn_tpu.serve.autoscaler import AutoScaler, ScaleBreaker, ScalePolicy
from mx_rcnn_tpu.serve.batcher import Request
from mx_rcnn_tpu.serve.buckets import BucketLadder, CompileCache
from mx_rcnn_tpu.serve.engine import ServingEngine
from mx_rcnn_tpu.serve.loadgen import (
    diurnal_arrivals,
    flash_arrivals,
    run_load,
)
from mx_rcnn_tpu.serve.replica import HealthPolicy
from mx_rcnn_tpu.serve.router import ReplicaPool


@pytest.fixture(autouse=True)
def _lock_order_check(monkeypatch):
    from mx_rcnn_tpu.analysis import lockcheck

    monkeypatch.setenv("MX_RCNN_LOCK_CHECK", "1")
    lockcheck.reset()
    yield


LADDER = ((32, 32), (48, 64))

FAST = HealthPolicy(
    stall_timeout=0.5,
    fail_threshold=2,
    breaker_backoff=0.05,
    breaker_max_backoff=0.2,
    flap_window=10.0,
)


class FakeRunner:
    """Runner stub (tests/test_replica.py shape): per-slot digest is a
    pure function of the pixels, so byte-identity across pool sizes is a
    meaningful assertion."""

    def __init__(self, index: int = 0, service_s: float = 0.0):
        self.index = index
        self.service_s = service_s
        self.ladder = BucketLadder(LADDER)
        self.max_batch = 2
        self.cfg = None
        self.compile_cache = CompileCache()

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
        im = batch["images"].astype(np.float64)
        return {"digest": im.sum(axis=(1, 2, 3))}

    def detections_for(self, out, batch, index, orig_hw=None, thresh=None):
        return [np.array([out["digest"][index]])]


def make_factory(service_s: float = 0.0):
    def factory(index: int) -> FakeRunner:
        return FakeRunner(index, service_s=service_s)

    return factory


def image(i: int, h: int = 24, w: int = 24) -> np.ndarray:
    rng = np.random.RandomState(1000 + i)
    return rng.rand(h, w, 3).astype(np.float32)


class FakePool:
    """Just enough pool surface for AutoScaler decision tests: a
    replicas list plus add/remove with the copy-on-write contract."""

    def __init__(self, n: int):
        self.replicas = [SimpleNamespace(routable=True) for _ in range(n)]

    def add_replica(self):
        r = SimpleNamespace(routable=True)
        self.replicas = self.replicas + [r]
        return r

    def remove_replica(self, replica=None, timeout=5.0):
        if len(self.replicas) <= 1:
            return None
        victim = self.replicas[-1]
        self.replicas = self.replicas[:-1]
        return victim


def sig(depth, healthy, p99=None):
    return {"queue_depth": depth, "healthy": healthy, "p99_ms": p99}


# ------------------------------------------------------------- breaker
class TestScaleBreaker:
    def test_cooldown_gates_next_event(self):
        b = ScaleBreaker(cooldown=1.0, flap_window=5.0)
        assert b.allow(0.0)
        b.note(0.0, "up")
        assert not b.allow(0.5)
        assert b.suppressed == 1
        assert b.allow(1.5)

    def test_reversal_inside_window_doubles_backoff(self):
        b = ScaleBreaker(cooldown=1.0, flap_window=5.0, max_backoff=8.0)
        b.note(0.0, "up")
        b.note(2.0, "down")  # reversal 2s later, inside the 5s window
        assert b.flaps == 1
        assert b.snapshot()["backoff_s"] == 2.0
        b.note(4.0, "up")
        assert b.flaps == 2
        assert b.snapshot()["backoff_s"] == 4.0
        # same-direction events are not flaps
        b.note(6.0, "up")
        assert b.flaps == 2

    def test_backoff_caps_at_max(self):
        b = ScaleBreaker(cooldown=3.0, flap_window=100.0, max_backoff=4.0)
        for t, d in [(0, "up"), (10, "down"), (20, "up"), (30, "down")]:
            b.note(float(t), d)
        assert b.snapshot()["backoff_s"] == 4.0

    def test_clean_window_ages_backoff_out(self):
        b = ScaleBreaker(cooldown=1.0, flap_window=5.0)
        b.note(0.0, "up")
        b.note(2.0, "down")
        assert b.snapshot()["backoff_s"] == 2.0
        # a full flap_window with no further flap closes the breaker
        assert b.allow(10.0)
        assert b.snapshot()["backoff_s"] == 1.0


# ---------------------------------------------------------- controller
class TestAutoScalerDecisions:
    def make(self, n=1, **policy_over):
        kw = dict(min_replicas=1, max_replicas=4, samples=3,
                  cooldown=0.0, flap_window=0.0)
        kw.update(policy_over)
        pool = FakePool(n)
        scaler = AutoScaler(pool, policy=ScalePolicy(**kw))
        return pool, scaler

    def drive(self, scaler, signals, t0=100.0, dt=1.0):
        actions = []
        now = t0
        for s in signals:
            scaler._signal_fn = lambda s=s: s
            actions.append(scaler.tick(now=now))
            now += dt
        return actions

    def test_streak_required_before_growing(self):
        pool, scaler = self.make(n=1, samples=3)
        acts = self.drive(scaler, [sig(100, 1)] * 3)
        # tick1 starts the streak, tick2 extends, tick3 acts
        assert acts == [None, None, "up"]
        assert len(pool.replicas) == 2

    def test_interrupted_streak_resets(self):
        pool, scaler = self.make(n=1, samples=3)
        acts = self.drive(
            scaler,
            [sig(100, 1), sig(100, 1), sig(1, 1), sig(100, 1), sig(100, 1)],
        )
        # the calm tick broke the streak; two more up-ticks are not
        # enough to act again
        assert acts == [None] * 5
        assert len(pool.replicas) == 1

    def test_shrinks_to_min_on_idle(self):
        pool, scaler = self.make(n=3, samples=2)
        self.drive(scaler, [sig(0, 3)] * 10)
        assert len(pool.replicas) == 1
        assert scaler.scale_downs == 2

    def test_respects_max_replicas(self):
        pool, scaler = self.make(n=1, samples=2, max_replicas=2)
        self.drive(scaler, [sig(1000, 1)] * 10)
        assert len(pool.replicas) == 2
        assert scaler.scale_ups == 1

    def test_p99_slo_triggers_growth(self):
        pool, scaler = self.make(n=1, samples=2, p99_slo_ms=100.0)
        # queue is calm but the interactive p99 is blown
        self.drive(scaler, [sig(0, 1, p99=500.0)] * 3)
        assert len(pool.replicas) == 2

    def test_oscillating_signal_is_damped(self):
        # naive control would flap every few ticks; the breaker must
        # bound the event count and log the suppression
        pool, scaler = self.make(
            n=2, samples=2, max_replicas=4,
            cooldown=0.5, flap_window=100.0, max_backoff=4.0,
        )
        script = ([sig(100, 2)] * 3 + [sig(0, 2)] * 3) * 10
        self.drive(scaler, script, dt=0.1)
        snap = scaler.snapshot()
        total_events = scaler.scale_ups + scaler.scale_downs
        assert total_events <= 6  # vs 20 naive reversals
        assert snap["breaker"]["flaps"] >= 1
        assert snap["breaker"]["suppressed"] >= 5
        assert 1 <= len(pool.replicas) <= 4

    def test_converges_without_flapping_on_sustained_load(self):
        pool, scaler = self.make(n=1, samples=2, max_replicas=3)
        self.drive(scaler, [sig(500, len(pool.replicas))] * 20)
        assert len(pool.replicas) == 3
        assert scaler.scale_ups == 2
        assert scaler.snapshot()["breaker"]["flaps"] == 0
        # events log carries the audit trail
        assert [e["action"] for e in scaler.snapshot()["events"]] \
            == ["up", "up"]


# ------------------------------------------------------- pool elasticity
class TestPoolElasticity:
    def test_add_replica_warms_and_serves(self):
        pool = ReplicaPool(make_factory(), 1, policy=FAST)
        try:
            pool.warmup()
            r = pool.add_replica()
            t_end = time.monotonic() + 10.0
            while not r.routable and time.monotonic() < t_end:
                time.sleep(0.01)
            assert r.routable
            assert len(pool.replicas) == 2
            assert pool.replicas[-1] is r
            # fresh index, not a reuse of an existing one
            assert r.index == 1
        finally:
            pool.close()

    def test_grow_costs_one_ladder_warmup_and_shrink_none(self):
        """Compile misses by pool size: a scale-up is exactly one ladder
        warm-up on the new replica, traffic at any size and a scale-down
        add none."""
        pool = ReplicaPool(make_factory(), 1, policy=FAST)
        try:
            pool.warmup()
            assert pool.compile_cache.misses == len(LADDER)
            r = pool.add_replica()
            t_end = time.monotonic() + 10.0
            while not r.routable and time.monotonic() < t_end:
                time.sleep(0.01)
            assert r.routable
            assert pool.compile_cache.misses == 2 * len(LADDER)
            ref = FakeRunner()
            for i in range(8):
                h, w = LADDER[i % 2]
                pool.run(ref.assemble([ref.make_request(image(i, h, w))]))
            assert pool.remove_replica() is r
            pool.run(ref.assemble([ref.make_request(image(9))]))
            assert [
                x.runner.compile_cache.misses for x in pool.replicas
            ] == [len(LADDER)]
            assert r.runner.compile_cache.misses == len(LADDER)
        finally:
            pool.close()

    def test_remove_replica_never_strands_the_anchor(self):
        pool = ReplicaPool(make_factory(), 2, policy=FAST)
        try:
            pool.warmup()
            anchor = pool.replicas[0]
            assert pool.remove_replica(anchor) is None  # refuses [0]
            victim = pool.remove_replica()
            assert victim is not None and victim is not anchor
            assert len(pool.replicas) == 1
            assert pool.remove_replica() is None  # size-1 floor
        finally:
            pool.close()

    def test_zero_loss_scale_down_byte_identical(self):
        images = [image(i) for i in range(40)]

        def run(shrink: bool):
            pool = ReplicaPool(make_factory(service_s=0.004), 2,
                               policy=FAST)
            engine = ServingEngine(pool, max_linger=0.0, max_queue=128,
                                   in_flight=1)
            try:
                with engine:
                    futs = [engine.submit(im) for im in images]
                    if shrink:
                        victim = pool.remove_replica()
                        assert victim is not None
                    results = [f.result(timeout=30.0) for f in futs]
            finally:
                pool.close()
            return results, engine.snapshot()

        fixed, _ = run(shrink=False)
        shrunk, snap = run(shrink=True)
        # zero loss: every request completed...
        assert snap["requests"]["completed"] == len(images)
        assert snap["requests"]["failed"] == 0
        # ...and the responses are byte-identical to the control run
        for a, b in zip(fixed, shrunk):
            assert len(a) == len(b)
            for ca, cb in zip(a, b):
                np.testing.assert_array_equal(ca, cb)


# --------------------------------------------------- engine integration
class TestEngineAutoscaler:
    def test_attach_requires_pool_path(self):
        engine = ServingEngine(FakeRunner(), max_linger=0.0)
        with engine:
            with pytest.raises(RuntimeError):
                engine.attach_autoscaler()

    def test_attach_and_real_signals(self):
        pool = ReplicaPool(make_factory(), 1, policy=FAST)
        engine = ServingEngine(pool, max_linger=0.0)
        try:
            with engine:
                scaler = engine.attach_autoscaler(
                    policy=ScalePolicy(max_replicas=2), start=False
                )
                s = scaler.signals()
                assert s["queue_depth"] == 0
                assert s["healthy"] == 1
                assert engine.snapshot()["autoscaler"]["replicas"] == 1
        finally:
            pool.close()

    def test_stop_joins_autoscaler_before_pool_teardown(self):
        # regression (ISSUE 16 satellite): engine.stop must join the
        # controller BEFORE tearing the pool down, otherwise a scale-up
        # firing mid-shutdown races pool.close — same interlock family
        # as the cancel_swaps-first ordering from the registry
        pool = ReplicaPool(make_factory(), 1, policy=FAST)
        engine = ServingEngine(pool, max_linger=0.0)
        with engine:
            scaler = engine.attach_autoscaler(
                policy=ScalePolicy(max_replicas=3, interval=0.01,
                                   samples=1, cooldown=0.0)
            )
            assert scaler.running
        # engine.__exit__ ran stop(): the controller thread is joined,
        # not orphaned, and no further scale events can fire
        assert not scaler.running
        assert not any(
            t.name == "autoscaler" and t.is_alive()
            for t in threading.enumerate()
        )
        pool.close()

    def test_stop_is_idempotent_with_autoscaler(self):
        pool = ReplicaPool(make_factory(), 1, policy=FAST)
        engine = ServingEngine(pool, max_linger=0.0)
        engine.start()
        engine.attach_autoscaler(policy=ScalePolicy(max_replicas=2))
        engine.stop()
        engine.stop()
        assert not engine.autoscaler.running
        pool.close()


# ------------------------------------------------------- trace shapes
class TestTraces:
    def test_diurnal_arrivals_shape(self):
        arr = diurnal_arrivals(200, lo_rps=5.0, hi_rps=50.0, seed=3)
        assert len(arr) == 200
        assert all(b >= a for a, b in zip(arr, arr[1:]))
        assert arr[0] >= 0.0
        # deterministic per seed
        assert arr == diurnal_arrivals(200, lo_rps=5.0, hi_rps=50.0, seed=3)
        assert arr != diurnal_arrivals(200, lo_rps=5.0, hi_rps=50.0, seed=4)
        # the ramp is real: arrivals cluster where the rate peaks, so
        # the middle third of the span holds more than a third of them
        span = arr[-1]
        mid = [t for t in arr if span / 3 <= t <= 2 * span / 3]
        assert len(mid) > len(arr) / 3

    def test_flash_arrivals_compress_the_spike(self):
        arr = flash_arrivals(300, base_rps=10.0, flash_frac=0.5,
                             flash_at=0.5, seed=1)
        assert len(arr) == 300
        assert all(b >= a for a, b in zip(arr, arr[1:]))
        gaps = np.diff(np.asarray(arr))
        # flash gaps (10x rate) are far tighter than base gaps
        assert np.median(gaps[:100]) > 3 * np.median(gaps[170:270])

    def test_run_load_trace_and_tenants(self):
        engine = ServingEngine(FakeRunner(), max_linger=0.0, max_queue=256)
        arr = flash_arrivals(24, base_rps=200.0, flash_frac=0.5, seed=2)
        with engine:
            report = run_load(
                engine, num_requests=24, concurrency=4,
                sizes=((24, 24),), seed=0,
                tenants=["acme", "beta"], arrivals=arr,
            )
        assert report["outcomes"]["ok"] == 24
        assert set(report["tenants"]) == {"acme", "beta"}
        per_tenant = report["tenant_outcomes"]
        assert sum(v["ok"] for v in per_tenant.values()) == 24
        assert report["trace"]["arrivals"] == 24
        assert report["trace"]["span_s"] > 0
