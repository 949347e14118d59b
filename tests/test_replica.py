"""Fault matrix for the replica pool (ISSUE 6), CPU-only and fast.

Every test drives the REAL replica/router/engine machinery; only the
predict path is a numpy stub (:class:`FakeRunner`) whose "detections"
are a pure deterministic digest of the batch pixels — so a batch that
was hedged, requeued, or served by a rewarmed replica must produce
byte-identical results to an unfaulted run, and any routing bug that
serves the wrong slot shows up as a digest mismatch, not a flake.

The invariants under test are the ISSUE 6 acceptance criteria: every
submitted request resolves exactly once (success or typed error — zero
lost), transitions match the injected fault schedule, and the breaker
backs a flapping replica off harder each trip.  Time constants are
shrunk ~100x from production defaults; total injected sleep across the
module is a few seconds (tier-1 budget).
"""

import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from mx_rcnn_tpu.core.resilience import (
    RETRY_PRESETS,
    RetryPolicy,
    make_retry_policy,
)
from mx_rcnn_tpu.serve.batcher import QueueFull, Request
from mx_rcnn_tpu.serve.buckets import BucketLadder, CompileCache
from mx_rcnn_tpu.serve.engine import (
    DeadlineExceeded,
    EngineStopped,
    ServingEngine,
)
from mx_rcnn_tpu.serve.loadgen import run_load
from mx_rcnn_tpu.serve.metrics import LatencyHistogram
from mx_rcnn_tpu.serve.replica import (
    HealthPolicy,
    Replica,
    ReplicaDrained,
    ReplicaState,
)
from mx_rcnn_tpu.serve.router import ReplicaPool
from mx_rcnn_tpu.utils import faults


@pytest.fixture(autouse=True)
def _lock_order_check(monkeypatch):
    """Run the whole fault matrix with the R4 runtime counterpart on:
    every serve-stack lock becomes an order-asserting proxy
    (analysis/lockcheck.py) that raises LockOrderViolation at the
    acquire that would close a cycle."""
    from mx_rcnn_tpu.analysis import lockcheck

    monkeypatch.setenv("MX_RCNN_LOCK_CHECK", "1")
    lockcheck.reset()
    yield

LADDER = ((32, 32), (48, 64))
SIZES = ((24, 24), (32, 48), (16, 16))  # exercises both buckets

# production HealthPolicy shrunk ~100x so a whole drain/rewarm/rejoin
# cycle fits in tens of milliseconds
FAST = HealthPolicy(
    stall_timeout=0.3,
    fail_threshold=2,
    breaker_backoff=0.05,
    breaker_max_backoff=0.2,
    flap_window=10.0,
)


class FakeRunner:
    """Runner-interface stub: real ladder/assembly semantics, numpy-only
    predict whose output is a pure function of the slot pixels."""

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
        while len(images) < self.max_batch:  # slot-0 padding, like the real one
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

    def run(self, batch):
        if self.service_s:
            time.sleep(self.service_s)
        self.compile_cache.record((batch["images"].shape, "f32"))
        im = batch["images"].astype(np.float64)
        return {  # per-slot digest: pure function of the pixels
            "digest": np.stack(
                [im.sum(axis=(1, 2, 3)), (im * im).sum(axis=(1, 2, 3))],
                axis=1,
            )
        }

    def detections_for(self, out, batch, index, orig_hw=None, thresh=None):
        return [out["digest"][index].copy()]


def make_factory(service_s: float = 0.0, builds=None):
    def factory(index: int) -> FakeRunner:
        if builds is not None:
            builds.append(index)
        return FakeRunner(index, service_s=service_s)

    return factory


def wait_for(pred, timeout=5.0, msg="condition"):
    t_end = time.monotonic() + timeout
    while time.monotonic() < t_end:
        if pred():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {msg}")


def image(i: int, h: int = 24, w: int = 24) -> np.ndarray:
    rng = np.random.RandomState(1000 + i)
    return rng.rand(h, w, 3).astype(np.float32)


def expected_digest(pool, im) -> np.ndarray:
    """What an unfaulted pool returns for a single-image batch."""
    ref = FakeRunner()
    batch = ref.assemble([ref.make_request(im)])
    return ref.detections_for(ref.run(batch), batch, 0)[0]


@pytest.fixture
def no_faults(monkeypatch):
    monkeypatch.delenv(faults.ENV_VAR, raising=False)
    faults.reset()
    yield
    faults.reset()


# --------------------------------------------------------------- presets

def test_make_retry_policy_presets():
    assert set(RETRY_PRESETS) >= {"loader", "serve", "replica"}
    p = make_retry_policy("serve")
    assert isinstance(p, RetryPolicy) and p.tries == 3
    # replica preset is deliberately tighter: fail over, don't retry long
    assert make_retry_policy("replica").tries < p.tries
    over = make_retry_policy("serve", tries=7)
    assert over.tries == 7 and make_retry_policy("serve").tries == 3
    with pytest.raises(KeyError):
        make_retry_policy("nope")


# --------------------------------------------------------- fault grammar

def test_serve_fault_grammar_parses_compound_keys():
    specs = faults._parse(
        "predict_fail@2.1x3:0.5,replica_wedge@1.*,predict_stall@0.7,"
        "nan_loss@5"
    )
    assert specs[0].kind == "predict_fail" and specs[0].key == (2, 1)
    assert specs[0].times == 3 and specs[0].arg == 0.5
    assert specs[1].key == (1, None) and specs[1].arg == 5.0  # wedge default
    assert specs[2].key == (0, 7) and specs[2].arg == 0.25   # stall default
    assert specs[3].key == 5  # train-phase keys stay plain ints


def test_predict_fault_hook_fires_by_replica_and_ordinal(monkeypatch):
    monkeypatch.setenv(faults.ENV_VAR, "predict_fail@2.1x2,predict_fail@3.*")
    faults.reset()
    faults.predict_fault(0, 1)      # wrong replica: no-op
    faults.predict_fault(2, 0)      # wrong ordinal: no-op
    with pytest.raises(faults.InjectedPredictFault):
        faults.predict_fault(2, 1)
    with pytest.raises(faults.InjectedPredictFault):
        faults.predict_fault(2, 1)  # x2: second fire
    faults.predict_fault(2, 1)      # exhausted
    for ordinal in (0, 5, 99):      # wildcard matches every ordinal
        with pytest.raises(faults.InjectedPredictFault):
            faults.predict_fault(3, ordinal)
    faults.reset()


# ------------------------------------------------------- pool happy path

def test_pool_warms_all_replicas_and_serves(no_faults):
    builds = []
    pool = ReplicaPool(make_factory(builds=builds), 2, policy=FAST)
    try:
        misses = pool.warmup()
        assert misses == 2 * len(LADDER)  # merged cache: per-replica warmup
        assert [r.state for r in pool.replicas] == [ReplicaState.HEALTHY] * 2
        for r in pool.replicas:
            assert [t["to"] for t in r.transitions] == ["healthy"]
            assert r.transitions[0]["reason"] == "warmup ok"
        im = image(0)
        ref = FakeRunner()
        batch = ref.assemble([ref.make_request(im)])
        out = pool.run(batch)
        np.testing.assert_array_equal(
            pool.detections_for(out, batch, 0)[0], expected_digest(pool, im)
        )
        assert pool.completed == 1 and pool.healthy_fraction() == 1.0
        assert builds == [0, 1]  # one build per replica, no rewarm
    finally:
        pool.close()


# ------------------------------------------------------ transient retry

def test_transient_predict_fail_absorbed_by_replica_retry(monkeypatch):
    # ordinal 0 is the warmup probe; ordinal 1 = first traffic dispatch.
    # x1: one attempt raises, the in-place retry's second attempt serves.
    monkeypatch.setenv(faults.ENV_VAR, "predict_fail@0.1x1")
    faults.reset()
    pool = ReplicaPool(make_factory(), 1, policy=FAST)
    try:
        pool.warmup()
        im = image(1)
        ref = FakeRunner()
        batch = ref.assemble([ref.make_request(im)])
        out = pool.run(batch)
        np.testing.assert_array_equal(
            pool.detections_for(out, batch, 0)[0], expected_digest(pool, im)
        )
        rep = pool.replicas[0]
        assert rep.retried == 1 and rep.failures == 0
        assert rep.state is ReplicaState.HEALTHY
        assert pool.failovers == 0  # absorbed below the router
    finally:
        pool.close()
        faults.reset()


# ----------------------------------------------------- hard-fail failover

def test_hard_fail_fails_over_to_sibling(monkeypatch, no_faults):
    pool = ReplicaPool(make_factory(), 2, policy=FAST)
    try:
        pool.warmup()
        im = image(2)
        ref = FakeRunner()
        batch = ref.assemble([ref.make_request(im)])
        primary = pool._pick(tuple(batch["images"].shape[1:3]))
        # every dispatch on the primary raises — retries exhausted, the
        # router must fail over to the sibling, and the result must be
        # identical to an unfaulted run
        monkeypatch.setenv(
            faults.ENV_VAR, f"predict_fail@{primary.index}.*"
        )
        faults.reset()
        out = pool.run(batch)
        np.testing.assert_array_equal(
            pool.detections_for(out, batch, 0)[0], expected_digest(pool, im)
        )
        assert pool.failovers >= 1
        assert primary.failures >= 1
        assert any(t["to"] == "degraded" for t in primary.transitions)
    finally:
        pool.close()


# --------------------------------------------- wedge: drain/rewarm/rejoin

def test_wedge_drains_requeues_and_rejoins(monkeypatch):
    builds = []
    pool = ReplicaPool(
        make_factory(builds=builds), 2, policy=FAST, hedge_timeout=5.0
    )
    try:
        pool.warmup()
        im = image(3)
        ref = FakeRunner()
        batch = ref.assemble([ref.make_request(im)])
        primary = pool._pick(tuple(batch["images"].shape[1:3]))
        # wedge past the 0.3 s stall watchdog on the primary's first
        # traffic dispatch (ordinal 1; ordinal 0 was its warmup probe)
        monkeypatch.setenv(
            faults.ENV_VAR, f"replica_wedge@{primary.index}.1:0.6"
        )
        faults.reset()
        t0 = time.monotonic()
        out = pool.run(batch)
        served_in = time.monotonic() - t0
        # the batch was requeued onto the sibling, not lost — and well
        # before the 0.6 s wedge released
        np.testing.assert_array_equal(
            pool.detections_for(out, batch, 0)[0], expected_digest(pool, im)
        )
        assert pool.requeued >= 1
        assert served_in < 0.6
        # the wedged replica walks the full recovery arc and rejoins
        wait_for(
            lambda: primary.state is ReplicaState.HEALTHY
            and primary.rewarms >= 1,
            timeout=5.0,
            msg="wedged replica rejoin",
        )
        tos = [t["to"] for t in primary.transitions]
        assert tos[:1] == ["healthy"]
        i_drain = tos.index("draining")
        assert "stall" in primary.transitions[i_drain]["reason"]
        assert tos[i_drain:i_drain + 3] == [
            "draining", "recovering", "healthy"
        ]
        assert primary.transitions[i_drain + 2]["reason"] == "rejoin"
        assert builds.count(primary.index) == 2  # initial build + rewarm
        assert primary.requeued_out >= 1
        wait_for(lambda: primary.abandoned >= 1, msg="late result discarded")
    finally:
        pool.close()
        faults.reset()


# ----------------------------------------------------------- hedge win

def test_slow_primary_hedges_and_hedge_wins(monkeypatch):
    pool = ReplicaPool(
        make_factory(), 2, policy=FAST, hedge_timeout=0.1
    )
    try:
        pool.warmup()
        im = image(4)
        ref = FakeRunner()
        batch = ref.assemble([ref.make_request(im)])
        primary = pool._pick(tuple(batch["images"].shape[1:3]))
        # stall between hedge timeout (0.1) and stall watchdog (0.3):
        # the hedge leg answers first, the primary stays healthy
        monkeypatch.setenv(
            faults.ENV_VAR, f"predict_stall@{primary.index}.1:0.25"
        )
        faults.reset()
        t0 = time.monotonic()
        out = pool.run(batch)
        dt = time.monotonic() - t0
        np.testing.assert_array_equal(
            pool.detections_for(out, batch, 0)[0], expected_digest(pool, im)
        )
        assert pool.hedged == 1 and pool.hedge_wins == 1
        assert dt < 0.25  # did not wait out the stall
        wait_for(
            lambda: primary.state is ReplicaState.HEALTHY
            and primary.dispatches == 1,
            msg="primary finishes its stalled dispatch",
        )
        assert not any(t["to"] == "draining" for t in primary.transitions)
    finally:
        pool.close()
        faults.reset()


# ------------------------------------------- breaker: flapping backoff

def test_breaker_backoff_grows_for_flapping_replica(no_faults):
    calls = {"n": 0}

    class FlakyRunner(FakeRunner):
        def run(self, batch):
            calls["n"] += 1
            if calls["n"] <= 6:
                raise RuntimeError("flap")
            return super().run(batch)

    rep = Replica(0, lambda i: FlakyRunner(i), policy=FAST)
    try:
        # warmup probe keeps failing: each lap is one trip, and the
        # breaker waits longer each lap (0 → 0 → 0.05 → 0.1)
        wait_for(
            lambda: rep.state is ReplicaState.HEALTHY, timeout=5.0,
            msg="flapping replica finally admitted",
        )
        assert rep.breaker_opens >= 2
        assert rep.last_backoff == pytest.approx(
            FAST.breaker_backoff * 2, rel=0.01
        )
        assert calls["n"] == 7  # 3 failed probe laps x2 attempts + success
    finally:
        rep.stop()


# ------------------------------------------------- engine: load shedding

def test_engine_sheds_when_pool_unhealthy(monkeypatch, no_faults):
    pool = ReplicaPool(make_factory(), 1, policy=FAST)
    engine = ServingEngine(pool, max_linger=10.0, max_queue=4)
    try:
        engine.start(warmup=True)
        assert engine._routed
        orig_frac = pool.healthy_fraction
        fut = engine.submit(image(5))  # lingers: batch not full
        # healthy capacity collapses: intake must shed, not queue
        monkeypatch.setattr(pool, "healthy_fraction", lambda: 0.0)
        with pytest.raises(QueueFull):
            engine.submit(image(6))
        assert engine.metrics.shed == 1
        # fractional health scales the cap: 1 pending >= int(4*0.26)=1
        monkeypatch.setattr(pool, "healthy_fraction", lambda: 0.26)
        with pytest.raises(QueueFull):
            engine.submit(image(7))
        assert engine.metrics.shed == 2
        monkeypatch.setattr(pool, "healthy_fraction", orig_frac)
        engine.submit(image(8))  # fills the batch of 2 → both complete
        assert len(fut.result(timeout=5.0)) == 1
        snap = engine.snapshot()
        assert snap["requests"]["shed"] == 2
        assert snap["pool"]["routing"]["completed"] >= 1
    finally:
        engine.stop()
        pool.close()


# ------------------------------------- engine: stop() resolves everything

def test_stop_resolves_pending_futures_with_engine_stopped(no_faults):
    runner = FakeRunner(service_s=0.25)
    engine = ServingEngine(runner, max_linger=0.0, in_flight=1)
    engine.start(warmup=True)
    # 5 requests at max_batch=2, in_flight=1: >= 3 batches, so at least
    # one is still queued when the abort lands
    futs = [engine.submit(image(10 + i, h=16, w=16)) for i in range(5)]
    time.sleep(0.05)  # let the first batch reach the device
    engine.stop(drain=False)
    outcomes = []
    for f in futs:
        try:
            f.result(timeout=5.0)
            outcomes.append("ok")
        except EngineStopped:
            outcomes.append("stopped")
    # zero lost: every future resolved — the in-flight batch finished,
    # everything behind it got the terminal error instead of hanging
    assert len(outcomes) == 5
    assert "stopped" in outcomes
    assert engine.metrics.stopped == outcomes.count("stopped")


def test_graceful_stop_drains_then_sweeps_nothing(no_faults):
    runner = FakeRunner(service_s=0.0)
    engine = ServingEngine(runner, max_linger=0.0)
    engine.start(warmup=True)
    futs = [engine.submit(image(20 + i)) for i in range(3)]
    engine.stop()  # drain=True: all work completes
    assert all(len(f.result(timeout=1.0)) == 1 for f in futs)
    assert engine.metrics.stopped == 0
    assert not engine._live


# ------------------------------- engine: completion-time deadline recheck

def test_deadline_rechecked_at_completion(no_faults):
    runner = FakeRunner(service_s=0.25)
    engine = ServingEngine(runner, max_linger=0.0, in_flight=1)
    engine.start(warmup=True)
    try:
        # passes the assembly-time check (picked up within ms) but
        # expires inside the 0.25 s predict: must NOT report stale success
        fut = engine.submit(image(30), deadline_s=0.1)
        with pytest.raises(DeadlineExceeded):
            fut.result(timeout=5.0)
        assert engine.metrics.expired == 1
    finally:
        engine.stop()


# ----------------------------- acceptance: loadgen under the fault matrix

def _loadgen_results(pool, n=12, seed=7):
    engine = ServingEngine(pool, max_linger=0.01, in_flight=3)
    with engine:
        report = run_load(
            engine, num_requests=n, concurrency=4, sizes=SIZES,
            seed=seed, collect=True,
        )
    return report


def test_faulted_pool_loses_nothing_and_matches_unfaulted(monkeypatch):
    n = 12
    monkeypatch.delenv(faults.ENV_VAR, raising=False)
    faults.reset()
    pool = ReplicaPool(make_factory(0.02), 3, policy=FAST, hedge_timeout=0.1)
    baseline = _loadgen_results(pool, n)
    pool.close()
    assert baseline["outcomes"]["ok"] == n
    base_results = baseline.pop("_results")

    # one fault of each serve kind, spread across the three replicas
    # (ordinal 0 everywhere is the warmup probe; traffic starts at 1)
    monkeypatch.setenv(
        faults.ENV_VAR,
        "predict_fail@0.1x1,replica_wedge@1.1:0.6,predict_stall@2.1:0.25",
    )
    faults.reset()
    pool = ReplicaPool(make_factory(0.02), 3, policy=FAST, hedge_timeout=0.1)
    faulted = _loadgen_results(pool, n)
    snap = pool.snapshot()
    pool.close()
    faults.reset()

    out = faulted["outcomes"]
    # zero lost: every request resolved exactly once, and under this
    # schedule every one of them SUCCEEDED (faults were absorbed by
    # retry/hedge/requeue, never surfaced to a client)
    assert out["ok"] + out["deadline"] + out["error"] == n
    assert out["ok"] == n
    # byte-identical to the unfaulted run, per request index
    fault_results = faulted.pop("_results")
    assert set(fault_results) == set(base_results)
    for i, (kind, dets) in fault_results.items():
        assert kind == "ok"
        bk, bdets = base_results[i]
        assert bk == "ok"
        assert len(dets) == len(bdets)
        for a, b in zip(dets, bdets):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    # the engine accounted every submission
    eng = faulted["engine"]["requests"]
    assert eng["completed"] == n and eng["failed"] == 0
    # pool-level accounting is consistent: batches <= requests, and the
    # pool-service histogram saw exactly the completed batches
    routing = snap["routing"]
    assert 1 <= routing["completed"] <= eng["completed"]
    assert snap["latency"]["pool_service"]["count"] == routing["completed"]


def test_pool_snapshot_merges_replica_histograms(no_faults):
    pool = ReplicaPool(make_factory(), 2, policy=FAST)
    try:
        pool.warmup()
        ref = FakeRunner()
        for i in range(4):
            batch = ref.assemble([ref.make_request(image(40 + i))])
            pool.run(batch)
        # a replica resolves a dispatch's future BEFORE it books the
        # latency, so ``pool.run`` can return with the last result still
        # unbooked: read the snapshot once all four are in
        wait_for(
            lambda: sum(r.latency.count for r in pool.replicas) == 4,
            msg="every replica to book its traffic results",
        )
        snap = pool.snapshot()
        merged = snap["latency"]["replica_predict_merged"]["count"]
        assert merged == sum(
            r["latency"]["count"] for r in snap["replicas"]
        )
        assert merged == 4  # traffic only; probes don't pollute latency
    finally:
        pool.close()


# ----------------------------------------------------- histogram merge

def test_latency_histogram_merge():
    a, b = LatencyHistogram(), LatencyHistogram()
    for v in (0.001, 0.01, 0.1):
        a.record(v)
    for v in (0.02, 2.0):
        b.record(v)
    a.merge(b)
    assert a.count == 5
    assert a.max_ms == pytest.approx(2000.0)
    assert a.total_ms == pytest.approx(1000 * (0.001 + 0.01 + 0.1 + 0.02 + 2.0))
    assert a.percentile(100) == pytest.approx(2000.0)
    with pytest.raises(ValueError):
        a.merge(LatencyHistogram(bins=8))
