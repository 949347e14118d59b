"""Query-of-death containment matrix (ISSUE 12), CPU-only and fast.

Covers the full request-plane taxonomy end to end against the REAL
batcher/engine/router/replica machinery (numpy-stub runners, as in
``tests/test_replica.py``):

* admission control — malformed inputs fail the CALLER with
  ``InvalidRequest`` before the batcher or assembler see them (the
  pre-existing crash-the-assembler bug is the regression under test);
* attribution + quarantine — a digest implicated in >= K independent
  replica trips fails fast with ``PoisonRequest``; co-batched innocents
  are split out, served, and exonerated; entries age out on TTL;
* retry budgets — every requeue/hedge/resubmit spends; exhaustion
  resolves ``RetriesExhausted``, and quarantine takes precedence;
* isolation probes — a recovering replica replays the top suspect alone
  and the verdict confirms or clears the attribution.

The whole module runs under ``MX_RCNN_LOCK_CHECK=1`` (the R4 runtime
lock-order proxy), so any containment-path lock cycle fails loudly.
"""

import time

import numpy as np
import pytest

from mx_rcnn_tpu.serve.batcher import DynamicBatcher, Request
from mx_rcnn_tpu.serve.buckets import BucketLadder, CompileCache
from mx_rcnn_tpu.serve.engine import ServingEngine
from mx_rcnn_tpu.serve.loadgen import (
    POISON_FLAVORS,
    poison_image,
    qod_image,
    run_load,
)
from mx_rcnn_tpu.serve.quarantine import (
    BatchBudget,
    InvalidRequest,
    PoisonRequest,
    QuarantineTable,
    RetriesExhausted,
    RetryBudget,
    request_digest,
    validate_image,
)
from mx_rcnn_tpu.serve.registry import ModelRegistry
from mx_rcnn_tpu.serve.replica import HealthPolicy, Replica, ReplicaState
from mx_rcnn_tpu.serve.router import ReplicaPool
from mx_rcnn_tpu.utils import faults


@pytest.fixture(autouse=True)
def _lock_order_check(monkeypatch):
    from mx_rcnn_tpu.analysis import lockcheck

    monkeypatch.setenv("MX_RCNN_LOCK_CHECK", "1")
    lockcheck.reset()
    yield


@pytest.fixture
def no_faults(monkeypatch):
    monkeypatch.delenv(faults.ENV_VAR, raising=False)
    faults.reset()
    yield
    faults.reset()


LADDER = ((32, 32), (48, 64))

# one failed dispatch trips DRAINING — attribution converges in the
# fewest possible dispatches, and every time constant is test-scaled
TRIGGER = HealthPolicy(
    stall_timeout=0.3,
    fail_threshold=1,
    breaker_backoff=0.02,
    breaker_max_backoff=0.1,
    flap_window=10.0,
)


class FakeRunner:
    """Runner-interface stub (the ``test_replica`` idiom): real ladder
    and assembly semantics, numpy-only predict returning a per-slot
    pixel digest."""

    def __init__(self, index: int = 0):
        self.index = index
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
        return {
            "images": np.stack(images),
            "im_info": np.stack(
                [r.im_info for r in requests]
                + [requests[0].im_info] * (self.max_batch - len(requests))
            ),
        }

    def run(self, batch):
        self.compile_cache.record((batch["images"].shape, "f32"))
        im = batch["images"].astype(np.float64)
        return {"digest": im.sum(axis=(1, 2, 3))}

    def detections_for(self, out, batch, index, orig_hw=None, thresh=None):
        return [np.array([out["digest"][index]])]


def factory(index: int) -> FakeRunner:
    return FakeRunner(index)


def image(i: int, h: int = 24, w: int = 24) -> np.ndarray:
    rng = np.random.RandomState(2000 + i)
    return rng.rand(h, w, 3).astype(np.float32)


def wait_for(pred, timeout=5.0, msg="condition"):
    t_end = time.monotonic() + timeout
    while time.monotonic() < t_end:
        if pred():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {msg}")


# ------------------------------------------------------ admission gate

@pytest.mark.parametrize(
    "bad",
    [
        None,
        np.zeros((0, 0, 3), np.float32),          # zero-dim
        np.zeros((4, 4), np.float32),             # wrong rank
        np.zeros((4, 4, 4), np.float32),          # wrong channels
        np.empty((2, 2, 3), dtype=object),        # object dtype
        np.zeros((2, 2, 3), "datetime64[s]"),     # non-numeric dtype
    ],
    ids=["none", "zero-dim", "rank2", "chan4", "objdtype", "datetime"],
)
def test_validate_image_rejects_malformed(bad):
    with pytest.raises(InvalidRequest):
        validate_image(bad)


def test_validate_image_rejects_nonfinite_and_oversize():
    im = image(0)
    im[1, 1, 1] = np.inf
    with pytest.raises(InvalidRequest, match="non-finite"):
        validate_image(im)
    with pytest.raises(InvalidRequest, match="side"):
        validate_image(np.zeros((32, 4, 3), np.float32),
                       limits={"max_side": 16})
    with pytest.raises(InvalidRequest, match="pixels"):
        validate_image(np.zeros((8, 8, 3), np.float32),
                       limits={"max_pixels": 32})


def test_validate_image_accepts_good_and_coerces():
    im = image(1)
    assert validate_image(im) is im                 # no copy on the fast path
    assert validate_image(im.astype(np.uint8)).dtype == np.uint8
    out = validate_image([[[0, 0, 0]], [[1, 1, 1]]])  # list → (2,1,3) array
    assert isinstance(out, np.ndarray) and out.shape == (2, 1, 3)


def test_request_digest_is_stable_and_content_keyed():
    im = image(2)
    assert request_digest(im) == request_digest(im.copy())
    other = im.copy()
    other[0, 0, 0] += 1.0
    assert request_digest(im) != request_digest(other)
    # dtype is part of the identity: same bytes, different interpretation
    assert request_digest(im) != request_digest(im.view(np.int32))


def test_registry_limits_roundtrip():
    reg = ModelRegistry()
    reg.register("det", model=None, cfg=None,
                 params={"w": np.ones(1, np.float32)},
                 limits={"max_side": 8})
    assert reg.limits("det") == {"max_side": 8}
    reg.limits("det")["max_side"] = 99          # accessor returns a copy
    assert reg.limits("det") == {"max_side": 8}


def test_engine_admission_rejects_in_caller_thread(no_faults):
    engine = ServingEngine(FakeRunner(), max_linger=0.0)
    engine.start(warmup=True)
    try:
        nan = image(3)
        nan[0, 0, 0] = np.nan
        for bad in (np.zeros((0, 0, 3), np.float32),
                    np.empty((2, 2, 3), dtype=object), nan):
            with pytest.raises(InvalidRequest):
                engine.submit(bad)
        assert engine.metrics.invalid == 3
        assert engine.metrics.rejected == 3
        # the assembler never saw the malformed work and still serves
        assert len(engine.submit(image(4)).result(timeout=5.0)) == 1
        snap = engine.snapshot()
        assert snap["requests"]["invalid"] == 3
        assert snap["requests"]["completed"] == 1
    finally:
        engine.stop()


def test_engine_admission_applies_registry_limits(no_faults):
    class Registry:
        default_model = "det"

        def has(self, model):
            return True

        def limits(self, model=None):
            return {"max_side": 16}

        def cancel_swaps(self, wait=True):
            pass

    class RegRunner(FakeRunner):
        registry = Registry()

        def make_request(self, im, deadline=None, model=None):
            return super().make_request(im, deadline)

        def run(self, batch, model=None):
            return super().run(batch)

        def detections_for(self, out, batch, index, orig_hw=None,
                           thresh=None, model=None):
            return super().detections_for(out, batch, index)

    engine = ServingEngine(RegRunner(), max_linger=0.0)
    engine.start(warmup=True)
    try:
        with pytest.raises(InvalidRequest, match="side"):
            engine.submit(image(5, h=24, w=24), model="det")
        assert len(engine.submit(image(6, h=12, w=12),
                                 model="det").result(timeout=5.0)) == 1
    finally:
        engine.stop()


def test_batcher_submit_validates_direct_callers(no_faults):
    """Regression: DynamicBatcher.submit used to trust the caller's
    image array — a zero-dim or dtype-object image sailed into the
    queue and crashed the ASSEMBLER thread at np.stack time.  The gate
    must fail the submitting thread instead."""
    b = DynamicBatcher(max_batch=2, max_linger=0.0)

    def req(im):
        return Request(image=im, im_info=np.zeros(3, np.float32),
                       orig_hw=(1, 1), bucket=(1, 1))

    with pytest.raises(InvalidRequest):
        b.submit(req(np.float32(0.0)))                    # zero-dim scalar
    with pytest.raises(InvalidRequest):
        b.submit(req(np.empty((2, 0, 3), dtype=np.float32)))  # empty
    with pytest.raises(InvalidRequest):
        b.submit(req(np.empty((1,), dtype=object)))       # object dtype
    with pytest.raises(InvalidRequest):
        b.submit(req("not an array"))
    assert b.pending() == 0                               # nothing enqueued
    b.submit(req(np.zeros((1,), np.float32)))             # sane work passes
    assert b.pending() == 1
    b.close()


# ------------------------------------------------------- retry budgets

def test_retry_budget_spend_and_exhaustion():
    b = RetryBudget(2)
    b.spend("requeue")
    b.spend("hedge")
    assert b.remaining == 0
    with pytest.raises(RetriesExhausted):
        b.spend("requeue")
    assert b.snapshot() == {
        "total": 2, "remaining": 0, "spent": {"requeue": 1, "hedge": 1},
    }


def test_batch_budget_spends_every_member():
    a, b = RetryBudget(3), RetryBudget(1)
    bb = BatchBudget([a, None, b])
    assert bb.remaining == 1
    bb.spend("requeue")
    assert (a.remaining, b.remaining) == (2, 0)
    with pytest.raises(RetriesExhausted):
        bb.spend("requeue")
    assert BatchBudget([]).remaining == 0


# --------------------------------------------------- quarantine table

def test_note_trip_reaches_k_and_fast_fails():
    qt = QuarantineTable(k=3, ttl_s=30.0)
    d = "a" * 32
    assert qt.note_trip([(d, None)]) == []
    assert qt.note_trip([(d, None)]) == []
    assert not qt.quarantined(d)
    assert qt.note_trip([(d, None)]) == [d]        # third independent trip
    assert qt.quarantined(d)
    assert qt.fastfail_hits >= 1
    assert qt.first_quarantined(["b" * 32, d]) == d
    # further trips skip an already-quarantined digest
    assert qt.note_trip([(d, None)]) == []
    snap = qt.snapshot()
    assert snap["quarantined"][d[:12]].startswith("3 trips")
    assert snap["trips"] == 4 and snap["quarantined_total"] == 1


def test_exoneration_drops_suspicion():
    qt = QuarantineTable(k=2, ttl_s=30.0)
    d = "c" * 32
    qt.note_trip([(d, None)])
    assert qt.exonerate(d) and not qt.exonerate(d)
    assert qt.note_trip([(d, None)]) == []         # count restarted at 1
    assert not qt.quarantined(d)


def test_quarantine_ttl_ages_out():
    qt = QuarantineTable(k=1, ttl_s=0.05)
    d = "d" * 32
    assert qt.note_trip([(d, None)]) == [d]
    assert qt.quarantined(d)
    time.sleep(0.08)
    assert not qt.quarantined(d)                   # expired, traffic resumes
    assert qt.expired == 1


def test_top_suspect_orders_and_probe_settles():
    qt = QuarantineTable(k=5, ttl_s=30.0)
    lo, hi = "e" * 32, "f" * 32
    qt.note_trip([(lo, None), (hi, {"arrays": {}, "slots": 1})])
    qt.note_trip([(hi, None)])
    d1, payload = qt.top_suspect()
    assert d1 == hi and payload["slots"] == 1      # most-implicated first
    d2, _ = qt.top_suspect()
    assert d2 == lo                                # hi is in-probe: skipped
    assert qt.top_suspect() is None
    qt.probe_result(lo, ok=None)                   # abstain: mark released
    assert qt.top_suspect()[0] == lo
    qt.probe_result(lo, ok=True)
    qt.probe_result(hi, ok=False)
    assert not qt.quarantined(lo) and qt.quarantined(hi)
    assert qt.probes_cleared == 1 and qt.probes_confirmed == 1
    assert qt.snapshot()["quarantined"][hi[:12]] == "isolation probe"


# --------------------------------------- pool integration: containment

def _containment_stack(n_replicas=2, k=2, retry_budget=8, **engine_kw):
    qt = QuarantineTable(k=k, ttl_s=30.0)
    pool = ReplicaPool(factory, n_replicas, policy=TRIGGER,
                       hedge_timeout=5.0, quarantine=qt)
    engine = ServingEngine(pool, max_queue=16, in_flight=2,
                           retry_budget=retry_budget, **engine_kw)
    return qt, pool, engine


def test_poison_quarantined_within_k_trips(monkeypatch):
    poison = image(10)
    digest = request_digest(poison)
    monkeypatch.setenv(faults.ENV_VAR, f"poison_fail@{digest[:12]}")
    faults.reset()
    qt, pool, engine = _containment_stack(max_linger=0.0)
    try:
        engine.start(warmup=True)
        with pytest.raises(PoisonRequest):
            engine.submit(poison).result(timeout=10.0)
        assert qt.quarantined_total >= 1
        assert qt.trips <= qt.k + 1        # attribution converged, no rampage
        assert engine.metrics.poisoned >= 1
        # fast-fail: a resubmit of the same bytes never reaches a replica
        with pytest.raises(PoisonRequest):
            engine.submit(poison)
        # healthy traffic still serves once the pool recovers
        wait_for(lambda: pool.healthy_fraction() > 0,
                 msg="a replica rejoins")
        fut = engine.submit(image(11))
        assert len(fut.result(timeout=10.0)) == 1
        snap = engine.snapshot()
        assert snap["quarantine"]["quarantined"]           # visible in both
        assert snap["pool"]["quarantine"]["quarantined"]
    finally:
        engine.stop()
        pool.close()
        faults.reset()


def test_cobatched_innocent_split_served_and_exonerated(monkeypatch):
    poison, innocent = image(12), image(13)
    digest = request_digest(poison)
    monkeypatch.setenv(faults.ENV_VAR, f"poison_fail@{digest[:12]}")
    faults.reset()
    qt, pool, engine = _containment_stack(max_linger=0.3)
    try:
        engine.start(warmup=True)
        f_poison = engine.submit(poison)       # co-batched: max_batch=2 and
        f_innocent = engine.submit(innocent)   # a 0.3 s linger window
        with pytest.raises(PoisonRequest):
            f_poison.result(timeout=15.0)
        dets = f_innocent.result(timeout=15.0)
        # the innocent's solo replay is byte-identical to a clean run
        ref = FakeRunner()
        batch = ref.assemble([ref.make_request(innocent)])
        expect = ref.detections_for(ref.run(batch), batch, 0)
        np.testing.assert_array_equal(dets[0], expect[0])
        # it was split out of the implicated batch and cleared by name
        assert engine.metrics.resubmitted >= 1
        assert engine.metrics.exonerated >= 1
        assert qt.exonerated >= 1
        assert request_digest(innocent)[:12] not in (
            engine.snapshot()["quarantine"]["quarantined"]
        )
    finally:
        engine.stop()
        pool.close()
        faults.reset()


def test_budget_exhaustion_when_quarantine_never_converges(monkeypatch,
                                                           no_faults):
    # K unreachably high AND every replica broken outright (recovery
    # probes fail too, so no isolation probe can convict the digest):
    # the retry budget, not the quarantine, must end the request
    qt, pool, engine = _containment_stack(k=99, retry_budget=3,
                                          max_linger=0.0)
    try:
        engine.start(warmup=True)       # warm while healthy, then break
        monkeypatch.setenv(faults.ENV_VAR,
                           "predict_fail@0.*,predict_fail@1.*")
        with pytest.raises(RetriesExhausted):
            engine.submit(image(14)).result(timeout=20.0)
        assert engine.metrics.exhausted >= 1
        assert qt.quarantined_total == 0
    finally:
        engine.stop()
        pool.close()
        faults.reset()


def test_quarantine_takes_precedence_over_spent_budget(no_faults):
    qt, pool, engine = _containment_stack(max_linger=0.0)
    try:
        engine.start(warmup=True)
        im = image(15)
        req = pool.make_request(im)
        req.digest = request_digest(im)
        req.budget = RetryBudget(0)
        qt.quarantine(req.digest, "operator")
        engine._settle_failed([req], RuntimeError("whatever"))
        with pytest.raises(PoisonRequest):     # not RetriesExhausted
            req.future.result(timeout=1.0)
    finally:
        engine.stop()
        pool.close()


# --------------------------------------------------- isolation probes

def _suspect_payload(im):
    ref = FakeRunner()
    batch = ref.assemble([ref.make_request(im)])
    return {
        "arrays": {k: np.array(v[0]) for k, v in batch.items()},
        "slots": ref.max_batch,
        "model": None,
    }


def test_isolation_probe_confirms_poison(monkeypatch):
    im = image(16)
    digest = request_digest(im)
    monkeypatch.setenv(faults.ENV_VAR, f"poison_fail@{digest[:12]}")
    faults.reset()
    qt = QuarantineTable(k=3, ttl_s=30.0)
    qt.note_trip([(digest, _suspect_payload(im))])
    rep = Replica(0, factory, policy=TRIGGER, quarantine=qt)
    try:
        wait_for(lambda: rep.state is ReplicaState.HEALTHY, msg="warmup")
        rep.trip("test")
        wait_for(lambda: rep.state is ReplicaState.HEALTHY, msg="rejoin")
        assert rep.isolation_probes == 1
        assert rep.isolation_confirmed == 1
        # one trip + one probe — quarantined without K downed replicas
        assert qt.quarantined(digest)
        assert qt.probes_confirmed == 1
    finally:
        rep.stop()
        faults.reset()


def test_isolation_probe_wedge_flavor_confirms(monkeypatch):
    im = image(17)
    digest = request_digest(im)
    # sleeps past the 0.3 s stall watchdog: a wedging query of death
    monkeypatch.setenv(faults.ENV_VAR,
                       f"poison_wedge@{digest[:12]}:0.45")
    faults.reset()
    qt = QuarantineTable(k=3, ttl_s=30.0)
    qt.note_trip([(digest, _suspect_payload(im))])
    rep = Replica(0, factory, policy=TRIGGER, quarantine=qt)
    try:
        wait_for(lambda: rep.state is ReplicaState.HEALTHY, msg="warmup")
        rep.trip("test")
        wait_for(lambda: rep.state is ReplicaState.HEALTHY, msg="rejoin")
        assert rep.isolation_confirmed == 1
        assert qt.quarantined(digest)
    finally:
        rep.stop()
        faults.reset()


def test_isolation_probe_clears_innocent_suspect(no_faults):
    im = image(18)
    digest = request_digest(im)
    qt = QuarantineTable(k=3, ttl_s=30.0)
    qt.note_trip([(digest, _suspect_payload(im))])
    rep = Replica(0, factory, policy=TRIGGER, quarantine=qt)
    try:
        wait_for(lambda: rep.state is ReplicaState.HEALTHY, msg="warmup")
        rep.trip("test")
        wait_for(lambda: rep.state is ReplicaState.HEALTHY, msg="rejoin")
        assert rep.isolation_probes == 1
        assert rep.isolation_cleared == 1
        assert not qt.quarantined(digest)
        assert qt.probes_cleared == 1
        assert qt.snapshot()["suspects"] == {}     # fully cleared
    finally:
        rep.stop()


# ------------------------------------------------------ loadgen poison

def test_loadgen_poison_mix_draw_is_deterministic():
    mix = [None, None, "qod", "nan"]
    rng_a = np.random.RandomState(9)
    rng_b = np.random.RandomState(9)
    draw_a = [mix[rng_a.randint(len(mix))] for _ in range(64)]
    draw_b = [mix[rng_b.randint(len(mix))] for _ in range(64)]
    assert draw_a == draw_b
    for flavor in POISON_FLAVORS:
        im = poison_image(flavor, 5, 24, 24, seed=1)
        assert isinstance(im, np.ndarray)
    # every qod request of one size shares one digest (fault-spec key)
    assert request_digest(qod_image(24, 24, 1)) == \
        request_digest(poison_image("qod", 99, 24, 24, 1))
    with pytest.raises(ValueError):
        poison_image("nope", 0, 4, 4)


def test_loadgen_poison_mix_accounts_per_flavor(no_faults):
    engine = ServingEngine(FakeRunner(), max_linger=0.005, max_queue=32)
    with engine:
        report = run_load(
            engine, num_requests=24, concurrency=4,
            sizes=((24, 24), (16, 16)), seed=3,
            poison_mix=["nan", None],
        )
    out = report["outcomes"]
    n_nan = report["poison_flavors"].count("nan")
    assert 0 < n_nan < 24
    assert out["invalid"] == n_nan                # all rejected at admission
    assert out["ok"] == 24 - n_nan                # healthy traffic untouched
    assert report["poison_outcomes"]["nan"] == {"invalid": n_nan}
    assert report["engine"]["requests"]["invalid"] == n_nan


def test_poison_mix_under_load_is_contained(monkeypatch):
    """A sixth of a live load is a query of death: no healthy request is
    lost, every healthy answer is byte-identical to the unfaulted run's,
    every poison digest ends quarantined within K trips each, and every
    replica is healthy again at the end."""
    sizes, seed, n = ((24, 24), (16, 16)), 5, 36
    mix = [None] * 5 + ["qod"]
    # run_load's own draws (sizes, then the poison mix) say which
    # requests are poison and which digests the fault spec must name
    rng = np.random.RandomState(seed)
    req_sizes = [sizes[rng.randint(len(sizes))] for _ in range(n)]
    req_poison = [mix[rng.randint(len(mix))] for _ in range(n)]
    healthy = [i for i, fl in enumerate(req_poison) if fl is None]
    digests = sorted({
        request_digest(qod_image(h, w, seed))
        for (h, w), fl in zip(req_sizes, req_poison) if fl == "qod"
    })
    assert digests and len(healthy) > n // 2

    def one_run(spec):
        monkeypatch.setenv(faults.ENV_VAR, spec)
        faults.reset()
        # the budget outlasts a lap in which both replicas are rewarming
        qt, pool, engine = _containment_stack(
            retry_budget=32, max_linger=0.002)
        try:
            with engine:
                report = run_load(
                    engine, num_requests=n, concurrency=4, sizes=sizes,
                    seed=seed, collect=True, poison_mix=mix,
                )
            wait_for(
                lambda: all(r.state is ReplicaState.HEALTHY
                            for r in pool.replicas),
                timeout=30.0, msg="every replica to rejoin",
            )
            return report["_results"], qt
        finally:
            pool.close()
            faults.reset()

    clean, _ = one_run("")
    dirty, qt = one_run(
        ",".join(f"poison_fail@{d[:12]}" for d in digests))
    for i in healthy:
        assert clean[i][0] == "ok" and dirty[i][0] == "ok", i
        np.testing.assert_array_equal(clean[i][1][0], dirty[i][1][0])
    quarantined = set(qt.snapshot()["quarantined"])
    assert {d[:12] for d in digests} <= quarantined
    assert qt.trips <= len(digests) * (qt.k + 1)
    for i in set(range(n)) - set(healthy):
        assert dirty[i][0] != "ok", i
