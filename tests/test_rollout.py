"""Chaos matrix for progressive rollout (ISSUE 17), CPU-only and fast.

Same philosophy as ``tests/test_registry.py``: every test drives the
REAL ``RolloutController`` / ``ModelRegistry`` / engine machinery —
including real orbax checkpoints through the manifest + structure
gates — and only the predict path is a numpy stub
(:class:`FakeRolloutRunner`) whose "detections" are a pure
deterministic function of the batch pixels AND the serving version's
``w``, emitted in the serve stack's per-class ClsDets shape so
``detection_parity`` sees real boxes.  A version's ``w`` shifts its
box corners by ``(w - 1) * 10`` px: ``w = 1.0001`` is a faithful
candidate (0.001 px drift — promotes), ``w = 2.0`` is a divergent one
(10 px shift, IoU 0.14 — every shadow comparison reports unmatched
detections and the rollout must auto-roll-back).

The invariants under test are the ISSUE 17 acceptance criteria:
deterministic digest-hash arm assignment (same digest → same arm,
always — and the response cache never crosses arms); shadow scoring
never blocks or degrades the live SLO path; a divergence-injected
candidate is auto-rolled-back while the incumbent serves
byte-identical responses throughout (live pointer untouched); a
promote under live load loses zero requests and adds zero compile
misses; and distilled records round-trip the synthetic-record schema
through the real training loader.
"""

import threading
import time

import numpy as np
import pytest

from mx_rcnn_tpu.core.checkpoint import save_checkpoint
from mx_rcnn_tpu.serve.batcher import Request
from mx_rcnn_tpu.serve.buckets import BucketLadder, CompileCache
from mx_rcnn_tpu.serve.engine import ServingEngine
from mx_rcnn_tpu.serve.loadgen import run_load, synthetic_image
from mx_rcnn_tpu.serve.quarantine import request_digest
from mx_rcnn_tpu.serve.registry import (
    ModelRegistry,
    TRANSITION_LOG_MAX,
    UnknownVersion,
    VersionState,
)
from mx_rcnn_tpu.serve.respcache import ResponseCache
from mx_rcnn_tpu.serve.rollout import (
    RolloutAborted,
    RolloutCancelled,
    RolloutController,
    RolloutInProgress,
    RolloutPolicy,
    assign_arm,
)
from mx_rcnn_tpu.utils import faults


@pytest.fixture(autouse=True)
def _lock_order_check(monkeypatch):
    """Whole matrix under MX_RCNN_LOCK_CHECK=1: every serve-stack lock
    becomes an order-asserting proxy that raises LockOrderViolation at
    the acquire that would close a cycle — the controller lock, the
    shadow condition, and the divergence-report leaf included."""
    from mx_rcnn_tpu.analysis import lockcheck

    monkeypatch.setenv("MX_RCNN_LOCK_CHECK", "1")
    lockcheck.reset()
    yield


@pytest.fixture(autouse=True)
def _no_faults(monkeypatch):
    monkeypatch.delenv(faults.ENV_VAR, raising=False)
    faults.reset()
    yield
    faults.reset()


LADDER = ((32, 32), (48, 64))
SIZES = ((24, 24), (32, 48), (16, 16))

# checkpoints store params as float32 — expectations must use the same
# rounded value or the "byte-identical" comparisons drift by one ULP
W_GOOD = float(np.float32(1.0001))
W_BAD = 2.0


def params_tree(w: float):
    return {"w": np.array([w], np.float32)}


def cls_dets(pixel_sum: float, w: float):
    """The fake's "detections" for one slot: a single confident box
    whose position is a pure function of the slot pixels and the
    serving version's ``w`` — a version change is visible in every
    coordinate byte, and ``(w - 1) * 10`` px of injected drift."""
    x = float(pixel_sum) % 7.0
    shift = (w - 1.0) * 10.0
    box = np.array(
        [[5.0 + x + shift, 6.0 + x + shift,
          25.0 + x + shift, 26.0 + x + shift, 0.9]],
        np.float32,
    )
    return [None, box]


class FakeRolloutRunner:
    """Registry-backed runner stub implementing the full rollout target
    surface (``warm_version`` / ``run_version`` / ``discard_version`` /
    ``assemble`` / ``detections_for``) with the real sync semantics:
    predict resolves the registry's live pointer per batch, and a
    version-pinned predict serves the STAGED tree without touching the
    live slot (the zero-recompile split path)."""

    def __init__(self, registry, service_s: float = 0.0,
                 warm_delay_s: float = 0.0):
        self.registry = registry
        self.default_model = registry.default_model
        self.service_s = service_s
        self.warm_delay_s = warm_delay_s
        self.ladder = BucketLadder(LADDER)
        self.max_batch = 2
        self.cfg = None
        self.compile_cache = CompileCache()
        self.served_buckets = {}
        self.warm_started = threading.Event()
        self._versions = {}
        self._params = {}
        self._staged = {}
        self._lock = threading.Lock()

    def _mid(self, model):
        return self.default_model if model is None else model

    def _sync(self, mid):
        live = self.registry.live(mid)
        with self._lock:
            if self._versions.get(mid) == live.version:
                return
            staged = self._staged.pop((mid, live.version), None)
            for k in [k for k in self._staged if k[0] == mid]:
                self._staged.pop(k, None)
            self._params[mid] = (
                staged if staged is not None else live.params
            )
            self._versions[mid] = live.version

    # ---- runner facade
    def warmup(self, buckets=None, models=None) -> int:
        for m in (models or self.registry.model_ids()):
            self._sync(m)
            for bh, bw in (buckets or self.ladder):
                self.compile_cache.record((m, (self.max_batch, bh, bw, 3),
                                           "f32"))
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

    def _predict(self, batch, mid, w):
        if self.service_s:
            time.sleep(self.service_s)
        self.compile_cache.record((mid, batch["images"].shape, "f32"))
        self.served_buckets.setdefault(mid, set()).add(
            tuple(batch["images"].shape[1:3])
        )
        return {
            "sums": batch["images"].astype(np.float64).sum(axis=(1, 2, 3)),
            "w": w,
        }

    def run(self, batch, model=None):
        mid = self._mid(model)
        self._sync(mid)
        w = float(np.asarray(self._params[mid]["w"]).ravel()[0])
        return self._predict(batch, mid, w)

    def run_version(self, batch, model=None, version=None):
        mid = self._mid(model)
        self._sync(mid)
        with self._lock:
            live_v = self._versions.get(mid)
            staged = self._staged.get((mid, int(version)))  \
                if version is not None else None
        if version is None or int(version) == live_v:
            return self.run(batch, model=model)
        if staged is None:
            raise UnknownVersion(
                f"model {mid!r} v{int(version)} is neither live "
                f"(v{live_v}) nor staged"
            )
        w = float(np.asarray(staged["w"]).ravel()[0])
        return self._predict(batch, mid, w)

    def detections_for(self, out, batch, index, orig_hw=None, thresh=None,
                       model=None):
        return cls_dets(out["sums"][index], out["w"])

    # ---- rollout target surface
    def warm_version(self, model, version, params, buckets=None, abort=None):
        mid = self._mid(model)
        self.warm_started.set()
        if abort is not None:
            abort()
        if buckets is None:
            buckets = sorted(self.served_buckets.get(mid, ())) or list(
                self.ladder
            )
        for _ in buckets:
            if abort is not None:
                abort()
            if self.warm_delay_s:
                time.sleep(self.warm_delay_s)
        with self._lock:
            self._staged[(mid, int(version))] = params
        return len(buckets)

    def canary(self, model=None):
        return 1

    def discard_version(self, model, version):
        with self._lock:
            self._staged.pop((self._mid(model), int(version)), None)


def make_registry(w: float = 1.0):
    reg = ModelRegistry()
    reg.register("det", model=None, cfg=None, params=params_tree(w))
    return reg


def expected_bytes(im: np.ndarray, w: float) -> bytes:
    """The confident box the engine resolves for ``im`` under version
    ``w`` — the single computation shared by the fake and the tests."""
    bh, bw = BucketLadder(LADDER).select(*im.shape[:2])
    canvas = np.zeros((bh, bw, 3), np.float32)
    canvas[: im.shape[0], : im.shape[1]] = im
    s = canvas.astype(np.float64).sum()
    return cls_dets(s, w)[1].tobytes()


def wait_for(pred, timeout=10.0, msg="condition"):
    t_end = time.monotonic() + timeout
    while time.monotonic() < t_end:
        if pred():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {msg}")


@pytest.fixture(scope="module")
def ckpts(tmp_path_factory):
    """Committed orbax dumps with the registry tree shape: ``good`` is
    a faithful candidate (0.001 px drift), ``bad`` a divergent one
    (10 px shift — trips the unmatched bound on every comparison)."""
    root = tmp_path_factory.mktemp("rollout-ckpts")
    out = {}
    for name, w in (("good", W_GOOD), ("bad", W_BAD)):
        out[name] = save_checkpoint(
            str(root / name), {"params": params_tree(w)}, 1
        )
    return out


def rollout_under_load(eng, ckpt, n=48, seed=7):
    """Run ``rollout det <ckpt>`` to its end with live load beside it;
    → ``(result, waves)``, one ``(seed, report)`` a wave of ``n``
    requests.  The load runs wave after wave until the rollout has
    settled: it needs traffic for its evidence from the moment its
    candidate has restored and warmed until it promotes, and a fixed
    count can be over before a slow restore is."""
    waves, settled = [], threading.Event()

    def load():
        s = seed
        while True:
            waves.append((s, run_load(
                eng, num_requests=n, concurrency=4, sizes=SIZES, seed=s,
                collect=True,
            )))
            if settled.is_set():
                return
            s += 1

    t = threading.Thread(target=load)
    t.start()
    try:
        wait_for(lambda: eng.metrics.completed >= n // 6, msg="mid-load")
        result = eng.rollout.start(
            "det", ckpt, policy=fast_policy(), block=True, timeout=60,
        )
    finally:
        settled.set()
        t.join(timeout=60)
    assert not t.is_alive()
    return result, waves


def fast_policy(**over):
    base = dict(
        split_pct=30.0, shadow=True, min_compared=4, min_served=3,
        min_error_samples=10_000, min_latency_samples=10_000,
        hold_s=0.05, eval_interval_s=0.01, score_thresh=0.1,
    )
    base.update(over)
    return RolloutPolicy(**base)


def find_arm_images(pct=50.0, size=(24, 24)):
    """Two concrete images whose content digests deterministically land
    on opposite arms at ``pct`` — recomputed, not hardcoded, so the
    test tracks the digest function."""
    cand = inc = None
    for i in range(256):
        im = np.full((*size, 3), float(i % 97) + 0.5, np.float32)
        im[0, 0, 0] = i  # unique content
        if assign_arm(request_digest(im), pct):
            cand = cand if cand is not None else im
        else:
            inc = inc if inc is not None else im
        if cand is not None and inc is not None:
            return cand, inc
    raise AssertionError("digest space did not cover both arms")


# ------------------------------------------------- deterministic split

def test_assign_arm_deterministic_and_proportional():
    digests = [request_digest(synthetic_image(i, 16, 16, 3))
               for i in range(400)]
    for d in digests[:32]:
        assert assign_arm(d, 25.0) == assign_arm(d, 25.0)
        assert assign_arm(d, 0.0) is False
        assert assign_arm(d, 100.0) is True
    frac = sum(assign_arm(d, 25.0) for d in digests) / len(digests)
    assert 0.15 < frac < 0.35, frac
    # monotone: an arm won at pct stays won at any higher pct
    for d in digests[:64]:
        if assign_arm(d, 10.0):
            assert assign_arm(d, 60.0)


def test_engine_split_same_digest_same_arm(ckpts):
    """Engine-level determinism with NO cache in the loop: the same
    image resubmitted under an active split serves the same arm's bytes
    every time, and the two arms' bytes differ."""
    reg = make_registry()
    runner = FakeRolloutRunner(reg)
    eng = ServingEngine(runner, max_linger=0.0).start()
    try:
        ctl = eng.attach_rollout()
        ro = ctl.start("det", ckpts["bad"], policy=fast_policy(
            split_pct=50.0, shadow=False, min_served=10_000, hold_s=30.0,
        ))
        wait_for(lambda: ctl.active("det"), msg="split open")
        im_cand, im_inc = find_arm_images(50.0)
        for _ in range(3):
            got = eng.submit(im_cand).result(5)[1].tobytes()
            assert got == expected_bytes(im_cand, 2.0)
            got = eng.submit(im_inc).result(5)[1].tobytes()
            assert got == expected_bytes(im_inc, 1.0)
        snap = eng.snapshot()["rollout"]["models"]["det"]
        assert snap["served"]["candidate"] == 3
        assert snap["served"]["incumbent"] == 3
        assert not ro.done()
    finally:
        eng.stop()
    with pytest.raises(RolloutCancelled):
        ro.result(0)


# --------------------------------------------- satellite 1: cache arms

def test_response_cache_never_crosses_arms(ckpts):
    """The regression the split demands of the response cache: a key is
    minted against the SERVED arm's version, so a repeated request hits
    only its own arm's bytes — never arm-A bytes for an arm-B digest —
    and a rollback drops the candidate's entries."""
    reg = make_registry()
    runner = FakeRolloutRunner(reg)
    cache = ResponseCache(capacity=64)
    eng = ServingEngine(runner, max_linger=0.0, response_cache=cache).start()
    try:
        ctl = eng.attach_rollout()
        ro = ctl.start("det", ckpts["bad"], policy=fast_policy(
            split_pct=50.0, shadow=False, min_served=10_000, hold_s=30.0,
        ))
        wait_for(lambda: ctl.active("det"), msg="split open")
        im_cand, im_inc = find_arm_images(50.0)
        v_cand = reg.entry("det").versions[-1].version
        cand_bytes = expected_bytes(im_cand, 2.0)
        inc_bytes = expected_bytes(im_inc, 1.0)
        # miss then hit, per arm — hits must reproduce the ARM's bytes
        for _ in range(2):
            assert eng.submit(im_cand).result(5)[1].tobytes() == cand_bytes
            assert eng.submit(im_inc).result(5)[1].tobytes() == inc_bytes
        assert cache.hits == 2
        # the two arms hold disjoint keys: same model, different version
        keys = list(cache._entries)
        assert {k[1] for k in keys} == {1, v_cand}
        # cancel → rollback path invalidates the model's entries; the
        # same candidate-arm digest now recomputes on the incumbent
        ctl.stop()
        with pytest.raises(RolloutCancelled):
            ro.result(0)
        assert eng.submit(im_cand).result(5)[1].tobytes() == \
            expected_bytes(im_cand, 1.0)
    finally:
        eng.stop()


# ------------------------------------------------ shadow off the SLO path

def test_shadow_never_blocks_slo_and_promotes_on_evidence(ckpts):
    """Pure shadow (split 0%): every live request resolves through the
    incumbent with incumbent bytes; the candidate earns promotion
    entirely from mirrored comparisons that never touch the batcher,
    the submit gate, or any tenant budget."""
    reg = make_registry()
    runner = FakeRolloutRunner(reg)
    eng = ServingEngine(runner, max_linger=0.0).start()
    try:
        ctl = eng.attach_rollout()
        ro = ctl.start("det", ckpts["good"], policy=fast_policy(
            split_pct=0.0, min_compared=6,
        ))
        wait_for(lambda: ro.state == "evaluating" or ro.done(),
                 msg="shadow open")
        n = 0
        deadline = time.monotonic() + 20
        while not ro.done() and time.monotonic() < deadline:
            im = synthetic_image(n, *SIZES[n % len(SIZES)], 3)
            got = eng.submit(im).result(5)[1].tobytes()
            # every live response is the incumbent's, byte-identical —
            # shadow scoring is invisible to callers
            assert got in (expected_bytes(im, 1.0),
                           expected_bytes(im, W_GOOD))
            n += 1
        result = ro.result(5)
        assert result["version"] == 2 and result["previous"] == 1
        div = result["divergence"]
        assert div["compared"] >= 6 and div["failed"] == 0
        assert div["mirrored"] >= div["compared"]
        assert div["max_box_delta_px"] <= 0.01
        snap = eng.snapshot()
        # the shadow lane never entered the engine: submissions are
        # exactly the live requests, none failed, none expired
        assert snap["requests"]["submitted"] == n
        assert snap["requests"]["failed"] == 0
        assert snap["rollout"]["promoted"] == 1
        assert reg.live("det").version == 2
    finally:
        eng.stop()


# ------------------------------------------------- divergence rollback

def test_divergence_rollback_serves_byte_identical_incumbent(ckpts):
    """The headline guarantee: a divergent candidate is auto-rolled-back
    by the evaluator while every response — during the rollout, at the
    rollback instant, and after — carries the incumbent's exact bytes.
    The live pointer never moves."""
    reg = make_registry()
    runner = FakeRolloutRunner(reg)
    eng = ServingEngine(runner, max_linger=0.0).start()
    try:
        ctl = eng.attach_rollout()
        ro = ctl.start("det", ckpts["bad"], policy=fast_policy(
            split_pct=0.0, min_compared=3, hold_s=30.0,
        ))
        wait_for(lambda: ro.state == "evaluating" or ro.done(),
                 msg="shadow open")
        n = 0
        deadline = time.monotonic() + 20
        while not ro.done() and time.monotonic() < deadline:
            im = synthetic_image(n, *SIZES[n % len(SIZES)], 3)
            got = eng.submit(im).result(5)[1].tobytes()
            assert got == expected_bytes(im, 1.0), \
                f"request {n} not incumbent bytes during rollout"
            n += 1
        with pytest.raises(RolloutAborted) as exc:
            ro.result(5)
        assert exc.value.stage == "evaluate"
        assert "unmatched" in str(exc.value.cause)
        # live pointer untouched; candidate retired + released; staged
        # device tree discarded
        assert reg.live("det").version == 1
        cand = reg.entry("det").versions[-1]
        assert cand.state is VersionState.RETIRED and cand.params is None
        assert not runner._staged
        snap = eng.snapshot()["rollout"]
        assert snap["rolled_back"] == 1 and snap["promoted"] == 0
        assert snap["models"]["det"]["state"] == "rolled_back"
        assert snap["models"]["det"]["divergence"]["max_unmatched"] >= 1
        # and the incumbent keeps serving, byte-identical
        im = synthetic_image(999, 24, 24, 3)
        assert eng.submit(im).result(5)[1].tobytes() == \
            expected_bytes(im, 1.0)
    finally:
        eng.stop()


def test_structure_mismatch_aborts_before_device(tmp_path):
    ck = save_checkpoint(
        str(tmp_path / "misshape"),
        {"params": {"w": np.zeros((2, 2), np.float32)}}, 1,
    )
    reg = make_registry()
    runner = FakeRolloutRunner(reg)
    ctl = RolloutController(reg, runner)
    with pytest.raises(RolloutAborted) as exc:
        ctl.start("det", ck, block=True, timeout=30)
    assert exc.value.stage == "verify"
    assert not runner.warm_started.is_set()
    assert reg.live("det").version == 1
    assert ctl.rolled_back == 1
    ctl.stop()


# ------------------------------------------------ promote under load

def test_promote_under_load_zero_lost_zero_recompile(ckpts):
    """A faithful candidate promotes through the atomic flip while live
    load is in flight: zero requests lost, zero failed, and the
    candidate's split traffic added ZERO compile misses (params are a
    traced jit argument — the whole rollout reuses live signatures)."""
    reg = make_registry()
    runner = FakeRolloutRunner(reg, service_s=0.002)
    eng = ServingEngine(runner, max_linger=0.001, max_queue=64).start()
    try:
        eng.attach_rollout()
        misses0 = runner.compile_cache.misses
        N = 48
        result, waves = rollout_under_load(eng, ckpts["good"], N)
        assert result["version"] == 2 and result["previous"] == 1
        assert result["split_served"] >= 3 and result["split_errors"] == 0
        snap = eng.snapshot()
        assert snap["requests"]["failed"] == 0
        assert snap["rollout"]["promoted"] == 1
        assert reg.live("det").version == 2
        # zero steady-state recompiles across split + shadow + promote
        assert runner.compile_cache.misses == misses0
        # every response was one version's bytes, never a mixture
        served = set()
        for seed, report in waves:
            assert report["outcomes"]["ok"] == N
            assert report["outcomes"].get("error", 0) == 0
            sizes_rng = np.random.RandomState(seed)
            req_sizes = [
                SIZES[sizes_rng.randint(len(SIZES))] for _ in range(N)
            ]
            for i in range(N):
                kind, dets = report["_results"][i]
                assert kind == "ok", f"wave {seed} request {i}: {kind}"
                im = synthetic_image(i, *req_sizes[i], seed)
                got = dets[1].tobytes()
                assert got in (
                    expected_bytes(im, 1.0), expected_bytes(im, W_GOOD)
                ), f"wave {seed} request {i} served mixed-version bytes"
                served.add(got == expected_bytes(im, W_GOOD))
        assert served == {False, True}  # both versions answered the load
        # per-version metrics partition recorded both arms
        assert {"det:v1", "det:v2"} <= set(snap["versions"])
        # post-promote traffic is candidate bytes
        im = synthetic_image(7777, 24, 24, 3)
        assert eng.submit(im).result(5)[1].tobytes() == \
            expected_bytes(im, W_GOOD)
    finally:
        eng.stop()


# --------------------------------------------------- control-plane edges

def test_second_rollout_while_in_flight_rejected(ckpts):
    reg = make_registry()
    runner = FakeRolloutRunner(reg, warm_delay_s=0.15)
    ctl = RolloutController(reg, runner)
    ro = ctl.start("det", ckpts["good"], policy=fast_policy(hold_s=30.0))
    try:
        wait_for(runner.warm_started.is_set, msg="warm start")
        with pytest.raises(RolloutInProgress):
            ctl.start("det", ckpts["bad"])
    finally:
        ctl.stop()
    with pytest.raises(RolloutCancelled):
        ro.result(0)
    assert ctl.cancelled == 1
    assert reg.live("det").version == 1
    assert reg.entry("det").versions[-1].state is VersionState.RETIRED
    assert not runner._staged


def test_engine_stop_cancels_rollout(ckpts):
    reg = make_registry()
    runner = FakeRolloutRunner(reg, warm_delay_s=0.1)
    eng = ServingEngine(runner, max_linger=0.0).start()
    eng.attach_rollout()
    ro = eng.rollout.start("det", ckpts["good"],
                           policy=fast_policy(hold_s=30.0))
    wait_for(runner.warm_started.is_set, msg="warm start")
    eng.stop()
    assert ro.done()
    with pytest.raises(RolloutCancelled):
        ro.result(0)
    assert ro.thread is not None and not ro.thread.is_alive()
    assert reg.live("det").version == 1


def test_run_version_unknown_version_is_typed(ckpts):
    reg = make_registry()
    runner = FakeRolloutRunner(reg)
    runner.warmup()
    im = np.ones((24, 24, 3), np.float32)
    batch = runner.assemble([runner.make_request(im)])
    with pytest.raises(UnknownVersion):
        runner.run_version(batch, version=99)
    # version=None and version=live both serve the live tree
    a = runner.run_version(batch)["sums"]
    b = runner.run_version(batch, version=reg.live("det").version)["sums"]
    np.testing.assert_array_equal(a, b)


def test_admin_rollout_surface(ckpts):
    reg = make_registry()
    runner = FakeRolloutRunner(reg)
    eng = ServingEngine(runner, max_linger=0.0).start()
    try:
        eng.attach_rollout(policy=fast_policy(split_pct=0.0, min_compared=0,
                                              shadow=False))
        assert eng.admin("rollout status") == eng.rollout.snapshot()
        out = eng.admin(f"rollout det {ckpts['good']}")
        assert out["version"] == 2
        assert reg.live("det").version == 2
    finally:
        eng.stop()


# ------------------------------- satellite 2: bounded logs + quarantine

def test_transition_log_is_ring_bounded():
    reg = make_registry()
    ver = reg.live("det")
    for i in range(TRANSITION_LOG_MAX + 40):
        reg._transition(ver, VersionState.LIVE, f"tick {i}")
    assert len(ver.transitions) == TRANSITION_LOG_MAX
    snap = ver.snapshot()
    assert snap["transitions_dropped"] == 41  # register + 40 overflow
    # the ring kept the NEWEST entries
    assert snap["transitions"][-1]["reason"] == f"tick {TRANSITION_LOG_MAX + 39}"


def test_quarantine_suspects_ring_counts_drops():
    from mx_rcnn_tpu.serve.quarantine import QuarantineTable

    qt = QuarantineTable(k=10, ttl_s=300.0, max_suspects=4)
    for i in range(10):
        qt.note_trip([(f"digest-{i:04d}", None)])
    snap = qt.snapshot()
    # each trip purges down to max_suspects BEFORE adding its own, so
    # the table holds at most max_suspects + 1 and every overflow is
    # counted instead of silently forgotten
    assert len(snap["suspects"]) == 5
    assert snap["suspects_dropped"] == 5
    # the ring kept the NEWEST suspects
    assert "digest-0009"[:12] in snap["suspects"]
    assert "digest-0000"[:12] not in snap["suspects"]


# ------------------------------- closed loop: serve -> distill -> promote

def test_closed_loop_distilled_checkpoint_promotes(tmp_path, monkeypatch):
    """Served detections are harvested into records, ``fine_tune`` turns
    them into a rollout-ready checkpoint, and that checkpoint promotes
    under load with nothing lost.  Host-only: the two device halves of
    ``fine_tune`` (the trainer, the model's serve-time init) are stood
    in for; harvest, the JSONL round-trip, ``merge_params`` (a train-time
    tree carries subtrees the serve tree lacks), the checkpoint, the
    structure gate and the rollout are the real code."""
    import mx_rcnn_tpu.core.fit as fit_mod
    import mx_rcnn_tpu.models as models_mod
    from mx_rcnn_tpu.tools import distill

    reg = make_registry()
    runner = FakeRolloutRunner(reg, service_s=0.002)
    eng = ServingEngine(runner, max_linger=0.001, max_queue=64).start()
    try:
        eng.attach_rollout()
        first = run_load(eng, num_requests=12, concurrency=4, sizes=SIZES,
                         seed=3, collect=True)
        sizes_rng = np.random.RandomState(3)
        records = distill.harvest(
            [(first["_results"][i][1],
              SIZES[sizes_rng.randint(len(SIZES))]) for i in range(12)],
            min_score=0.5, num_classes=2,
        )
        # (a box the 16x16 images clip to nothing gives no record)
        assert 8 <= len(records) <= 12
        path = str(tmp_path / "distilled.jsonl")
        distill.write_records(records, path)

        seen = {}

        def fake_fit(model, cfg, roidb, **kw):
            seen["records"] = len(roidb)
            return {**params_tree(W_GOOD),
                    "sampling_head": np.zeros(3, np.float32)}

        class ServeInit:
            def init(self, *a, train, **kw):
                assert train is False
                return {"params": params_tree(0.0)}

        monkeypatch.setattr(fit_mod, "fit", fake_fit)
        monkeypatch.setattr(models_mod, "build_model", lambda cfg: ServeInit())
        ck = distill.fine_tune(distill.read_records(path),
                               out_dir=str(tmp_path / "loop"))
        assert seen["records"] == len(records)

        result, waves = rollout_under_load(eng, ck)
        assert result["version"] == 2 and reg.live("det").version == 2
        for _seed, report in waves:
            assert report["outcomes"]["ok"] == 48
        assert eng.snapshot()["requests"]["failed"] == 0
        im = synthetic_image(4242, 24, 24, 3)
        assert eng.submit(im).result(5)[1].tobytes() == \
            expected_bytes(im, W_GOOD)
    finally:
        eng.stop()


# -------------------------------------- closed loop: distill round-trip

def test_distill_record_schema_roundtrips_through_loader(tmp_path):
    """Harvested records must be indistinguishable from
    ``SyntheticDataset.gt_roidb`` output: same keys, same dtypes, and
    the REAL training loader must batch them."""
    import dataclasses

    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.data.loader import TrainLoader
    from mx_rcnn_tpu.data.synthetic import SyntheticDataset
    from mx_rcnn_tpu.tools.distill import (
        harvest,
        read_records,
        record_from_detections,
        write_records,
    )

    # one response with mixed quality: low-score dropped, degenerate
    # box dropped, out-of-range class dropped, good boxes clipped
    dets = [
        None,
        np.array([[10, 10, 60, 70, 0.9], [5, 5, 6, 6, 0.95],
                  [0, 0, 30, 40, 0.2]], np.float32),
        np.array([[-20, 15, 90, 200, 0.8]], np.float32),
        np.array([[40, 40, 100, 100, 0.99]], np.float32),  # class 3
    ]
    rec = record_from_detections(dets, 128, 128, index=0, min_score=0.5,
                                 seed=5, num_classes=3)
    assert rec["gt_classes"].tolist() == [1, 2]  # class 3 dropped
    assert rec["boxes"].dtype == np.float32
    assert rec["gt_classes"].dtype == np.int32
    assert float(rec["boxes"].max()) <= 127.0 and float(rec["boxes"].min()) >= 0.0
    ref = SyntheticDataset(num_images=1, num_classes=4,
                           image_size=(128, 128)).gt_roidb()[0]
    assert set(rec) == set(ref)
    for k in ref:
        assert type(rec[k]) is type(ref[k]), k

    # nothing confident → no record
    assert record_from_detections([None, np.zeros((0, 5), np.float32)],
                                  128, 128, index=1) is None

    # unique URIs + seeds per record: the loader's render cache keys on
    # (image, flipped, seed), so two distilled records must never alias
    responses = [(dets, (128, 128))] * 4
    records = harvest(responses, min_score=0.5, seed=5, num_classes=3)
    assert len(records) == 4
    assert len({r["image"] for r in records}) == 4
    assert len({r["synthetic_seed"] for r in records}) == 4

    # JSONL round-trip is exact
    path = str(tmp_path / "distilled.jsonl")
    assert write_records(records, path) == 4
    back = read_records(path)
    for a, b in zip(records, back):
        assert set(a) == set(b)
        np.testing.assert_array_equal(a["boxes"], b["boxes"])
        np.testing.assert_array_equal(a["gt_classes"], b["gt_classes"])
        assert b["boxes"].dtype == np.float32
        assert b["gt_classes"].dtype == np.int32

    # the REAL loader batches them
    cfg = generate_config("resnet50", "PascalVOC")
    cfg = cfg.replace(
        SHAPE_BUCKETS=((128, 128),),
        dataset=dataclasses.replace(
            cfg.dataset, NUM_CLASSES=4, SCALES=((128, 128),), MAX_GT_BOXES=8
        ),
    )
    loader = TrainLoader(back, cfg, 2, shuffle=False, prefetch=0)
    batches = list(loader)
    assert len(batches) == 2
    for b in batches:
        assert b["gt_boxes"].shape[0] == 2
        assert (b["gt_boxes"][:, :, 4] > 0).any()
