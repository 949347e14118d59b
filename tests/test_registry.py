"""Fault matrix for the model registry + hot-swap pipeline (ISSUE 7),
CPU-only and fast.

Same philosophy as ``tests/test_replica.py``: every test drives the REAL
``ModelRegistry`` / ``SwapController`` / engine machinery — including
real orbax checkpoints through ``core/checkpoint.py``'s manifest gate —
and only the predict path is a numpy stub (:class:`FakeSwapRunner`)
whose "detections" are a pure deterministic digest of the batch pixels
AND the live params, so a version swap is visible in every result byte
and a request served by the wrong version shows up as a digest mismatch,
not a flake.

The invariants under test are the ISSUE 7 acceptance criteria: a swap
under load loses zero requests and requests served entirely before
(after) the swap window are byte-identical to an all-v1 (all-v2) run; an
injected verify/warm/canary failure rolls back to the previous LIVE
version with the candidate retired and its staged buffers discarded;
``stop(drain=True)`` during an in-flight swap cancels it cleanly (no
warm work after stop returns); and two model families share one batcher
with per-(model, bucket) compile accounting.
"""

import os
import shutil
import threading
import time

import numpy as np
import pytest

from mx_rcnn_tpu.core.checkpoint import (
    CheckpointCorrupt,
    restore_tree,
    save_checkpoint,
    verify_manifest,
)
from mx_rcnn_tpu.serve.batcher import Request
from mx_rcnn_tpu.serve.buckets import BucketLadder, CompileCache
from mx_rcnn_tpu.serve.engine import ServingEngine
from mx_rcnn_tpu.serve.loadgen import run_load
from mx_rcnn_tpu.serve.registry import (
    ModelRegistry,
    SwapCancelled,
    SwapInProgress,
    SwapRolledBack,
    UnknownModel,
    VersionState,
)
from mx_rcnn_tpu.serve.replica import HealthPolicy, Replica, ReplicaState
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

FAST = HealthPolicy(
    stall_timeout=0.3,
    fail_threshold=2,
    breaker_backoff=0.05,
    breaker_max_backoff=0.2,
    flap_window=10.0,
)


def params_tree(w: float):
    """A registry params tree: one scalar leaf that changes per version
    (structure/shape/dtype identical, so the swap signature gate passes)."""
    return {"w": np.array([w], np.float32)}


def _digest(images: np.ndarray, w: float) -> np.ndarray:
    """Per-slot digest, a pure function of the slot pixels and the live
    version's ``w`` — the single computation shared by the fake's predict
    and the tests' expectations, so comparisons are byte-exact."""
    im = images.astype(np.float64)
    return np.stack(
        [
            im.sum(axis=(1, 2, 3)) * (1.0 + w),
            (im * im).sum(axis=(1, 2, 3)) + w,
        ],
        axis=1,
    )


class FakeSwapRunner:
    """Registry-backed runner stub implementing the full swap target
    surface (``warm_version`` / ``canary`` / ``discard_version``) with
    the real sync semantics: predict resolves the registry's live
    pointer per batch, adopting a staged tree on version mismatch."""

    def __init__(self, registry, index: int = 0, service_s: float = 0.0,
                 warm_delay_s: float = 0.0):
        self.registry = registry
        self.default_model = registry.default_model
        self.index = index
        self.service_s = service_s
        self.warm_delay_s = warm_delay_s
        self.ladder = BucketLadder(LADDER)
        self.max_batch = 2
        self.cfg = None
        self.compile_cache = CompileCache()
        self.served_buckets = {}
        self.swaps_applied = 0
        self.warm_started = threading.Event()
        self.warm_rungs_done = 0
        self.warmed_plan = None  # what the last warmup() actually warmed
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
            self.swaps_applied += 1

    # ---- runner facade (same shapes as tests/test_replica.FakeRunner)
    def warmup(self, buckets=None, models=None) -> int:
        if isinstance(buckets, dict):
            per = {m: sorted(bs) for m, bs in buckets.items() if bs}
            if not per:
                per = {m: list(self.ladder)
                       for m in self.registry.model_ids()}
        elif buckets is not None:
            per = {m: sorted(buckets)
                   for m in (models or [self.default_model])}
        else:
            per = {m: list(self.ladder)
                   for m in (models or self.registry.model_ids())}
        self.warmed_plan = {m: list(bs) for m, bs in per.items()}
        for m, rungs in per.items():
            self._sync(m)
            for bh, bw in rungs:
                self.compile_cache.record(
                    (m, (self.max_batch, bh, bw, 3), "f32")
                )
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
        mid = requests[0].model
        if any(r.model != mid for r in requests):
            raise ValueError("mixed models in one batch")
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

    def run(self, batch, model=None):
        mid = self._mid(model)
        self._sync(mid)
        if self.service_s:
            time.sleep(self.service_s)
        self.compile_cache.record((mid, batch["images"].shape, "f32"))
        w = float(np.asarray(self._params[mid]["w"]).ravel()[0])
        self.served_buckets.setdefault(mid, set()).add(
            tuple(batch["images"].shape[1:3])
        )
        return {"digest": _digest(batch["images"], w)}

    def detections_for(self, out, batch, index, orig_hw=None, thresh=None,
                       model=None):
        return [out["digest"][index].copy()]

    # ---- swap target surface
    def warm_version(self, model, version, params, buckets=None, abort=None):
        mid = self._mid(model)
        self.warm_started.set()
        if abort is not None:
            abort()
        if buckets is None:
            buckets = sorted(self.served_buckets.get(mid, ())) or list(
                self.ladder
            )
        warmed = 0
        for _ in buckets:
            if abort is not None:
                abort()
            if self.warm_delay_s:
                time.sleep(self.warm_delay_s)
            warmed += 1
            self.warm_rungs_done += 1
        self._staged[(mid, int(version))] = params
        return warmed

    def canary(self, model=None):
        mid = self._mid(model)
        served = sorted(self.served_buckets.get(mid, ()))
        bh, bw = served[0] if served else next(iter(self.ladder))
        batch = {
            "images": np.zeros((self.max_batch, bh, bw, 3), np.float32),
            "im_info": np.tile(
                np.array([bh, bw, 1.0], np.float32), (self.max_batch, 1)
            ),
            "orig_hw": np.tile(
                np.array([bh, bw], np.float32), (self.max_batch, 1)
            ),
        }
        self.run(batch, model=None if mid == self.default_model else mid)
        return 1

    def discard_version(self, model, version):
        self._staged.pop((self._mid(model), int(version)), None)


def make_registry(models=(("det", 1.0),)):
    reg = ModelRegistry()
    for mid, w in models:
        reg.register(mid, model=None, cfg=None, params=params_tree(w))
    return reg


def expected(im: np.ndarray, w: float) -> np.ndarray:
    bh, bw = BucketLadder(LADDER).select(*im.shape[:2])
    canvas = np.zeros((bh, bw, 3), np.float32)
    canvas[: im.shape[0], : im.shape[1]] = im
    return _digest(canvas[None], w)[0]


def wait_for(pred, timeout=5.0, msg="condition"):
    t_end = time.monotonic() + timeout
    while time.monotonic() < t_end:
        if pred():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {msg}")


@pytest.fixture
def no_faults(monkeypatch):
    monkeypatch.delenv(faults.ENV_VAR, raising=False)
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(scope="module")
def ckpts(tmp_path_factory):
    """Two committed orbax dumps with the registry tree shape
    (``{"params": {"w": ...}}``): the v2 and v3 swap candidates."""
    root = tmp_path_factory.mktemp("registry-ckpts")
    out = {}
    for name, w in (("v2", 2.0), ("v3", 3.0)):
        out[name] = save_checkpoint(
            str(root / name), {"params": params_tree(w)}, 1
        )
    return out


# --------------------------------------------------- verify_manifest gate

def test_verify_manifest_matrix(tmp_path, no_faults):
    good = save_checkpoint(str(tmp_path / "ok"), {"params": params_tree(5.0)}, 1)
    man = verify_manifest(good)
    assert man["checksum"] and man["files"]
    # the no-reload fast path agrees with the self-restoring path
    assert verify_manifest(good, tree=restore_tree(good)) == man

    # missing manifest
    nomani = str(tmp_path / "nomani")
    shutil.copytree(good, nomani)
    os.remove(os.path.join(nomani, "manifest.json"))
    with pytest.raises(CheckpointCorrupt, match="manifest"):
        verify_manifest(nomani)

    # truncated data file (size disagrees with the manifest record)
    trunc = str(tmp_path / "trunc")
    shutil.copytree(good, trunc)
    rel = next(iter(verify_manifest(good)["files"]))
    with open(os.path.join(trunc, rel), "ab") as f:
        f.write(b"x")
    with pytest.raises(CheckpointCorrupt, match="truncated"):
        verify_manifest(trunc)

    # checksum tampered: files intact, digest disagrees
    bad = str(tmp_path / "badsum")
    shutil.copytree(good, bad)
    import json

    mpath = os.path.join(bad, "manifest.json")
    with open(mpath) as f:
        m = json.load(f)
    m["checksum"] = "0" * 64
    with open(mpath, "w") as f:
        json.dump(m, f)
    with pytest.raises(CheckpointCorrupt, match="checksum"):
        verify_manifest(bad)


# ------------------------------------------------------- fault grammar

def test_swap_fault_grammar_and_hook(monkeypatch):
    specs = faults._parse("swap_verify_fail@1,canary_fail@*,swap_warm_fail@2")
    assert specs[0].key == 1 and specs[1].key is None and specs[2].key == 2
    monkeypatch.setenv(faults.ENV_VAR, "swap_warm_fail@2x1,canary_fail@*")
    faults.reset()
    faults.swap_fault("warm", 1)        # wrong ordinal: no-op
    with pytest.raises(faults.InjectedSwapFault):
        faults.swap_fault("warm", 2)
    faults.swap_fault("warm", 2)        # x1: exhausted
    for ordinal in (1, 7):              # wildcard matches every swap
        with pytest.raises(faults.InjectedSwapFault):
            faults.swap_fault("canary", ordinal)
    faults.reset()


# ------------------------------------------------------ swap happy path

def test_swap_under_load_zero_lost_and_byte_identical(no_faults, ckpts):
    reg = make_registry()
    runner = FakeSwapRunner(reg, service_s=0.002)
    eng = ServingEngine(runner, max_linger=0.001, max_queue=64).start()
    try:
        N = 60
        waves = []  # (seed, report)
        swapped = threading.Event()

        def load():
            # wave after wave until one has been sent WHOLE after the swap
            # returned: a fixed count can be over before the swap lands,
            # and then nothing is "post"
            seed = 7
            while True:
                last = swapped.is_set()
                waves.append((seed, run_load(
                    eng, num_requests=N, concurrency=4, sizes=SIZES,
                    seed=seed, collect=True,
                )))
                if last:
                    return
                seed += 1

        t = threading.Thread(target=load)
        t.start()
        try:
            wait_for(lambda: eng.metrics.completed >= N // 4,
                     msg="mid-load")
            t_sw0 = time.monotonic()
            result = eng.swap("det", ckpts["v2"], block=True, timeout=30)
            t_sw1 = time.monotonic()
        finally:
            swapped.set()
            t.join(timeout=60)
        assert not t.is_alive()

        assert result["model"] == "det" and result["version"] == 2
        assert result["previous"] == 1 and result["warmed"] >= 1
        snap = eng.snapshot()
        assert snap["requests"]["failed"] == 0
        assert snap["registry"]["swaps"]["completed"] == 1
        assert snap["registry"]["models"]["det"]["live_version"] == 2
        assert runner.swaps_applied >= 2  # initial slot sync + the swap

        # classify by the per-request submit/done timestamps: entirely
        # before the swap started → v1 bytes; submitted after the swap
        # returned → v2 bytes; straddling → exactly one of the two
        # (exactly-once: never a mixture, never a loss)
        from mx_rcnn_tpu.serve.loadgen import synthetic_image

        pre = post = straddle = 0
        for seed, report in waves:
            assert report["outcomes"]["ok"] == N
            assert report["outcomes"]["error"] == 0
            sizes_rng = np.random.RandomState(seed)
            req_sizes = [
                SIZES[sizes_rng.randint(len(SIZES))] for _ in range(N)
            ]
            for i in range(N):
                kind, dets = report["_results"][i]
                assert kind == "ok", f"wave {seed} request {i}: {kind}"
                got = dets[0].tobytes()
                h, w = req_sizes[i]
                im = synthetic_image(i, h, w, seed)
                v1 = expected(im, 1.0).tobytes()
                v2 = expected(im, 2.0).tobytes()
                t_submit, t_done = report["_times"][i]
                if t_done <= t_sw0:
                    assert got == v1, f"pre-swap request {i} not v1 bytes"
                    pre += 1
                elif t_submit >= t_sw1:
                    assert got == v2, f"post-swap request {i} not v2 bytes"
                    post += 1
                else:
                    assert got in (v1, v2), f"straddling request {i} mixed"
                    straddle += 1
        assert pre > 0 and post > 0, (pre, straddle, post)
        # retired v1 released its params (PR 4 free-the-retired discipline)
        v1_ver = reg.entry("det").versions[0]
        assert v1_ver.state is VersionState.RETIRED and v1_ver.params is None
        assert snap["registry"]["versions_released"] == 1
    finally:
        eng.stop()


def test_swap_is_zero_compile_and_admin_surface(no_faults, ckpts):
    reg = make_registry()
    runner = FakeSwapRunner(reg)
    eng = ServingEngine(runner, max_linger=0.0).start()
    try:
        misses0 = runner.compile_cache.misses
        assert misses0 == len(LADDER)
        fut = eng.submit(np.ones((24, 24, 3), np.float32))
        np.testing.assert_array_equal(
            fut.result(5)[0], expected(np.ones((24, 24, 3), np.float32), 1.0)
        )
        out = eng.admin(f"swap det {ckpts['v2']}")
        assert out["version"] == 2
        # post-swap traffic hits only already-recorded signatures
        fut = eng.submit(np.ones((24, 24, 3), np.float32))
        np.testing.assert_array_equal(
            fut.result(5)[0], expected(np.ones((24, 24, 3), np.float32), 2.0)
        )
        assert runner.compile_cache.misses == misses0
        models = eng.admin("models")
        assert models["models"]["det"]["live_version"] == 2
        with pytest.raises(ValueError):
            eng.admin("bogus cmd")
    finally:
        eng.stop()


# ------------------------------------------------------ rollback matrix

@pytest.mark.parametrize(
    "kind,stage",
    [
        ("swap_verify_fail", "verify"),
        ("swap_warm_fail", "warm"),
        ("canary_fail", "canary"),
    ],
)
def test_injected_fault_rolls_back_to_previous_live(
    monkeypatch, ckpts, kind, stage
):
    monkeypatch.setenv(faults.ENV_VAR, f"{kind}@1")
    faults.reset()
    try:
        reg = make_registry()
        runner = FakeSwapRunner(reg)
        eng = ServingEngine(runner, max_linger=0.0).start()
        try:
            im = np.ones((24, 24, 3), np.float32)
            np.testing.assert_array_equal(
                eng.submit(im).result(5)[0], expected(im, 1.0)
            )
            with pytest.raises(SwapRolledBack) as exc:
                eng.swap("det", ckpts["v2"], block=True, timeout=30)
            assert exc.value.stage == stage
            assert isinstance(exc.value.cause, faults.InjectedSwapFault)
            # previous LIVE still serves, byte-identical
            assert reg.live("det").version == 1
            np.testing.assert_array_equal(
                eng.submit(im).result(5)[0], expected(im, 1.0)
            )
            # candidate retired + released; staged buffers discarded
            cand = reg.entry("det").versions[1]
            assert cand.state is VersionState.RETIRED and cand.params is None
            assert not runner._staged
            snap = reg.snapshot()
            assert snap["swaps"]["rolled_back"] == 1
            assert snap["swaps"]["completed"] == 0
            assert not snap["models"]["det"]["swap_in_flight"]
            # the registry is not wedged: swap #2 (no fault keyed) lands
            result = eng.swap("det", ckpts["v3"], block=True, timeout=30)
            assert result["version"] == 3 and reg.live("det").version == 3
            np.testing.assert_array_equal(
                eng.submit(im).result(5)[0], expected(im, 3.0)
            )
        finally:
            eng.stop()
    finally:
        faults.reset()


def test_corrupt_checkpoint_rolls_back_at_verify(no_faults, tmp_path, ckpts):
    bad = str(tmp_path / "bad")
    shutil.copytree(ckpts["v2"], bad)
    os.remove(os.path.join(bad, "manifest.json"))
    reg = make_registry()
    runner = FakeSwapRunner(reg)
    ctrl = reg.swap("det", bad, target=runner)
    with pytest.raises(SwapRolledBack) as exc:
        ctrl.result(30)
    assert isinstance(exc.value.cause, CheckpointCorrupt)
    assert reg.live("det").version == 1


def test_structure_mismatch_rejected_before_device(no_faults, tmp_path):
    # candidate with a DIFFERENT tree shape: the signature gate must
    # refuse it (a swap is never allowed to force a recompile)
    ck = save_checkpoint(
        str(tmp_path / "misshape"),
        {"params": {"w": np.zeros((2, 2), np.float32)}}, 1,
    )
    reg = make_registry()
    runner = FakeSwapRunner(reg)
    with pytest.raises(SwapRolledBack, match="verify"):
        reg.swap("det", ck, target=runner, block=True, timeout=30)
    assert not runner.warm_started.is_set()  # never reached the target
    assert reg.live("det").version == 1


def test_second_swap_while_in_flight_rejected(no_faults, ckpts):
    reg = make_registry()
    runner = FakeSwapRunner(reg, warm_delay_s=0.15)
    ctrl = reg.swap("det", ckpts["v2"], target=runner)
    try:
        wait_for(runner.warm_started.is_set, msg="warm start")
        with pytest.raises(SwapInProgress):
            reg.swap("det", ckpts["v3"], target=runner)
    finally:
        ctrl.result(30)
    assert reg.live("det").version == 2
    assert reg.snapshot()["swaps"]["started"] == 1


# -------------------------------------------------------- stop interlock

def test_stop_during_swap_cancels_cleanly(no_faults, ckpts):
    reg = make_registry()
    runner = FakeSwapRunner(reg, warm_delay_s=0.1)
    eng = ServingEngine(runner, max_linger=0.0).start()
    ctrl = eng.swap("det", ckpts["v2"])
    wait_for(runner.warm_started.is_set, msg="warm start")
    eng.stop(drain=True)
    # the interlock waited for the controller thread: no orphaned warmup
    assert ctrl.done() and not ctrl._thread.is_alive()
    with pytest.raises(SwapCancelled):
        ctrl.result(0)
    assert reg.swaps_in_flight() == 0
    snap = reg.snapshot()
    assert snap["swaps"]["cancelled"] == 1
    assert reg.live("det").version == 1
    cand = reg.entry("det").versions[1]
    assert cand.state is VersionState.RETIRED
    assert not runner._staged
    # no warm work lands after stop returns (the no-post-stop-device_put
    # contract: abort raises before each rung's placement)
    done_at_stop = runner.warm_rungs_done
    time.sleep(0.3)
    assert runner.warm_rungs_done == done_at_stop


# ------------------------------------------------------------- tenancy

def test_multi_model_routing_isolation(no_faults, ckpts):
    reg = make_registry((("alpha", 1.0), ("beta", 10.0)))
    runner = FakeSwapRunner(reg)
    eng = ServingEngine(runner, max_linger=0.001).start()
    try:
        # cold start: per-(model, bucket) signatures, once each
        assert runner.compile_cache.misses == 2 * len(LADDER)
        im = np.ones((24, 24, 3), np.float32)
        futs = {
            ("alpha", i): eng.submit(im, model="alpha") for i in range(3)
        }
        futs.update(
            {("beta", i): eng.submit(im, model="beta") for i in range(3)}
        )
        fut_default = eng.submit(im)  # model-less → default (first) family
        for (mid, _), f in futs.items():
            np.testing.assert_array_equal(
                f.result(5)[0], expected(im, 1.0 if mid == "alpha" else 10.0)
            )
        np.testing.assert_array_equal(
            fut_default.result(5)[0], expected(im, 1.0)
        )
        # steady state: no new signatures from either family
        assert runner.compile_cache.misses == 2 * len(LADDER)
        with pytest.raises(UnknownModel):
            eng.submit(im, model="gamma")
        snap = eng.snapshot()
        assert snap["requests"]["rejected"] == 1
        assert snap["models"]["alpha"]["completed"] == 3
        assert snap["models"]["beta"]["completed"] == 3

        # swapping beta must not move alpha: alpha bytes unchanged,
        # beta bytes flip to the candidate's params
        out = eng.swap("beta", ckpts["v2"], block=True, timeout=30)
        assert out["model"] == "beta" and out["version"] == 2
        np.testing.assert_array_equal(
            eng.submit(im, model="alpha").result(5)[0], expected(im, 1.0)
        )
        np.testing.assert_array_equal(
            eng.submit(im, model="beta").result(5)[0], expected(im, 2.0)
        )
        assert reg.live("alpha").version == 1
        assert reg.live("beta").version == 2
    finally:
        eng.stop()


def test_batcher_never_mixes_models(no_faults):
    reg = make_registry((("alpha", 1.0), ("beta", 10.0)))
    runner = FakeSwapRunner(reg)
    a = runner.make_request(np.ones((24, 24, 3), np.float32), model="alpha")
    b = runner.make_request(np.ones((24, 24, 3), np.float32), model="beta")
    with pytest.raises(ValueError, match="mixed models"):
        runner.assemble([a, b])
    from mx_rcnn_tpu.serve.batcher import DynamicBatcher

    batcher = DynamicBatcher(max_batch=2, max_linger=0.0)
    batcher.submit(a)
    batcher.submit(b)
    first = batcher.next_batch()
    second = batcher.next_batch()
    assert len(first) == 1 and len(second) == 1
    assert {first[0].model, second[0].model} == {"alpha", "beta"}


# ------------------------------------------- per-bucket warm partitioning

def test_recovery_rewarms_only_served_buckets(no_faults):
    reg = make_registry()
    built = []

    def factory(index):
        r = FakeSwapRunner(reg, index=index)
        built.append(r)
        return r

    rep = Replica(0, factory, policy=FAST)
    try:
        wait_for(lambda: rep.state is ReplicaState.HEALTHY, msg="warm")
        # traffic on ONE rung only
        im = np.ones((24, 24, 3), np.float32)
        runner0 = rep.runner
        batch = runner0.assemble([runner0.make_request(im)])
        rep.submit(batch).future.result(5)
        assert runner0.served_buckets == {"det": {(32, 32)}}
        rep.drain()
        wait_for(
            lambda: rep.state is ReplicaState.HEALTHY and len(built) == 2,
            msg="rejoin",
        )
        # the rebuilt runner warmed exactly the served partition
        assert built[1].warmed_plan == {"det": [(32, 32)]}
        assert rep.partial_rewarms == 1 and rep.last_rewarm_rungs == 1
        # an un-served rung still works (lazy warm on first dispatch)
        im2 = np.ones((32, 48, 3), np.float32)
        batch2 = rep.runner.assemble([rep.runner.make_request(im2)])
        d = rep.submit(batch2)
        np.testing.assert_array_equal(
            rep.runner.detections_for(d.future.result(5), batch2, 0)[0],
            expected(im2, 1.0),
        )
    finally:
        rep.stop()


# --------------------------------------------------------- observability

def test_registry_snapshot_and_transition_log(no_faults, ckpts):
    reg = make_registry()
    runner = FakeSwapRunner(reg)
    runner.warmup()
    result = reg.swap("det", ckpts["v2"], target=runner, block=True,
                      timeout=30)
    assert result["digest"]  # manifest checksum rode along
    snap = reg.snapshot()
    det = snap["models"]["det"]
    assert det["live_version"] == 2
    states = [v["state"] for v in det["versions"]]
    assert states == ["retired", "live"]
    v2 = det["versions"][1]
    walk = [t["to"] for t in v2["transitions"]]
    assert walk == ["verifying", "warming", "live"]
    assert det["versions"][0]["released"] is True
    assert snap["versions_released"] == 1
    assert snap["swaps"] == {
        "started": 1, "completed": 1, "rolled_back": 0, "cancelled": 0,
        "in_flight": 0,
    }
