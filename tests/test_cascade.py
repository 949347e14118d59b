"""Chaos matrix for the confidence-gated cascade (ISSUE 18), CPU-only
and fast.

Same philosophy as ``tests/test_rollout.py``: every test drives the
REAL ``ServingEngine`` / ``CascadeRouter`` / ``ModelRegistry`` /
``ResponseCache`` machinery and only the predict path is a numpy stub
(:class:`CascadeStub`) whose "detections" are a pure deterministic
function of the batch pixels, the family, and the serving version's
``w`` — so which family/version produced a response is visible in
every coordinate byte.  First-pass confidence is steered by the image
fill: an "easy" image scores 0.9 on the cheap family (ships), a
"hard" one 0.2 (escalates), and the flagship always scores 0.95.

The invariants under test are the ISSUE 18 acceptance criteria: the
gate is deterministic and pure-host; escalation preserves the
request's lane/tenant/deadline identity; the response cache never
crosses (family, precision, arm) keys; 100% escalation is
byte-identical to flagship-only serving; and the cascade composes
with the rest of the serve stack's chaos — poison-mixed traffic,
hot-swaps of the cheap family, and an active flagship rollout split.
Every test runs with the lock-order checker armed (graftlint R4's
runtime counterpart).
"""

import time

import numpy as np
import pytest

from mx_rcnn_tpu.core.checkpoint import save_checkpoint
from mx_rcnn_tpu.serve.batcher import Request
from mx_rcnn_tpu.serve.buckets import BucketLadder, CompileCache
from mx_rcnn_tpu.serve.cascade import (
    CascadePolicy,
    CascadeRouter,
    detection_stats,
    parse_cascade_spec,
)
from mx_rcnn_tpu.serve.engine import ServingEngine
from mx_rcnn_tpu.serve.quarantine import (
    InvalidRequest,
    QuarantineTable,
    RetriesExhausted,
    request_digest,
)
from mx_rcnn_tpu.serve.registry import ModelRegistry, UnknownModel, UnknownVersion
from mx_rcnn_tpu.serve.respcache import ResponseCache
from mx_rcnn_tpu.serve.rollout import RolloutPolicy, assign_arm


@pytest.fixture(autouse=True)
def _lock_order_check(monkeypatch):
    from mx_rcnn_tpu.analysis import lockcheck

    monkeypatch.setenv("MX_RCNN_LOCK_CHECK", "1")
    lockcheck.reset()
    yield


LADDER = ((32, 32),)

# image fills steering the stub's confidence (canvas sums at 24x24):
# easy ~173 -> cheap scores 0.9, hard ~8640 -> cheap scores 0.2,
# poison ~51840 -> the predict itself raises (query of death)
HARD_SUM = 1000.0
POISON_SUM = 20000.0


def fill_image(value: float, size=(24, 24)) -> np.ndarray:
    return np.full((*size, 3), value, np.float32)


def easy_image(i: int = 0) -> np.ndarray:
    im = fill_image(0.1)
    im[0, 0, 0] = 0.1 + i * 1e-3  # unique content, still easy
    return im


def hard_image(i: int = 0) -> np.ndarray:
    im = fill_image(5.0)
    im[0, 0, 0] = 5.0 + i * 1e-3
    return im


def params_tree(w: float):
    return {"w": np.array([w], np.float32)}


class CascadeStub:
    """Registry-backed runner stub for the cascade matrix.

    Detections are ``[None, box]`` with box x-corner
    ``1 + 50*(family is flagship) + (w - 1) * 10`` — family AND serving
    version visible in the bytes — and a score that is a pure function
    of (family, image hardness).  ``run_version`` serves a staged tree
    without touching the live slot (the rollout candidate-arm path) and
    a poison-fill slot raises from ``run`` itself (the containment
    path)."""

    def __init__(self, registry):
        self.registry = registry
        self.default_model = registry.default_model
        self.ladder = BucketLadder(LADDER)
        self.max_batch = 1
        self.cfg = None
        self.compile_cache = CompileCache()
        self.calls = {}
        self._staged = {}

    def warmup(self) -> int:
        return 0

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
        return {"images": np.stack([r.image for r in requests])}

    def _predict(self, batch, mid, w):
        sums = batch["images"].astype(np.float64).sum(axis=(1, 2, 3))
        if float(sums.max()) > POISON_SUM:
            raise RuntimeError("injected poison predict failure")
        self.calls[mid] = self.calls.get(mid, 0) + 1
        self.compile_cache.record((mid, batch["images"].shape, "f32"))
        return {"sums": sums, "mid": mid, "w": w}

    def run(self, batch, model=None):
        mid = model or self.default_model
        w = float(np.asarray(self.registry.live(mid).params["w"]).ravel()[0])
        return self._predict(batch, mid, w)

    def run_version(self, batch, model=None, version=None):
        mid = model or self.default_model
        live = self.registry.live(mid)
        if version is None or int(version) == live.version:
            return self.run(batch, model=mid)
        staged = self._staged.get((mid, int(version)))
        if staged is None:
            raise UnknownVersion(
                f"model {mid!r} v{int(version)} is neither live nor staged"
            )
        w = float(np.asarray(staged["w"]).ravel()[0])
        return self._predict(batch, mid, w)

    def detections_for(self, out, batch, index, orig_hw=None, thresh=None,
                       model=None):
        mid = out["mid"]
        hard = float(out["sums"][index]) > HARD_SUM
        score = 0.95 if mid == "flag" else (0.2 if hard else 0.9)
        x = 1.0 + (50.0 if mid == "flag" else 0.0) + (out["w"] - 1.0) * 10.0
        return [
            None,
            np.array([[x, 2.0, x + 10.0, 12.0, score]], np.float32),
        ]

    # ---- swap / rollout target surface
    def warm_version(self, model, version, params, buckets=None, abort=None):
        self._staged[(model, int(version))] = params
        return 1

    def canary(self, model=None):
        return 1

    def discard_version(self, model, version):
        self._staged.pop((model, int(version)), None)


def make_registry(w_cheap: float = 1.0, w_flag: float = 1.0):
    reg = ModelRegistry()
    reg.register("cheap", model=None, cfg=None, params=params_tree(w_cheap))
    reg.register("flag", model=None, cfg=None, params=params_tree(w_flag))
    return reg


def make_engine(reg=None, cache=None, **kw):
    reg = reg if reg is not None else make_registry()
    runner = CascadeStub(reg)
    eng = ServingEngine(runner, max_linger=0.0, response_cache=cache, **kw)
    return eng, runner


def served_x(dets) -> float:
    """The box x-corner: which (family, version) produced these bytes."""
    return float(dets[1][0, 0])


POLICY = {"cheap": "cheap", "flagship": "flag", "min_score": 0.5}


# ---------------------------------------------------------- policy + gate

class TestPolicyAndGate:
    def test_policy_validation(self):
        with pytest.raises(ValueError, match="must differ"):
            CascadePolicy(cheap="m", flagship="m")
        with pytest.raises(ValueError, match="both"):
            CascadePolicy(cheap="", flagship="m")
        with pytest.raises(ValueError, match="min_dets"):
            CascadePolicy(cheap="a", flagship="b", min_dets=-1)

    def test_spec_parsing(self):
        p = parse_cascade_spec("small>big")
        assert (p.cheap, p.flagship, p.min_score) == ("small", "big", 0.5)
        p = parse_cascade_spec("c4_small>flagship:0.65")
        assert (p.cheap, p.flagship, p.min_score) == (
            "c4_small", "flagship", 0.65,
        )
        with pytest.raises(ValueError, match="CHEAP>FLAGSHIP"):
            parse_cascade_spec("no-arrow")

    def test_detection_stats_over_clsdets_shapes(self):
        assert detection_stats(None) == (0, 0.0)
        assert detection_stats([None, np.zeros((0, 5))]) == (0, 0.0)
        dets = [
            None,
            np.array([[0, 0, 1, 1, 0.3], [0, 0, 1, 1, 0.8]], np.float32),
            np.array([[0, 0, 1, 1, 0.6]], np.float32),
        ]
        n, mx = detection_stats(dets)
        assert n == 3 and mx == pytest.approx(0.8)

    def test_gate_deterministic_and_counted(self):
        r = CascadeRouter(CascadePolicy(**POLICY))
        dets = [None, np.array([[0, 0, 1, 1, 0.9]], np.float32)]
        assert all(r.sufficient(dets) for _ in range(3))
        assert not r.sufficient([None, np.zeros((0, 5), np.float32)])
        snap = r.snapshot()
        assert snap["first_pass"] == 4
        assert snap["first_pass_sufficient"] == 3
        assert snap["escalations"] == 1
        assert snap["escalation_rate"] == 0.25

    def test_min_dets_requires_confidently_nonempty(self):
        r = CascadeRouter(CascadePolicy(cheap="a", flagship="b",
                                        min_score=0.0, min_dets=1))
        assert not r.sufficient([None])  # empty pass must escalate
        assert r.sufficient([None, np.array([[0, 0, 1, 1, 0.1]], np.float32)])


# ------------------------------------------------------- engine routing

class TestEngineCascade:
    def test_attach_rejects_unregistered_family(self):
        eng, _ = make_engine()
        with pytest.raises(UnknownModel, match="ghost"):
            eng.attach_cascade({"cheap": "ghost", "flagship": "flag"})

    def test_easy_ships_cheap_hard_escalates(self):
        eng, runner = make_engine()
        with eng:
            eng.attach_cascade(POLICY)
            assert served_x(eng.submit(easy_image(), model="flag").result(5)) \
                == 1.0
            assert served_x(eng.submit(hard_image(), model="flag").result(5)) \
                == 51.0
            snap = eng.snapshot()
        assert snap["cascade"]["first_pass"] == 2
        assert snap["cascade"]["first_pass_sufficient"] == 1
        assert snap["cascade"]["escalations"] == 1
        assert snap["requests"]["escalations"] == 1
        assert snap["requests"]["first_pass_sufficient"] == 1
        # the escalated request ran BOTH families; the easy one only cheap
        assert runner.calls == {"cheap": 2, "flag": 1}
        # e2e accounting spans both passes as ONE completed request each
        assert snap["requests"]["completed"] == 2
        assert snap["requests"]["submitted"] == 2

    def test_cascade_adds_no_compile_signature(self):
        """A first pass is a cheap-family batch and an escalation a
        flagship batch on the bucket both already have: one signature a
        (family, bucket), however much traffic escalates."""
        eng, runner = make_engine()
        with eng:
            eng.attach_cascade(POLICY)
            for i in range(6):
                im = hard_image(i) if i % 2 else easy_image(i)
                eng.submit(im, model="flag").result(5)
            eng.submit(easy_image(9), model="cheap").result(5)
            snap = eng.snapshot()
        assert snap["cascade"]["escalations"] == 3
        assert runner.compile_cache.misses == 2 * len(LADDER)

    def test_direct_cheap_and_other_traffic_bypass_gate(self):
        eng, _ = make_engine()
        with eng:
            eng.attach_cascade(POLICY)
            d = eng.submit(hard_image(), model="cheap").result(5)
            assert d[1][0, 4] == np.float32(0.2)  # uncertain bytes SHIP
            snap = eng.snapshot()
        assert snap["cascade"]["first_pass"] == 0

    def test_escalation_keeps_lane_and_tenant_accounting(self):
        eng, _ = make_engine()
        with eng:
            eng.attach_cascade(POLICY)
            f = eng.submit(hard_image(), model="flag", lane="interactive")
            assert served_x(f.result(5)) == 51.0
            lanes = eng.snapshot()["lanes"]
        # both passes rode the ORIGINAL flagship lane — nothing in bulk
        assert lanes["interactive"]["completed"] == 1
        assert lanes.get("bulk", {}).get("completed", 0) == 0

    def test_full_escalation_byte_identical_to_flagship_only(self):
        imgs = [easy_image(1), hard_image(1), fill_image(2.0)]
        eng, _ = make_engine()
        with eng:
            eng.attach_cascade(dict(POLICY, min_score=1.01))
            casc = [eng.submit(im, model="flag").result(5)[1].tobytes()
                    for im in imgs]
            snap = eng.snapshot()["cascade"]
        assert snap["escalation_rate"] == 1.0
        eng2, _ = make_engine()
        with eng2:
            base = [eng2.submit(im, model="flag").result(5)[1].tobytes()
                    for im in imgs]
        assert casc == base

    def test_zero_threshold_never_escalates(self):
        eng, runner = make_engine()
        with eng:
            eng.attach_cascade(dict(POLICY, min_score=0.0))
            for i in range(3):
                assert served_x(
                    eng.submit(hard_image(i), model="flag").result(5)
                ) == 1.0
            snap = eng.snapshot()["cascade"]
        assert snap["escalations"] == 0
        assert snap["first_pass_sufficient"] == 3
        assert runner.calls == {"cheap": 3}


# ------------------------------------------------- response-cache keying

class TestCascadeCacheKeys:
    def test_keys_never_cross_families_and_flagship_probe_hits(self):
        cache = ResponseCache()
        eng, runner = make_engine(cache=cache)
        with eng:
            eng.attach_cascade(POLICY)
            d_easy = eng.submit(easy_image(), model="flag").result(5)
            d_hard = eng.submit(hard_image(), model="flag").result(5)
            # each digest lives under exactly ONE family key — the gate
            # is deterministic per (policy, cheap version, image)
            fams = {}
            for k in list(cache._entries):
                fams.setdefault(k[3], set()).add(k[0])
            assert all(len(v) == 1 for v in fams.values())
            assert {k[0] for k in cache._entries} == {"cheap", "flag"}
            # a resubmitted escalated digest hits the FLAGSHIP key at
            # submit — no cheap pass, no gate, no device trip at all
            calls0 = dict(runner.calls)
            first0 = eng.snapshot()["cascade"]["first_pass"]
            d_hit = eng.submit(hard_image(), model="flag").result(5)
            assert d_hit[1].tobytes() == d_hard[1].tobytes()
            assert runner.calls == calls0
            assert eng.snapshot()["cascade"]["first_pass"] == first0
            # and a resubmitted easy digest hits the cheap key
            assert eng.submit(easy_image(), model="flag").result(5)[1] \
                .tobytes() == d_easy[1].tobytes()
        assert cache.snapshot()["hits"] == 2

    def test_uncertain_first_pass_is_never_cached(self):
        cache = ResponseCache()
        eng, _ = make_engine(cache=cache)
        with eng:
            eng.attach_cascade(POLICY)
            eng.submit(hard_image(7), model="flag").result(5)
        # only the flagship (final-serving) entry exists — the cheap
        # pass's uncertain bytes never seeded the cache
        keys = list(cache._entries)
        assert len(keys) == 1 and keys[0][0] == "flag"


# --------------------------------------------------------- chaos rows

class TestCascadeChaos:
    def test_escalation_correct_under_poison_mix(self):
        """A query-of-death mixed into cascade traffic fails ITSELF
        (typed, after its retry budget) while easy/hard neighbours keep
        routing correctly — and malformed input never reaches the
        batcher at all."""
        reg = make_registry()
        runner = CascadeStub(reg)
        runner.quarantine = QuarantineTable(k=2, ttl_s=60.0)
        eng = ServingEngine(runner, max_linger=0.0, retry_budget=2)
        with eng:
            eng.attach_cascade(POLICY)
            with pytest.raises(InvalidRequest):
                eng.submit(np.full((8, 8, 3), np.nan, np.float32),
                           model="flag")
            f_poison = eng.submit(fill_image(30.0), model="flag")
            f_easy = eng.submit(easy_image(), model="flag")
            f_hard = eng.submit(hard_image(), model="flag")
            assert served_x(f_easy.result(10)) == 1.0
            assert served_x(f_hard.result(10)) == 51.0
            with pytest.raises(RetriesExhausted):
                f_poison.result(10)
            snap = eng.snapshot()
        assert snap["cascade"]["escalations"] == 1
        assert snap["cascade"]["first_pass_sufficient"] == 1
        assert snap["requests"]["invalid"] == 1
        assert snap["requests"]["exhausted"] == 1
        assert snap["requests"]["completed"] == 2

    def test_cascade_with_cheap_family_hot_swap(self, tmp_path):
        """A live hot-swap of the CHEAP family mid-cascade: new cheap
        bytes after commit, cache invalidated for the cheap family only,
        flagship escalations unaffected throughout."""
        cache = ResponseCache()
        eng, _ = make_engine(cache=cache)
        ckpt = save_checkpoint(
            str(tmp_path / "cheap-v2"), {"params": params_tree(2.0)}, 1
        )
        with eng:
            eng.attach_cascade(POLICY)
            v1_easy = eng.submit(easy_image(), model="flag").result(5)
            v1_hard = eng.submit(hard_image(), model="flag").result(5)
            assert served_x(v1_easy) == 1.0
            eng.swap("cheap", ckpt, block=True)
            # cheap entries dropped, flagship entry survives
            assert {k[0] for k in cache._entries} == {"flag"}
            v2_easy = eng.submit(easy_image(), model="flag").result(5)
            v2_hard = eng.submit(hard_image(), model="flag").result(5)
        assert served_x(v2_easy) == 11.0  # w=2.0 visible in the bytes
        assert v2_hard[1].tobytes() == v1_hard[1].tobytes()
        # the fresh cheap entry is keyed by the NEW live version
        assert any(k[0] == "cheap" and k[1] == 2 for k in cache._entries)

    def test_cascade_rollout_arm_isolation(self, tmp_path):
        """An active FLAGSHIP rollout splits escalated traffic by the
        same digest-deterministic assignment as direct traffic: a
        digest's arm is stable across resubmits, candidate and
        incumbent bytes differ, and cache entries stay keyed by the
        SERVED version — arms never share bytes."""
        cache = ResponseCache()
        reg = make_registry()
        runner = CascadeStub(reg)
        eng = ServingEngine(runner, max_linger=0.0, response_cache=cache)
        ckpt = save_checkpoint(
            str(tmp_path / "flag-v2"), {"params": params_tree(1.5)}, 1
        )
        with eng:
            eng.attach_cascade(POLICY)
            ctl = eng.attach_rollout()
            ro = ctl.start("flag", ckpt, policy=RolloutPolicy(
                split_pct=50.0, shadow=False, min_compared=10_000,
                min_served=10_000, min_error_samples=10_000,
                min_latency_samples=10_000, hold_s=30.0,
                eval_interval_s=0.01,
            ))
            deadline = time.monotonic() + 10.0
            while not ctl.active("flag"):
                assert time.monotonic() < deadline, "split never opened"
                time.sleep(0.01)
            # two hard images on opposite arms (recomputed, not
            # hardcoded, so the test tracks the digest function)
            im_cand = im_inc = None
            for i in range(256):
                im = hard_image(i)
                if assign_arm(request_digest(im), 50.0):
                    im_cand = im_cand if im_cand is not None else im
                else:
                    im_inc = im_inc if im_inc is not None else im
                if im_cand is not None and im_inc is not None:
                    break
            assert im_cand is not None and im_inc is not None
            for _ in range(2):  # arm assignment stable across resubmits
                assert served_x(
                    eng.submit(im_cand, model="flag").result(5)
                ) == 56.0  # flagship candidate: 1 + 50 + (1.5-1)*10
                assert served_x(
                    eng.submit(im_inc, model="flag").result(5)
                ) == 51.0  # flagship incumbent
            snap = eng.snapshot()["cascade"]
            # 3, not 4: the incumbent digest's resubmit hit the
            # flagship cache (probed at the live version) before any
            # cheap pass; the candidate digest is keyed under the
            # candidate version, so its resubmit re-escalated — arm-
            # coherent bytes either way, asserted above
            assert snap["escalations"] == 3
            # cache: both digests under the flagship family, keyed by
            # the version that SERVED them — never each other's
            flag_keys = {k[3]: k[1] for k in cache._entries
                         if k[0] == "flag"}
            assert flag_keys[cache.digest(im_cand)] == 2
            assert flag_keys[cache.digest(im_inc)] == 1
        # engine stop cancels the in-flight rollout (the swap interlock)
        with pytest.raises(Exception):
            ro.result(0)
