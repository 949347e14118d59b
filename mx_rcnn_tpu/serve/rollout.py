"""Progressive rollout: traffic-split canarying, shadow scoring, and
auto-promote/auto-rollback on ONLINE evidence (ISSUE 17).

The registry's hot-swap (PR 7) promotes on a single binary canary
probe.  This module grows that into production rollout machinery: a
:class:`RolloutController` walks a candidate checkpoint through the
same load→verify→warm gauntlet as a swap, but instead of flipping the
live pointer it parks the candidate in VERIFYING and starts gathering
evidence from live traffic:

* **traffic split** — ``engine.submit`` asks :meth:`arm_for` on every
  request; a deterministic hash of the request's content digest sends
  ``split_pct`` percent of traffic to the candidate (same digest →
  same arm, always, so the response cache and quarantine stay
  arm-coherent).  Candidate-arm requests are released as solo batches
  (``Request.solo``) so a device batch is never a mix of arms, and
  served through the staged candidate tree via ``run_version`` —
  params are a jit argument, so the split adds ZERO jit signatures.
* **shadow mode** — the engine mirrors incumbent-arm completions (the
  input plus the incumbent's detections) into a bounded queue; a
  worker re-scores each through the candidate OFF the SLO path (no
  batcher, no tenant budget, no deadline) and feeds a structural
  comparison — IoU-matched box deltas, score drift, detection-count
  drift via :func:`~mx_rcnn_tpu.serve.runner.detection_parity` — into
  an online :class:`DivergenceReport` exposed in
  ``engine.snapshot()["rollout"]``.
* **auto-promote / auto-rollback** — the controller's evaluator
  promotes through the registry's existing atomic flip only after the
  evidence gates (``min_compared`` shadow comparisons, ``min_served``
  split responses) are met and every policy bound has held for
  ``hold_s`` continuously.  The moment any bound trips — divergence,
  candidate error rate, candidate p99 blowing past the incumbent's —
  the candidate is RETIRED, its staged buffers discarded, and the
  rollout future resolves with a typed :class:`RolloutAborted`.  The
  live pointer is never touched on the rollback path: the incumbent
  serves byte-identical responses throughout.

The closed loop rides on top: ``tools/distill.py`` harvests served
detections into ``data/synthetic.py``-schema records, fine-tunes with
the existing trainer, and submits the resulting checkpoint right back
through :meth:`RolloutController.start` — serve → collect → train →
verify → promote, end-to-end.

Locking: ``RolloutController._lock`` guards only the split/shadow
tables and counters — never device work, never a registry call (R4
keeps the graph acyclic: controller → registry edges only ever go
through registry methods called OUTSIDE the controller lock).  The
shadow queue has its own condition; the worker pops under it and
scores outside it.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Any, Deque, Dict, List, Optional

import numpy as np

from mx_rcnn_tpu.analysis.lockcheck import make_condition, make_lock
from mx_rcnn_tpu.core.checkpoint import restore_tree, verify_manifest
from mx_rcnn_tpu.serve.registry import (
    ModelVersion,
    UnknownVersion,
    VersionState,
    _tree_signature,
)
from mx_rcnn_tpu.serve.runner import detection_parity

logger = logging.getLogger(__name__)

__all__ = [
    "RolloutAborted",
    "RolloutCancelled",
    "RolloutController",
    "RolloutError",
    "RolloutInProgress",
    "RolloutPolicy",
    "DivergenceReport",
    "UnknownVersion",
    "assign_arm",
]


class RolloutError(RuntimeError):
    """A rollout failed outright (bad structure, bound violation, …)."""


class RolloutInProgress(RolloutError):
    """At most one rollout per model: a second ``start`` on the same
    model while one is evaluating is an operator error, not a queue."""


class RolloutCancelled(RolloutError):
    """The rollout was cancelled (engine stop / operator) before a
    verdict — the incumbent was never at risk."""


class RolloutAborted(RolloutError):
    """The rollout rolled back: a stage failed or an online policy
    bound tripped.  ``stage`` says where ("verify"/"warm" before any
    live traffic, "evaluate" during the split/shadow window); the
    incumbent's live pointer was never moved."""

    def __init__(self, stage: str, cause: BaseException):
        super().__init__(f"rollout aborted at {stage} stage: {cause!r}")
        self.stage = stage
        self.cause = cause


def assign_arm(digest: str, split_pct: float) -> bool:
    """Deterministic arm assignment: True → candidate arm.  The leading
    64 hash bits of the request's content digest, reduced mod 10000,
    gate against ``split_pct`` in basis points — a given digest lands
    on the same arm for the life of the split (cache coherence), and
    the split fraction is exact over the digest space, not sampled."""
    if split_pct <= 0.0:
        return False
    return int(digest[:16], 16) % 10000 < int(round(split_pct * 100.0))


@dataclasses.dataclass(frozen=True)
class RolloutPolicy:
    """Bounds and evidence gates for one progressive rollout.

    Divergence bounds are per-comparison maxima (the worst single
    shadow comparison observed); the error-rate and latency bounds are
    online aggregates over the candidate's split + shadow traffic."""

    split_pct: float = 5.0            # % of live traffic on the candidate
    shadow: bool = True               # mirror incumbent traffic off-SLO
    max_box_delta_px: float = 2.0     # IoU-matched box-corner drift bound
    max_score_delta: float = 0.1      # matched-pair score drift bound
    max_unmatched: int = 0            # confident dets without a counterpart
    max_count_drift: float = 0.5      # |n_cand - n_ref| / max(1, n_ref)
    max_error_rate: float = 0.05      # candidate errors / attempts
    max_p99_ratio: float = 3.0        # candidate p99 vs incumbent p99
    min_compared: int = 8             # shadow comparisons before promote
    min_served: int = 8               # split responses before promote
    min_error_samples: int = 4        # attempts before error rate binds
    min_latency_samples: int = 8      # per-arm samples before p99 binds
    hold_s: float = 0.5               # continuous in-bounds time to promote
    eval_interval_s: float = 0.05     # evaluator poll period
    shadow_queue: int = 64            # mirror backlog bound (drop beyond)
    score_thresh: Optional[float] = None  # parity thresh (None: model cfg)

    def snapshot(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


class DivergenceReport:
    """Online structural comparison of candidate vs incumbent responses.

    One shadow comparison contributes its :func:`detection_parity`
    result plus the confident-detection-count drift; the report keeps
    the WORST observed value per metric (bounds are per-comparison) and
    the throughput counters the evidence gates read.  The lock is a
    leaf — callers compute the (numpy) comparison outside it and only
    fold scalars under it."""

    def __init__(self):
        self._lock = make_lock("DivergenceReport._lock")
        self.mirrored = 0       # accepted into the shadow queue
        self.dropped = 0        # queue-full drops (never blocks serving)
        self.compared = 0       # scored + compared successfully
        self.failed = 0         # candidate raised while scoring
        self.max_box_delta_px = 0.0
        self.max_score_delta = 0.0
        self.max_unmatched = 0
        self.max_count_drift = 0.0

    def update(self, parity: Dict[str, Any], n_ref: int, n_cand: int) -> None:
        drift = abs(n_cand - n_ref) / max(1, n_ref)
        with self._lock:
            self.compared += 1
            self.max_box_delta_px = max(
                self.max_box_delta_px, float(parity["max_box_delta_px"])
            )
            self.max_score_delta = max(
                self.max_score_delta, float(parity["max_score_delta"])
            )
            self.max_unmatched = max(
                self.max_unmatched, int(parity["unmatched_confident"])
            )
            self.max_count_drift = max(self.max_count_drift, float(drift))

    def note_mirrored(self) -> None:
        with self._lock:
            self.mirrored += 1

    def note_dropped(self) -> None:
        with self._lock:
            self.dropped += 1

    def note_failed(self) -> None:
        with self._lock:
            self.failed += 1

    def violations(self, policy: RolloutPolicy) -> List[str]:
        with self._lock:
            out = []
            if self.max_box_delta_px > policy.max_box_delta_px:
                out.append(
                    f"box delta {self.max_box_delta_px:.3f}px > "
                    f"{policy.max_box_delta_px:g}px"
                )
            if self.max_score_delta > policy.max_score_delta:
                out.append(
                    f"score delta {self.max_score_delta:.4f} > "
                    f"{policy.max_score_delta:g}"
                )
            if self.max_unmatched > policy.max_unmatched:
                out.append(
                    f"{self.max_unmatched} unmatched confident detections "
                    f"> {policy.max_unmatched}"
                )
            if self.max_count_drift > policy.max_count_drift:
                out.append(
                    f"detection-count drift {self.max_count_drift:.3f} > "
                    f"{policy.max_count_drift:g}"
                )
            return out

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "mirrored": self.mirrored,
                "dropped": self.dropped,
                "compared": self.compared,
                "failed": self.failed,
                "max_box_delta_px": round(self.max_box_delta_px, 4),
                "max_score_delta": round(self.max_score_delta, 5),
                "max_unmatched": self.max_unmatched,
                "max_count_drift": round(self.max_count_drift, 4),
            }


class _ShadowItem:
    """One mirrored completion: the prepared input plus the incumbent's
    detections, frozen at resolve time (detections are treated as
    immutable by every consumer, same contract as the response cache)."""

    __slots__ = ("model", "version", "image", "im_info", "orig_hw",
                 "bucket", "ref_dets")

    def __init__(self, model, version, image, im_info, orig_hw, bucket,
                 ref_dets):
        self.model = model
        self.version = int(version)
        self.image = image
        self.im_info = im_info
        self.orig_hw = orig_hw
        self.bucket = bucket
        self.ref_dets = ref_dets


class _Rollout:
    """Per-model rollout state: the candidate version walking the
    gauntlet, its policy, the online evidence, and the verdict future.

    ``future`` resolves exactly once: a result dict on promote, or
    :class:`RolloutAborted` / :class:`RolloutCancelled`."""

    def __init__(self, model_id: str, checkpoint: str,
                 policy: RolloutPolicy, ordinal: int):
        self.model_id = model_id
        self.checkpoint = checkpoint
        self.policy = policy
        self.ordinal = int(ordinal)
        self.state = "staging"
        self.ver: Optional[ModelVersion] = None
        self.old: Optional[ModelVersion] = None
        self.report = DivergenceReport()
        self.future: "Future" = Future()
        self.cancel_event = threading.Event()
        self.thread: Optional[threading.Thread] = None
        self.split_t0: Optional[float] = None
        # online per-arm evidence (controller lock guards the scalars;
        # the deques are appended under it too — pure host bookkeeping)
        self.served = {"incumbent": 0, "candidate": 0}
        self.errors = {"incumbent": 0, "candidate": 0}
        self.lat: Dict[str, Deque[float]] = {
            "incumbent": deque(maxlen=512),
            "candidate": deque(maxlen=512),
        }

    def done(self) -> bool:
        return self.future.done()

    def result(self, timeout: Optional[float] = None):
        return self.future.result(timeout)


class RolloutController:
    """The rollout control plane for one registry + serve target.

    ``registry`` owns versions and the atomic live flip; ``target`` is
    the predict surface (a ServeRunner or ReplicaPool — anything with
    ``warm_version`` / ``run_version`` / ``assemble`` /
    ``detections_for`` / ``discard_version``); ``engine`` (optional)
    is consulted for response-cache invalidation on rollback."""

    def __init__(self, registry: Any, target: Any, engine: Any = None,
                 policy: Optional[RolloutPolicy] = None):
        self.registry = registry
        self.target = target
        self.engine = engine
        self.default_policy = policy or RolloutPolicy()
        self._lock = make_lock("RolloutController._lock")
        self._active: Dict[str, _Rollout] = {}
        # split table: model -> (candidate version, split_pct); shadow
        # table: model -> candidate version.  Kept separate from
        # _active so the per-request hot path reads one small dict.
        self._split: Dict[str, tuple] = {}
        self._shadow: Dict[str, int] = {}
        self._ordinal = 0
        self._stop = False
        # bounded mirror queue + its own condition; the worker pops
        # under the condition and scores OUTSIDE it (R5: every path
        # from the pop uses the item)
        self._shadow_queue: Deque[_ShadowItem] = deque()
        self._shadow_cond = make_condition("RolloutController._shadow_cond")
        self._shadow_thread: Optional[threading.Thread] = None
        # lifetime counters
        self.promoted = 0
        self.rolled_back = 0
        self.cancelled = 0

    # ------------------------------------------------------------ control
    def start(self, model_id: Optional[str], checkpoint: str,
              policy: Optional[RolloutPolicy] = None, block: bool = False,
              timeout: Optional[float] = None):
        """Launch a progressive rollout of ``checkpoint`` for
        ``model_id``: load→verify→warm off the serve path, then split +
        shadow live traffic until the evaluator promotes or rolls back.
        Returns the :class:`_Rollout` (or, with ``block=True``, its
        result — raising :class:`RolloutAborted` etc. inline)."""
        mid = self.registry.entry(model_id).model_id
        with self._lock:
            if self._stop:
                raise RolloutError("controller is stopped")
            prev = self._active.get(mid)
            if prev is not None and not prev.done():
                raise RolloutInProgress(
                    f"model {mid!r} already has a rollout in flight"
                )
            self._ordinal += 1
            ro = _Rollout(mid, checkpoint, policy or self.default_policy,
                          self._ordinal)
            self._active[mid] = ro
            if self._shadow_thread is None:
                self._shadow_thread = threading.Thread(
                    target=self._shadow_loop, name="rollout-shadow",
                    daemon=True,
                )
                self._shadow_thread.start()
        ro.thread = threading.Thread(
            target=self._run, args=(ro,),
            name=f"rollout-{mid}-{ro.ordinal}", daemon=True,
        )
        ro.thread.start()
        if block:
            return ro.result(timeout)
        return ro

    def stop(self) -> None:
        """Cancel every in-flight rollout and stop the shadow worker;
        blocks until the threads exit (the engine-stop interlock — no
        device work after this returns)."""
        with self._lock:
            self._stop = True
            active = list(self._active.values())
        for ro in active:
            ro.cancel_event.set()
        for ro in active:
            if ro.thread is not None:
                ro.thread.join(timeout=30.0)
        with self._shadow_cond:
            self._shadow_cond.notify_all()
        t = self._shadow_thread
        if t is not None:
            t.join(timeout=30.0)

    # ----------------------------------------------------- request plane
    def active(self, model_id: str) -> bool:
        """Cheap hot-path check: is this model under a traffic split?"""
        with self._lock:
            return model_id in self._split

    def arm_for(self, model_id: str, digest: str) -> Optional[int]:
        """The candidate version this digest is split onto, or None for
        the incumbent arm (also None when no split is active)."""
        with self._lock:
            entry = self._split.get(model_id)
        if entry is None:
            return None
        version, pct = entry
        return version if assign_arm(digest, pct) else None

    def mirror(self, model_id: str, req: Any, dets: Any) -> None:
        """Mirror one incumbent-arm completion into the shadow queue —
        non-blocking, bounded, off the SLO path entirely.  Called by the
        engine after it resolved the live response; a full queue drops
        the mirror (counted), never the serving thread."""
        with self._lock:
            version = self._shadow.get(model_id)
            ro = self._active.get(model_id)
        if version is None or ro is None or ro.done():
            return
        item = _ShadowItem(
            model_id, version, req.image, req.im_info, req.orig_hw,
            req.bucket, dets,
        )
        with self._shadow_cond:
            if len(self._shadow_queue) >= ro.policy.shadow_queue:
                ro.report.note_dropped()
                return
            self._shadow_queue.append(item)
            self._shadow_cond.notify()
        ro.report.note_mirrored()

    def note_serve(self, model_id: str, version: Optional[int],
                   ok: bool, e2e_s: Optional[float] = None) -> None:
        """Per-request evidence from the engine: which arm served, did
        it succeed, how long end-to-end.  Pure host bookkeeping."""
        with self._lock:
            ro = self._active.get(model_id)
            if ro is None or ro.done() or ro.ver is None:
                return
            arm = (
                "candidate"
                if version is not None and version == ro.ver.version
                else "incumbent"
            )
            if ok:
                ro.served[arm] += 1
                if e2e_s is not None:
                    ro.lat[arm].append(float(e2e_s))
            else:
                ro.errors[arm] += 1

    def note_arm_error(self, model_id: str, exc: BaseException) -> None:
        """A candidate-arm request failed in the candidate path (the
        engine fell back to the incumbent — zero lost requests)."""
        self.note_serve(model_id, self._candidate_version(model_id),
                        ok=False)

    def _candidate_version(self, model_id: str) -> Optional[int]:
        with self._lock:
            ro = self._active.get(model_id)
            return ro.ver.version if ro and ro.ver is not None else None

    # --------------------------------------------------------- the stages
    def _abort_check(self, ro: _Rollout) -> None:
        if ro.cancel_event.is_set():
            raise RolloutCancelled(
                f"rollout #{ro.ordinal} of model {ro.model_id!r} cancelled"
            )

    def _run(self, ro: _Rollout) -> None:
        reg = self.registry
        stage = "load"
        try:
            e = reg.entry(ro.model_id)
            ro.old = reg.live(ro.model_id)
            with reg._lock:
                ro.ver = ModelVersion(
                    ro.model_id, e.next_version,
                    source=str(ro.checkpoint),
                )
                e.next_version += 1
                e.versions.append(ro.ver)
            self._abort_check(ro)

            # LOADING: host-side restore, nothing on device
            tree = restore_tree(ro.checkpoint)
            self._abort_check(ro)

            # VERIFYING: shared manifest gate + structure-vs-live check
            stage = "verify"
            reg._transition(ro.ver, VersionState.VERIFYING, "loaded")
            man = verify_manifest(ro.checkpoint, tree=tree)
            params = (
                tree["params"]
                if isinstance(tree, dict) and "params" in tree
                else tree
            )
            got = _tree_signature(params)
            want = _tree_signature(ro.old.params)
            if got != want:
                raise RolloutError(
                    f"checkpoint tree structure does not match live "
                    f"v{ro.old.version} — a rollout must not force a "
                    f"recompile"
                )
            ro.ver.params = params
            ro.ver.digest = man.get("checksum")
            self._abort_check(ro)

            # WARMING: candidate through every served signature, off the
            # live path (predict_with — zero new compile misses); the
            # staged device tree is what run_version serves the split on
            stage = "warm"
            reg._transition(ro.ver, VersionState.WARMING, "verified")
            self.target.warm_version(
                ro.model_id, ro.ver.version, params,
                abort=lambda: self._abort_check(ro),
            )
            self._abort_check(ro)

            # back to VERIFYING — the candidate now earns promotion from
            # live traffic instead of one probe: open the split + shadow
            stage = "evaluate"
            reg._transition(
                ro.ver, VersionState.VERIFYING, "rollout: split+shadow open"
            )
            with self._lock:
                if ro.policy.split_pct > 0.0:
                    self._split[ro.model_id] = (
                        ro.ver.version, ro.policy.split_pct
                    )
                if ro.policy.shadow:
                    self._shadow[ro.model_id] = ro.ver.version
                ro.state = "evaluating"
                ro.split_t0 = time.monotonic()
            self._evaluate(ro)
        except RolloutCancelled as exc:
            self._close_tables(ro)
            if ro.ver is not None:
                reg._retire(ro.ver, "rollout cancelled")
                self._discard(ro)
            self._drop_cached(ro.model_id)
            with self._lock:
                ro.state = "cancelled"
                self.cancelled += 1
            ro.future.set_exception(exc)
        except RolloutAborted as exc:
            ro.future.set_exception(exc)
        except Exception as exc:  # noqa: BLE001 — every gate failure aborts
            self._rollback(ro, stage, exc)
            ro.future.set_exception(RolloutAborted(stage, exc))

    def _evaluate(self, ro: _Rollout) -> None:
        """The background evaluator: poll the online evidence; roll back
        the moment any bound trips, promote once every gate has held
        for ``hold_s`` continuously."""
        pol = ro.policy
        healthy_since: Optional[float] = None
        while True:
            self._abort_check(ro)
            bad = self._violations(ro)
            if bad:
                cause = RolloutError("; ".join(bad))
                self._rollback(ro, "evaluate", cause)
                raise RolloutAborted("evaluate", cause)
            now = time.monotonic()
            if self._evidence_met(ro):
                if healthy_since is None:
                    healthy_since = now
                if now - healthy_since >= pol.hold_s:
                    self._promote(ro)
                    return
            else:
                healthy_since = None
            time.sleep(pol.eval_interval_s)

    def _violations(self, ro: _Rollout) -> List[str]:
        pol = ro.policy
        out = ro.report.violations(pol)
        with self._lock:
            attempts = (
                ro.served["candidate"] + ro.errors["candidate"]
            )
            errors = ro.errors["candidate"]
            inc = list(ro.lat["incumbent"])
            cand = list(ro.lat["candidate"])
        attempts += ro.report.compared + ro.report.failed
        errors += ro.report.failed
        if attempts >= pol.min_error_samples:
            rate = errors / attempts
            if rate > pol.max_error_rate:
                out.append(
                    f"candidate error rate {rate:.3f} > "
                    f"{pol.max_error_rate:g} ({errors}/{attempts})"
                )
        if (len(inc) >= pol.min_latency_samples
                and len(cand) >= pol.min_latency_samples):
            p_inc = float(np.percentile(inc, 99))
            p_cand = float(np.percentile(cand, 99))
            if p_inc > 0 and p_cand > pol.max_p99_ratio * p_inc:
                out.append(
                    f"candidate p99 {p_cand * 1e3:.1f}ms > "
                    f"{pol.max_p99_ratio:g}x incumbent "
                    f"{p_inc * 1e3:.1f}ms"
                )
        return out

    def _evidence_met(self, ro: _Rollout) -> bool:
        pol = ro.policy
        if pol.shadow and ro.report.compared < pol.min_compared:
            return False
        if pol.split_pct > 0.0:
            with self._lock:
                if ro.served["candidate"] < pol.min_served:
                    return False
        return True

    def _promote(self, ro: _Rollout) -> None:
        """The verdict passed: flip the live pointer through the
        registry's existing atomic commit, retire the incumbent, and
        resolve the future with the evidence."""
        reg = self.registry
        e = reg.entry(ro.model_id)
        self._close_tables(ro)
        with reg._lock:
            self._abort_check(ro)
            reg._transition(ro.ver, VersionState.LIVE, "rollout promote")
            e.live = ro.ver
        reg._notify_live(ro.model_id)  # cached v(old) responses: out
        reg._retire(
            ro.old,
            f"superseded by v{ro.ver.version} (rollout promote)",
        )
        with self._lock:
            ro.state = "promoted"
            self.promoted += 1
            evidence = {
                "split_served": ro.served["candidate"],
                "split_errors": ro.errors["candidate"],
                "incumbent_served": ro.served["incumbent"],
            }
        ro.future.set_result(
            {
                "model": ro.model_id,
                "version": ro.ver.version,
                "previous": ro.old.version,
                "divergence": ro.report.snapshot(),
                **evidence,
            }
        )

    def _rollback(self, ro: _Rollout, stage: str,
                  cause: BaseException) -> None:
        """A bound tripped (or a stage failed): retire the candidate,
        free its staged buffers, drop any candidate-keyed cached
        responses.  The live pointer is NEVER touched here — the
        incumbent kept serving all along."""
        self._close_tables(ro)
        if ro.ver is not None:
            self.registry._retire(
                ro.ver, f"rollout rolled back at {stage}: {cause!r}"
            )
            self._discard(ro)
        self._drop_cached(ro.model_id)
        with self._lock:
            ro.state = "rolled_back"
            self.rolled_back += 1

    def _drop_cached(self, model_id: str) -> None:
        """Drop the model's response-cache entries (candidate keys are
        unreachable once the split closes — this is memory hygiene, the
        version-carrying key is what guarantees correctness)."""
        cache = getattr(self.engine, "response_cache", None)
        if cache is not None:
            try:
                cache.invalidate_model(model_id)
            except Exception:  # noqa: BLE001 — hygiene, not a gate
                logger.exception(
                    "response-cache invalidation failed for %s", model_id
                )

    def _close_tables(self, ro: _Rollout) -> None:
        with self._lock:
            self._split.pop(ro.model_id, None)
            self._shadow.pop(ro.model_id, None)

    def _discard(self, ro: _Rollout) -> None:
        discard = getattr(self.target, "discard_version", None)
        if discard is not None and ro.ver is not None:
            try:
                discard(ro.model_id, ro.ver.version)
            except Exception:  # noqa: BLE001 — cleanup, not a gate
                logger.exception(
                    "discard_version(%s, %d) failed",
                    ro.model_id, ro.ver.version,
                )

    # --------------------------------------------------------- shadow lane
    def _shadow_loop(self) -> None:
        """Drain the mirror queue through the candidate, off the SLO
        path.  The pop happens under the condition; scoring (device
        work) happens outside every lock."""
        while True:
            with self._shadow_cond:
                while not self._shadow_queue and not self._stop:
                    self._shadow_cond.wait(0.05)
                if not self._shadow_queue and self._stop:
                    return
                item = self._shadow_queue.popleft()
            self._score_shadow(item)

    def _score_shadow(self, item: _ShadowItem) -> None:
        with self._lock:
            ro = self._active.get(item.model)
        if ro is None or ro.done() or ro.ver is None \
                or ro.ver.version != item.version:
            return  # the rollout this mirror belonged to is over
        try:
            from mx_rcnn_tpu.serve.batcher import Request

            req = Request(
                image=item.image, im_info=item.im_info,
                orig_hw=item.orig_hw, bucket=item.bucket,
                model=item.model,
            )
            batch = self.target.assemble([req])
            out = self.target.run_version(
                batch, model=item.model, version=item.version
            )
            cand = self.target.detections_for(
                out, batch, 0, orig_hw=item.orig_hw, model=item.model
            )
        except Exception:  # noqa: BLE001 — a failing candidate is evidence
            ro.report.note_failed()
            return
        thresh = self._score_thresh(ro)
        parity = detection_parity(item.ref_dets, cand, thresh)
        ro.report.update(
            parity,
            n_ref=self._confident(item.ref_dets, thresh),
            n_cand=self._confident(cand, thresh),
        )

    def _score_thresh(self, ro: _Rollout) -> float:
        if ro.policy.score_thresh is not None:
            return float(ro.policy.score_thresh)
        cfg = getattr(self.registry.entry(ro.model_id), "cfg", None)
        try:
            return float(cfg.TEST.SCORE_THRESH)
        except AttributeError:
            return 0.05

    @staticmethod
    def _confident(dets: Any, thresh: float) -> int:
        n = 0
        for arr in (dets or [])[1:]:
            if arr is None or not len(arr):
                continue
            a = np.asarray(arr)
            n += int((a[:, 4] >= thresh).sum())
        return n

    # ------------------------------------------------------ observability
    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            rollouts = {}
            for mid, ro in self._active.items():
                rollouts[mid] = {
                    "state": ro.state,
                    "candidate_version": (
                        ro.ver.version if ro.ver is not None else None
                    ),
                    "split_pct": (
                        self._split[mid][1] if mid in self._split else 0.0
                    ),
                    "shadow": mid in self._shadow,
                    "served": dict(ro.served),
                    "errors": dict(ro.errors),
                    "divergence": ro.report.snapshot(),
                }
            return {
                "models": rollouts,
                "promoted": self.promoted,
                "rolled_back": self.rolled_back,
                "cancelled": self.cancelled,
                "shadow_backlog": len(self._shadow_queue),
            }
