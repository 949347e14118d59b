"""Threaded serving loop: submit → batcher → device → per-request futures.

Thread layout (blocking predicts from a few threads overlap one batch's
host work — assembly, fetch, per-request postprocess — with another's
device time, the GIL dropping inside the runtime; whether an async
dispatch chain does as well on a local chip is ROADMAP D9):

  * N client threads: ``submit`` prepares the image (resize/quantize/
    pad) in the CALLER's thread, so host preprocessing of the next
    requests overlaps device execution of earlier batches, then enqueues
    into the bounded batcher (``QueueFull`` → backpressure).
  * 1 assembler thread: pulls bucket-homogeneous batches from the
    batcher, fails requests whose deadline already passed (cheaper than
    running them), pads to ``max_batch``, and hands the batch to…
  * ``in_flight`` completion threads: blocking ``runner.run`` (wrapped
    in PR 1's :class:`~mx_rcnn_tpu.core.resilience.RetryPolicy` — a
    transient device fault retries the whole batch
    deterministically), then per-request detections + future resolution.
    The workers live in a bounded
    :class:`~mx_rcnn_tpu.data.assembler.CompletionPool` whose blocking
    submit keeps the assembler at most ``in_flight`` batches ahead, so
    device-side queueing stays bounded too — and whose counters land in
    :meth:`ServingEngine.snapshot`.

Every request resolves exactly once: detections list, or
:class:`DeadlineExceeded` / :class:`QueueFull` /
:class:`~mx_rcnn_tpu.serve.buckets.BucketOverflow` / the predict error
after retries are exhausted / :class:`EngineStopped` when the engine is
torn down first (``stop`` sweeps the live-request registry, so a
submitter can never block forever on a dead engine).

The runner may also be a :class:`~mx_rcnn_tpu.serve.router.ReplicaPool`
(detected by its ``replicas`` attribute): the engine then passes each
batch's tightest deadline to ``run`` and disables its own RetryPolicy —
retry, hedging, and failover belong to the pool — and ``submit`` sheds
load early (``QueueFull`` + ``shed`` counter) when the pool's healthy
fraction scales the effective queue capacity below the current backlog.
"""

from __future__ import annotations

import itertools
import threading
import time
from concurrent.futures import Future, InvalidStateError
from typing import Dict, List, Optional

import numpy as np

from mx_rcnn_tpu.core.resilience import RetryPolicy, make_retry_policy
from mx_rcnn_tpu.analysis.lockcheck import make_lock
from mx_rcnn_tpu.data.assembler import CompletionPool
from mx_rcnn_tpu.serve.batcher import (
    DEFAULT_LANE,
    DeadlineExceeded,
    DynamicBatcher,
    LANES,
    QueueFull,
    Request,
)
from mx_rcnn_tpu.serve.metrics import ServeMetrics
from mx_rcnn_tpu.serve.quarantine import (
    BatchBudget,
    InvalidRequest,
    PoisonRequest,
    RetriesExhausted,
    RetryBudget,
    request_digest,
    validate_image,
)
from mx_rcnn_tpu.serve.runner import ServeRunner
from mx_rcnn_tpu.serve.streams import StreamTable
from mx_rcnn_tpu.utils import tracing

# DeadlineExceeded historically lived here; it moved to serve.batcher so
# the expired-request sweep can raise it without a circular import, and
# stays re-exported for every existing `from serve.engine import` site.
# The containment taxonomy (ISSUE 12) lives in serve.quarantine and is
# re-exported here for the same reason: clients catch engine errors.
__all__ = [
    "DeadlineExceeded", "EngineStopped", "ServingEngine",
    "InvalidRequest", "PoisonRequest", "RetriesExhausted",
]


#: process-wide request numbers, drawn at submit (the spans' ``req`` id)
_REQUEST_SEQ = itertools.count(1)


class EngineStopped(RuntimeError):
    """The engine was torn down before this request completed — a
    terminal resolution, so no submitter is ever left blocked on a
    future the engine will never touch again."""


class ServingEngine:
    """Online inference front-end over a :class:`ServeRunner`."""

    def __init__(
        self,
        runner: ServeRunner,
        max_linger: float = 0.005,
        max_queue: int = 64,
        in_flight: int = 2,
        retry: Optional[RetryPolicy] = None,
        interactive_linger: float = 0.0,
        bulk_age_limit: float = 2.0,
        response_cache=None,
        retry_budget: int = 8,
        tenants=None,
        shed_fraction: float = 0.75,
    ):
        self.runner = runner
        # multi-tenant front door (ISSUE 16): a TenantTable turns on
        # token-bucket admission at submit, weighted-fair release in the
        # batcher, shed-over-budget-tenant-first under pressure, and the
        # per-tenant metrics partition
        self.tenants = tenants
        self.shed_fraction = float(shed_fraction)
        fair = None
        if tenants is not None:
            from mx_rcnn_tpu.serve.tenancy import WeightedFairScheduler

            fair = WeightedFairScheduler(weight_fn=tenants.weight)
        self.batcher = DynamicBatcher(
            runner.max_batch, max_linger=max_linger, max_queue=max_queue,
            interactive_linger=interactive_linger,
            bulk_age_limit=bulk_age_limit,
            on_expired=self._expire_swept,
            fair=fair,
        )
        # idempotent response cache (serve/respcache.py), keyed by image
        # digest per (model, live version); the registry's live-pointer
        # hook invalidates on hot-swap so hits can never be stale
        self.response_cache = response_cache
        if response_cache is not None:
            reg = getattr(runner, "registry", None)
            if reg is not None and hasattr(reg, "subscribe_live"):
                reg.subscribe_live(response_cache.invalidate_model)
        self.metrics = ServeMetrics()
        self.retry = retry if retry is not None else make_retry_policy("serve")
        self._in_flight = max(1, int(in_flight))
        self._pool: Optional[CompletionPool] = None
        self._assembler: Optional[threading.Thread] = None
        self._started = False
        # a ReplicaPool routes/retries/hedges internally; the engine then
        # skips its own RetryPolicy and sheds early on pool health
        self._routed = hasattr(runner, "replicas")
        # query-of-death containment (ISSUE 12): active when the pool
        # carries a QuarantineTable — the engine then digests every
        # request at admission, attaches retry budgets, and splits
        # implicated batches instead of failing them wholesale
        self._quarantine = getattr(runner, "quarantine", None)
        self._retry_budget = max(1, int(retry_budget))
        self._aborting = False
        # elastic capacity (ISSUE 16): a background AutoScaler attached
        # via attach_autoscaler; stop() joins it BEFORE pool teardown
        self.autoscaler = None
        # progressive rollout (ISSUE 17): a RolloutController attached
        # via attach_rollout — submit consults it for arm assignment,
        # _complete feeds it evidence; stop() joins it with the swaps
        self.rollout = None
        # confidence-gated cascade (ISSUE 18): a CascadeRouter attached
        # via attach_cascade — submit reroutes flagship requests to the
        # cheap family, _complete runs the gate and escalates uncertain
        # first passes back through the batcher as flagship requests
        self.cascade = None
        # streaming mode (ISSUE 20): per-stream in-order delivery gate
        # at _resolve — the exactly-once choke point every redispatch
        # path (trip/requeue/hedge/resubmit/escalation) funnels through,
        # so frames of one stream complete in order no matter how they
        # executed.  Untagged requests bypass it entirely.
        self.streams = StreamTable()
        # every not-yet-resolved request, so stop() can sweep leftovers
        # with a terminal EngineStopped instead of stranding submitters
        self._live: Dict[int, Request] = {}
        self._live_lock = make_lock("ServingEngine._live_lock")

    # ---------------------------------------------------------- lifecycle
    def start(self, warmup: bool = True) -> "ServingEngine":
        if self._started:
            return self
        if warmup:
            self.runner.warmup()
        # same thread layout as before (in_flight workers, submit blocks
        # at depth=in_flight — the old semaphore), but the pool exports
        # the shared data-plane counters into snapshot()
        self._pool = CompletionPool(
            self._in_flight, depth=self._in_flight, name="serve-complete"
        )
        self._assembler = threading.Thread(
            target=self._assemble_loop, name="serve-assemble", daemon=True
        )
        self._started = True
        self._assembler.start()
        return self

    def stop(self, drain: bool = True) -> None:
        """Stop accepting and join threads.  ``drain=True`` finishes
        queued work first; ``drain=False`` aborts — queued batches are
        failed instead of dispatched.  Either way every still-pending
        future is resolved (terminal :class:`EngineStopped`) before this
        returns: no submitter is left blocked on a dead engine.

        Swap interlock (ISSUE 7): any in-flight background model swap is
        cancelled FIRST, waiting for its controller thread to exit — so
        no orphaned warmup thread survives the engine and no swap-side
        ``device_put`` runs after stop returns.

        Autoscaler interlock (ISSUE 16, same pattern): the controller
        thread is stopped and JOINED before pool teardown — a stop
        racing a scale-up must not leave an orphaned controller minting
        replicas (and device placements) into a pool being closed."""
        if not self._started:
            return
        reg = getattr(self.runner, "registry", None)
        if reg is not None:
            reg.cancel_swaps(wait=True)
        if self.rollout is not None:
            # same interlock as swaps: cancel in-flight rollouts and
            # join the shadow worker before any pool/batcher teardown,
            # so no rollout-side device work runs after stop returns
            self.rollout.stop()
        if self.autoscaler is not None:
            self.autoscaler.stop()
        if not drain:
            self._aborting = True
        self.batcher.close()
        if self._assembler is not None:
            self._assembler.join()
        # raise_errors=False: request futures already carry per-request
        # failures; an engine drain must not re-raise them at shutdown
        if self._pool is not None:
            self._pool.close(raise_errors=False)
        self._started = False
        # results already settled but parked behind a stream gap must
        # ship before the leftover sweep fails their successors — no
        # settled result is ever lost to a stop (ordering is best-effort
        # at teardown: the gap frames resolve EngineStopped below)
        self.streams.flush()
        with self._live_lock:
            leftovers = list(self._live.values())
            self._live.clear()
        stopped = EngineStopped("engine stopped before request completed")
        for r in leftovers:
            try:
                r.future.set_exception(stopped)
            except InvalidStateError:
                continue
            self.metrics.inc("stopped")

    def attach_autoscaler(self, policy=None, signal_fn=None, start=True):
        """Create (and by default start) an
        :class:`~mx_rcnn_tpu.serve.autoscaler.AutoScaler` bound to this
        engine's replica pool.  Requires a routed runner.  The engine
        owns its lifecycle from here: ``stop()`` joins the controller
        before tearing the pool down."""
        if not self._routed:
            raise RuntimeError(
                "autoscaling needs a ReplicaPool runner — single-runner "
                "engines have nothing to scale"
            )
        from mx_rcnn_tpu.serve.autoscaler import AutoScaler

        self.autoscaler = AutoScaler(
            self.runner, policy=policy, engine=self, signal_fn=signal_fn
        )
        if start:
            self.autoscaler.start()
        return self.autoscaler

    def attach_rollout(self, policy=None):
        """Create a
        :class:`~mx_rcnn_tpu.serve.rollout.RolloutController` bound to
        this engine's registry and runner/pool.  From here ``submit``
        consults it for deterministic arm assignment, ``_complete``
        feeds it per-arm evidence and mirrors incumbent completions
        into the shadow lane, and ``stop()`` joins it alongside the
        swap interlock."""
        reg = getattr(self.runner, "registry", None)
        if reg is None:
            raise RuntimeError(
                "progressive rollout needs a registry-backed "
                "ServeRunner/ReplicaPool"
            )
        from mx_rcnn_tpu.serve.rollout import RolloutController

        self.rollout = RolloutController(
            reg, self.runner, engine=self, policy=policy
        )
        return self.rollout

    def attach_cascade(self, policy) -> "CascadeRouter":
        """Bind a :class:`~mx_rcnn_tpu.serve.cascade.CascadePolicy` to
        this engine.  From here every request resolving to the policy's
        flagship family first serves on the cheap family; ``_complete``
        runs the pure-host confidence gate on the first pass's
        detections and either resolves (sufficient) or re-enters the
        batcher as a flagship request with the original lane, tenant,
        deadline, digest, and retry budget intact.  Requests addressed
        to any other family — including direct cheap-family traffic —
        are untouched."""
        from mx_rcnn_tpu.serve.cascade import CascadePolicy, CascadeRouter

        if not isinstance(policy, CascadePolicy):
            policy = CascadePolicy(**dict(policy))
        reg = getattr(self.runner, "registry", None)
        if reg is not None:
            for mid in (policy.cheap, policy.flagship):
                if not reg.has(mid):
                    from mx_rcnn_tpu.serve.registry import UnknownModel

                    raise UnknownModel(
                        f"cascade family {mid!r} is not registered"
                    )
        self.cascade = CascadeRouter(policy)
        return self.cascade

    def _precision_tag(self, model: Optional[str]) -> str:
        """Serve-graph precision of ``model`` on this engine's runner
        ("f32" for stub runners without precision plumbing) — joins the
        response-cache key so rungs never share bytes."""
        pf = getattr(self.runner, "_precision_for", None)
        if pf is None:
            return "f32"
        try:
            return pf(self._resolved_mid(model))
        except Exception:  # noqa: BLE001 — unknown model: default tag
            return "f32"

    def _resolved_mid(self, model: Optional[str]) -> Optional[str]:
        """Registry model id a request resolves to (the rollout tables
        are keyed by it, never by None)."""
        if model is not None:
            return model
        mid = getattr(self.runner, "default_model", None)
        if mid is not None:
            return mid
        reg = getattr(self.runner, "registry", None)
        if reg is not None:
            try:
                return reg.default_model
            except Exception:  # noqa: BLE001 — empty registry
                return None
        return None

    def __enter__(self) -> "ServingEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ------------------------------------------------------------- client
    def _lane_for(self, model: Optional[str], lane: Optional[str]) -> str:
        """Resolve a request's SLO lane: explicit tag wins, else the
        model's registry-declared SLO class (an interactive-tier model
        taints its requests' lane), else bulk."""
        if lane is not None:
            if lane not in LANES:
                raise ValueError(f"unknown SLO lane {lane!r}")
            return lane
        reg = getattr(self.runner, "registry", None)
        if reg is not None and hasattr(reg, "slo_class"):
            return reg.slo_class(model)
        return DEFAULT_LANE

    def _live_version(self, model: Optional[str]) -> Optional[int]:
        """Current live version of ``model`` (None when the runner has no
        registry — stub runners — or no live version yet)."""
        reg = getattr(self.runner, "registry", None)
        if reg is None or not hasattr(reg, "live"):
            return None
        try:
            return int(reg.live(model).version)
        except Exception:  # noqa: BLE001 — no live version = no caching
            return None

    def _stream_admit(self, stream, frame) -> bool:
        """Validate + register a streaming submit's ``(stream, frame)``
        identity; True when registered (the caller must cancel on any
        later synchronous rejection, or the permanent gap would buffer
        the stream's later frames forever)."""
        if stream is None and frame is None:
            return False
        if stream is None or frame is None:
            self.metrics.inc("invalid")
            self.metrics.inc("rejected")
            raise InvalidRequest(
                "stream and frame must be provided together"
            )
        try:
            self.streams.register(stream, frame)
        except (TypeError, ValueError) as e:
            self.metrics.inc("invalid")
            self.metrics.inc("rejected")
            raise InvalidRequest(f"bad stream/frame: {e}")
        return True

    def _stream_cancel(self, stream, frame) -> None:
        if stream is not None and frame is not None:
            self.streams.cancel(stream, int(frame))

    def submit(
        self,
        im: np.ndarray,
        deadline_s: Optional[float] = None,
        model: Optional[str] = None,
        lane: Optional[str] = None,
        tenant: Optional[str] = None,
        stream: Optional[str] = None,
        frame: Optional[int] = None,
        masks: bool = False,
    ) -> Future:
        """Enqueue one image; see :meth:`_submit` for the contract.  The
        request's process-wide number is drawn here, so the caller-thread
        span and every later ``rcnn.serve.pickup`` name the same ``req``."""
        seq = next(_REQUEST_SEQ)
        with tracing.span(tracing.SERVE_PREPARE, req=seq):
            return self._submit(
                seq, im, deadline_s, model, lane, tenant, stream, frame, masks
            )

    def _submit(
        self,
        seq: int,
        im: np.ndarray,
        deadline_s: Optional[float],
        model: Optional[str],
        lane: Optional[str],
        tenant: Optional[str],
        stream: Optional[str],
        frame: Optional[int],
        masks: bool,
    ) -> Future:
        """Enqueue one image; returns a Future resolving to the
        per-class detections list.  ``model`` selects a registry family
        (None = the default model — the tenancy request schema);
        ``lane`` tags the SLO class (``"interactive"`` | ``"bulk"``,
        None = the model's registry default); ``tenant`` is the fair-
        share identity (None = untagged in-process caller).

        Streaming mode (ISSUE 20): ``stream``/``frame`` (always
        together; ``frame`` strictly increasing per stream) put the
        request under the per-stream in-order delivery guarantee —
        frames of one stream resolve in frame order no matter how
        trips, requeues, hedges, or escalations reorder execution;
        cross-stream and untagged traffic is unordered and unaffected.
        ``masks=True`` resolves to ``(cls_dets, rles)`` — canvas-space
        mask RLEs from the runner's device-paste path (requires a mask
        model family).  Raises
        :class:`~mx_rcnn_tpu.serve.quarantine.InvalidRequest` (failed
        the admission gate),
        :class:`~mx_rcnn_tpu.serve.quarantine.PoisonRequest` (digest is
        quarantined),
        :class:`~mx_rcnn_tpu.serve.tenancy.UnknownTenant` /
        :class:`~mx_rcnn_tpu.serve.tenancy.TenantOverBudget` (tenant
        admission, with a TenantTable configured),
        :class:`~mx_rcnn_tpu.serve.buckets.BucketOverflow` (oversize),
        :class:`~mx_rcnn_tpu.serve.batcher.QueueFull` (backpressure), or
        :class:`~mx_rcnn_tpu.serve.registry.UnknownModel` synchronously
        — all count as ``rejected``."""
        if not self._started:
            raise RuntimeError("engine not started")
        if self.tenants is not None:
            # tenant admission BEFORE any image work: an unknown tenant
            # or an empty token bucket must cost nothing but this check
            # (the quarantine fast-fail discipline, applied per tenant)
            from mx_rcnn_tpu.serve.tenancy import TenantOverBudget

            try:
                self.tenants.admit(tenant)
            except TenantOverBudget:
                self.metrics.inc("over_budget")
                self.metrics.inc("rejected")
                self.metrics.record_tenant(tenant, rejected=True)
                raise
            except Exception:
                self.metrics.inc("rejected")
                raise
        reg = getattr(self.runner, "registry", None)
        if model is not None:
            if reg is not None and not reg.has(model):
                self.metrics.inc("rejected")
                from mx_rcnn_tpu.serve.registry import UnknownModel

                raise UnknownModel(model)
        # admission gate (ISSUE 12): malformed work fails the CALLER
        # with a typed error before it can reach the batcher or crash
        # the shared assembler thread; registry-declared per-model
        # bounds tighten the default shape/size limits
        limits = None
        if reg is not None and hasattr(reg, "limits"):
            try:
                limits = reg.limits(model)
            except Exception:  # noqa: BLE001 — no entry yet: defaults
                limits = None
        try:
            im = validate_image(im, limits)
        except InvalidRequest:
            self.metrics.inc("invalid")
            self.metrics.inc("rejected")
            raise
        digest = None
        if self._quarantine is not None:
            digest = request_digest(im)
            if self._quarantine.quarantined(digest):
                # fail fast: a quarantined query of death must not cost
                # another replica trip, or even a queue slot
                self.metrics.inc("poisoned")
                self.metrics.inc("rejected")
                raise PoisonRequest(
                    f"digest {digest[:12]} is quarantined (query of death)"
                )
        lane = self._lane_for(model, lane)
        # streaming admission: validate + register the (stream, frame)
        # identity BEFORE any path that can resolve the future (cache
        # hits included), so every resolution goes through the gate in
        # registration order
        streamed = self._stream_admit(stream, frame)
        # cascade reroute (ISSUE 18): a request resolving to the
        # flagship family serves the cheap family first; the gate at
        # completion decides escalation.  The LANE above was resolved
        # from the original (flagship) target — the cheap pass and any
        # escalation both ride it, so cascading never demotes an SLO.
        serve_model = model
        cascade_first = False
        if self.cascade is not None \
                and self._resolved_mid(model) == self.cascade.policy.flagship:
            serve_model = self.cascade.policy.cheap
            cascade_first = True
        arm_version = None
        if self.rollout is not None:
            # deterministic arm assignment (ISSUE 17): the content
            # digest — not a coin flip — picks the arm, so a repeated
            # request always lands on the same version and the response
            # cache stays arm-coherent by construction.  Under a
            # cascade the first pass serves the CHEAP family, so its
            # rollouts are the ones consulted here; a flagship rollout
            # is consulted at escalation time instead.
            mid_r = self._resolved_mid(serve_model)
            if mid_r is not None and self.rollout.active(mid_r):
                if digest is None:
                    digest = request_digest(im)
                arm_version = self.rollout.arm_for(mid_r, digest)
        cache_key = None
        # masks requests bypass the response cache: keys are image-
        # content keyed and a (dets, rles) tuple must never collide
        # with a plain-detections entry for the same bytes
        if self.response_cache is not None and not masks:
            t0 = time.monotonic()
            if cascade_first:
                # the final serving of a cascaded digest may be the
                # flagship (escalated earlier) — probe that key first;
                # the gate is deterministic per (policy, cheap version,
                # image), so at most one of the two keys can exist
                fmid = self.cascade.policy.flagship
                fver = self._live_version(fmid)
                if fver is not None:
                    fhit = self.response_cache.get(
                        self.response_cache.key_for(
                            im, fmid, fver, self._precision_tag(fmid)
                        )
                    )
                    if fhit is not None:
                        return self._cached_future(
                            fhit, t0, lane, tenant, model, stream, frame
                        )
            # split serving: the key carries the SERVED arm's version,
            # not the live pointer — two versions serve concurrently
            # under a split and must never share cache entries
            version = (
                arm_version if arm_version is not None
                else self._live_version(serve_model)
            )
            if version is not None:
                reg = getattr(self.runner, "registry", None)
                mid = (
                    serve_model if serve_model is not None
                    else getattr(self.runner, "default_model", None)
                    or reg.default_model
                )
                cache_key = self.response_cache.key_for(
                    im, mid, version, self._precision_tag(mid)
                )
                hit = self.response_cache.get(cache_key)
                if hit is not None:
                    # byte-identical by construction: the stored arrays
                    # ARE what the miss returned (callers treat
                    # detections as immutable)
                    return self._cached_future(
                        hit, t0, lane, tenant, model, stream, frame
                    )
        cap = self.batcher.max_queue
        if self._routed:
            # load shedding: scale the effective intake capacity by the
            # pool's healthy fraction — when half the replicas are out,
            # rejecting at half queue depth beats queueing work the pool
            # cannot clear before its deadlines
            frac = self.runner.healthy_fraction()
            cap = max(1, int(self.batcher.max_queue * frac))
            if frac == 0.0 or self.batcher.pending() >= cap:
                self.metrics.inc("shed")
                self.metrics.inc("rejected")
                if tenant is not None:
                    self.metrics.record_tenant(tenant, shed=True)
                if streamed:
                    self._stream_cancel(stream, frame)
                raise QueueFull(
                    f"shedding load: healthy fraction {frac:.2f}, "
                    f"effective queue capacity {cap if frac else 0}"
                )
        if self.tenants is not None and tenant is not None:
            # shed the over-budget tenant FIRST: past the pressure
            # threshold, a tenant already holding more than its weight
            # share of the backlog is rejected while under-share tenants
            # keep landing until the hard cap — overload cost falls on
            # whoever caused it
            pending = self.batcher.pending()
            if pending >= self.shed_fraction * cap:
                by_t = self.batcher.queued_by_tenant()
                if self.tenants.over_share(tenant, by_t):
                    from mx_rcnn_tpu.serve.tenancy import TenantOverBudget

                    self.tenants.note_shed(tenant)
                    self.metrics.inc("tenant_shed")
                    self.metrics.inc("shed")
                    self.metrics.inc("rejected")
                    self.metrics.record_tenant(tenant, shed=True)
                    if streamed:
                        self._stream_cancel(stream, frame)
                    raise TenantOverBudget(
                        f"shedding tenant {tenant!r}: holds "
                        f"{by_t.get(tenant, 0)}/{pending} queued requests, "
                        f"over its fair share under pressure"
                    )
        deadline = (
            time.monotonic() + deadline_s if deadline_s is not None else None
        )
        try:
            # model passed only when explicit, so runner fakes/stubs with
            # the legacy two-arg make_request keep working unchanged
            if serve_model is None:
                req = self.runner.make_request(im, deadline=deadline)
            else:
                req = self.runner.make_request(
                    im, deadline=deadline, model=serve_model
                )
            req.seq = seq
            req.lane = lane
            req.tenant = tenant
            req.cache_key = cache_key
            if streamed:
                req.stream = stream
                req.frame = int(frame)
            req.masks = bool(masks)
            if cascade_first:
                # keep the validated pixels so an escalation can
                # re-prepare them for the flagship family's config
                req.cascade = True
                req.raw_image = im
            if digest is not None:
                req.digest = digest
                if self._quarantine is not None:
                    req.budget = RetryBudget(self._retry_budget)
            if arm_version is not None:
                # candidate-arm requests release as a batch-of-1 (solo):
                # a device batch is never a mix of arms, so one predict
                # serves exactly one version
                req.arm_version = arm_version
                req.solo = True
            self.batcher.submit(req)
        except Exception:
            self.metrics.inc("rejected")
            if streamed:
                # withdraw the registration or the stream deadlocks on
                # the permanent gap
                self._stream_cancel(stream, frame)
            raise
        with self._live_lock:
            self._live[id(req)] = req
            if req.future.done():
                # a concurrent sweep resolved it between batcher.submit
                # and here — don't leave a dead entry in the live set
                self._live.pop(id(req), None)
        self.metrics.inc("submitted")
        self.metrics.record_queue_depth(self.batcher.pending())
        return req.future

    def _cached_future(
        self,
        hit,
        t0: float,
        lane: str,
        tenant: Optional[str],
        model: Optional[str],
        stream: Optional[str] = None,
        frame: Optional[int] = None,
    ) -> Future:
        """Resolve a response-cache hit: a pre-completed Future plus the
        same request accounting a recompute would have produced.  A
        stream-tagged hit still goes through the delivery gate — a
        cached frame N+1 must not resolve before in-flight frame N."""
        f: Future = Future()

        def fire() -> bool:
            try:
                f.set_result(hit)
                return True
            except InvalidStateError:
                return False

        if stream is None:
            fire()
        else:
            self.streams.settle(stream, int(frame), fire)
        self.metrics.inc("submitted")
        self.metrics.inc("completed")
        e2e = time.monotonic() - t0
        self.metrics.e2e.record(e2e)
        self.metrics.record_lane(lane, e2e_s=e2e)
        self.metrics.record_tenant(tenant, e2e_s=e2e)
        if model is not None:
            self.metrics.record_model(model, e2e)
        return f

    # ------------------------------------------------------------- device
    def _expire_swept(self, req: Request, now: float) -> None:
        """Batcher sweep hook: a queued request's deadline passed before
        any batch could include it — fail it NOW (the client has already
        moved on) instead of letting it occupy queue and batch slots
        until pickup.  Runs under the batcher's condition lock; both
        callees only take leaf locks."""
        self.metrics.inc("expired")
        self.metrics.record_lane(req.lane, expired=True)
        self.metrics.record_tenant(req.tenant, expired=True)
        self._resolve(
            req,
            exc=DeadlineExceeded(
                f"deadline passed {now - req.deadline:.3f}s before "
                f"device pickup (swept from queue)"
            ),
        )

    def _resolve(self, req: Request, result=None,
                 exc: Optional[BaseException] = None) -> bool:
        """Resolve one request exactly once and retire it from the live
        registry; False when it already resolved elsewhere (e.g. swept
        by a concurrent ``stop``).

        Stream-tagged requests route through the StreamTable gate:
        delivery (success AND failure — a client never sees frame N+1
        before learning frame N's fate) waits for every earlier frame
        of the stream, while cross-stream and untagged resolutions are
        untouched.  True here means the settlement was ACCEPTED — it
        fires now or when the stream gap closes, exactly once."""
        with self._live_lock:
            self._live.pop(id(req), None)

        def fire() -> bool:
            try:
                if exc is not None:
                    req.future.set_exception(exc)
                else:
                    req.future.set_result(result)
                return True
            except InvalidStateError:
                return False

        if req.stream is None:
            return fire()
        return self.streams.settle(req.stream, req.frame, fire)

    def _assemble_loop(self) -> None:
        batch_numbers = itertools.count(1)
        while True:
            with tracing.span(tracing.SERVE_BATCH_WAIT):
                batch_reqs = self.batcher.next_batch()
            if batch_reqs is None:
                return
            if self._aborting:
                stopped = EngineStopped("engine aborted before dispatch")
                for r in batch_reqs:
                    if self._resolve(r, exc=stopped):
                        self.metrics.inc("stopped")
                continue
            now = time.monotonic()
            live: List[Request] = []
            for r in batch_reqs:
                if r.expired(now):
                    self.metrics.inc("expired")
                    self.metrics.record_lane(r.lane, expired=True)
                    self.metrics.record_tenant(r.tenant, expired=True)
                    self._resolve(
                        r,
                        exc=DeadlineExceeded(
                            f"deadline passed {now - r.deadline:.3f}s before "
                            f"device pickup"
                        ),
                    )
                else:
                    self.metrics.queue_wait.record(r.picked_t - r.enqueue_t)
                    live.append(r)
            self.metrics.record_queue_depth(self.batcher.pending())
            if not live:
                continue
            number = next(batch_numbers)
            tracing.set_batch(number)
            if tracing.enabled():
                # the same subtraction queue_wait records, written onto
                # the trace beside the ids that join the batch's stages
                waits = [
                    round((r.picked_t - r.enqueue_t) * 1e3, 3) for r in live
                ]
                with tracing.span(
                    tracing.SERVE_PICKUP, batch=number, n=len(live),
                    reqs=[r.seq for r in live], wait_ms=max(waits),
                    wait_ms_each=waits,
                ):
                    pass
            batch = self.runner.assemble(live)
            # pool submit blocks at depth=in_flight: at most in_flight
            # batches on the device (the old explicit semaphore)
            self._pool.submit(self._complete, live, batch, number)

    def _complete(
        self, reqs: List[Request], batch: Dict[str, np.ndarray],
        number: int = 0,
    ) -> None:
        # runs on a completion-pool worker; the pool's depth slot is
        # released when this returns, unblocking the assembler
        tracing.set_batch(number)
        t0 = time.monotonic()
        model = reqs[0].model
        lane = reqs[0].lane
        # model kwarg only when the batch carries one (legacy runner
        # fakes keep their run(batch) signature)
        mkw = {} if model is None else {"model": model}
        # rollout split (ISSUE 17): a candidate-arm request is always
        # solo, so the whole batch shares one arm_version
        arm_ver = reqs[0].arm_version
        served_version: Optional[int] = None
        try:
            if arm_ver is not None and self.rollout is not None:
                try:
                    out = self.runner.run_version(
                        batch, version=arm_ver, **mkw
                    )
                    served_version = arm_ver
                except Exception as arm_e:  # noqa: BLE001 — any arm failure
                    # the candidate arm failed (rolled back mid-flight,
                    # or the candidate itself raised): count it as
                    # evidence, then serve the request on the incumbent
                    # — a rollout never loses a request
                    self.rollout.note_arm_error(
                        self._resolved_mid(model), arm_e
                    )
                    out = self._run_batch(batch, reqs, lane, mkw)
            else:
                out = self._run_batch(batch, reqs, lane, mkw)
        except Exception as e:
            self._settle_failed(reqs, e)
            return
        done = time.monotonic()
        self.metrics.service.record(done - t0)
        self.metrics.record_batch(len(reqs), self.runner.max_batch)
        self.metrics.record_lane_batch(lane, len(reqs), self.runner.max_batch)
        with tracing.span(
            tracing.SERVE_POSTPROCESS, batch=number, n=len(reqs)
        ):
            self._deliver(reqs, batch, out, mkw, arm_ver, served_version)

    def _deliver(
        self, reqs: List[Request], batch: Dict[str, np.ndarray], out,
        mkw: Dict, arm_ver: Optional[int], served_version: Optional[int],
    ) -> None:
        """Per-request postprocess and resolution of a fetched batch."""
        model = reqs[0].model
        for k, r in enumerate(reqs):
            # deadline re-check at completion: a request that expired
            # while its batch waited behind a slow/hedged predict must
            # report DeadlineExceeded, not a stale success
            if r.expired():
                self.metrics.inc("expired")
                self.metrics.record_lane(r.lane, expired=True)
                self.metrics.record_tenant(r.tenant, expired=True)
                self._resolve(
                    r,
                    exc=DeadlineExceeded(
                        "deadline passed while the batch was in flight"
                    ),
                )
                continue
            try:
                if r.masks:
                    # streaming mask serve: canvas-space RLEs from the
                    # device-paste path (host keeps only RLE encoding);
                    # result = (cls_dets, rles), paste cost counted
                    cls_dets, rles = self.runner.mask_rles_for(
                        out, batch, k, orig_hw=r.orig_hw, **mkw
                    )
                    dets = (cls_dets, rles)
                    lp = getattr(self.runner, "last_paste_ms", None)
                    if lp is None:
                        ref = getattr(self.runner, "_ref", None)
                        lp = getattr(ref, "last_paste_ms", 0.0)
                        lb = getattr(ref, "last_paste_bytes", 0)
                    else:
                        lb = getattr(self.runner, "last_paste_bytes", 0)
                    self.metrics.record_paste(lp or 0.0, lb or 0)
                else:
                    dets = self.runner.detections_for(
                        out, batch, k, orig_hw=r.orig_hw, **mkw
                    )
            except Exception as e:  # postprocess bug: fail this request
                self.metrics.inc("failed")
                if model is not None:
                    self.metrics.record_model(model, ok=False)
                self.metrics.record_lane(r.lane, ok=False)
                self.metrics.record_tenant(r.tenant, ok=False)
                self._resolve(r, exc=e)
                continue
            if r.cascade and not r.escalated and self.cascade is not None:
                # confidence gate (ISSUE 18): pure host numpy over the
                # decoded cheap-pass detections — no lock held, nothing
                # on device.  Sufficient → the cheap answer ships below
                # under the CHEAP family's cache key; uncertain → the
                # request re-enters the batcher as a flagship request
                # and nothing about this pass is cached or resolved.
                if self.cascade.sufficient(dets[0] if r.masks else dets):
                    self.metrics.inc("first_pass_sufficient")
                else:
                    self.metrics.inc("escalations")
                    self._escalate(r)
                    continue
            if r.cache_key is not None and self.response_cache is not None:
                # store only if the version that SERVED is still the one
                # the key was minted against — a swap that landed
                # mid-flight, or a candidate arm that fell back to the
                # incumbent, must not seed the cache under a version
                # that did not produce these bytes
                if arm_ver is not None:
                    ok_put = (
                        served_version is not None
                        and served_version == r.cache_key[1]
                    )
                else:
                    ok_put = self._live_version(model) == r.cache_key[1]
                if ok_put:
                    self.response_cache.put(r.cache_key, dets)
            if self._quarantine is not None and r.digest is not None:
                # a suspect that completes cleanly was an innocent
                # co-batched bystander: drop the suspicion
                if self._quarantine.exonerate(r.digest):
                    self.metrics.inc("exonerated")
            self.metrics.inc("completed")
            e2e_s = time.monotonic() - r.enqueue_t
            self.metrics.e2e.record(e2e_s)
            if model is not None:
                self.metrics.record_model(model, e2e_s)
            self.metrics.record_lane(
                r.lane, e2e_s, queue_wait_s=r.picked_t - r.enqueue_t
            )
            self.metrics.record_tenant(
                r.tenant, e2e_s, queue_wait_s=r.picked_t - r.enqueue_t
            )
            if self.rollout is not None:
                mid_r = self._resolved_mid(model)
                sv = (
                    served_version if served_version is not None
                    else self._live_version(model)
                )
                if mid_r is not None and sv is not None:
                    self.metrics.record_version(mid_r, sv, e2e_s)
                    self.rollout.note_serve(mid_r, sv, True, e2e_s)
                if arm_ver is None and mid_r is not None:
                    # shadow lane: mirror the incumbent's resolved
                    # response for off-SLO candidate re-scoring (a full
                    # queue drops, never blocks this thread)
                    self.rollout.mirror(mid_r, r, dets)
            self._resolve(r, dets)

    def _run_batch(
        self, batch: Dict[str, np.ndarray], reqs: List[Request],
        lane: str, mkw: Dict,
    ):
        """The incumbent (live-version) predict path: pool routing with
        containment plumbing when routed, engine-side RetryPolicy when
        not — factored out of :meth:`_complete` so the rollout's
        candidate-arm fallback reuses it verbatim."""

        def attempt_run(attempt: int):
            if attempt:
                self.metrics.inc("retried")
            return self.runner.run(batch, **mkw)

        if self._routed:
            # the pool retries/hedges/fails-over internally — the
            # engine's own RetryPolicy would rerun an already-hedged
            # batch; the tightest live deadline drives the hedge,
            # and the lane tag tightens it further for interactive
            deadlines = [r.deadline for r in reqs if r.deadline is not None]
            rkw = dict(mkw)
            if self._quarantine is not None:
                # containment: the pool sees member identities and a
                # shared budget view (one re-dispatch re-runs every
                # member, so one spend decrements each)
                rkw["digests"] = tuple(r.digest for r in reqs)
                rkw["budget"] = BatchBudget([r.budget for r in reqs])
            return self.runner.run(
                batch, deadline=min(deadlines) if deadlines else None,
                lane=lane, **rkw,
            )
        return self.retry.run(attempt_run)

    # -------------------------------------------------- containment triage
    def _fail_one(self, req: Request,
                  exc: BaseException) -> None:
        self.metrics.inc("failed")
        if req.model is not None:
            self.metrics.record_model(req.model, ok=False)
        self.metrics.record_lane(req.lane, ok=False)
        self.metrics.record_tenant(req.tenant, ok=False)
        self._resolve(req, exc=exc)

    def _settle_failed(self, reqs: List[Request],
                       exc: BaseException) -> None:
        """Batch-level failure triage.  Without containment this is the
        legacy wholesale fail.  With it, each member settles on its own:
        a quarantined digest fails fast as :class:`PoisonRequest`, a
        member with budget left is split out and resubmitted solo (so
        the next trip attributes unambiguously and innocents stop
        co-tripping with the poison), and a spent budget resolves
        :class:`RetriesExhausted`."""
        qt = self._quarantine
        for r in reqs:
            if qt is not None and r.digest is not None \
                    and qt.quarantined(r.digest):
                self.metrics.inc("poisoned")
                self._fail_one(r, PoisonRequest(
                    f"digest {r.digest[:12]} quarantined after replica "
                    f"trips"
                ))
                continue
            budget = r.budget
            if qt is not None and budget is not None \
                    and budget.remaining > 0 and self._started \
                    and not self._aborting:
                self._resubmit(r)
                continue
            if budget is not None and budget.remaining <= 0:
                e: BaseException = RetriesExhausted(
                    f"retry budget {budget.total} spent; last error: "
                    f"{exc!r}"
                )
                e.__cause__ = exc
                self.metrics.inc("exhausted")
                self._fail_one(r, e)
                continue
            self._fail_one(r, exc)

    def _resubmit(self, req: Request) -> None:
        """Solo retry of one member of a failed or implicated batch.
        The spend here is what bounds the containment loop (graftlint
        R8); ``solo`` makes the batcher release it as a batch-of-1."""
        try:
            req.budget.spend("resubmit")
        except RetriesExhausted as e:
            self.metrics.inc("exhausted")
            self._fail_one(req, e)
            return
        req.solo = True
        self.metrics.inc("resubmitted")
        try:
            self.batcher.submit(req)
        except Exception as e:  # noqa: BLE001 — closed batcher at stop
            self._fail_one(req, e)

    def _escalate(self, req: Request) -> None:
        """Re-enter an uncertain cascade first pass as a flagship
        request.  The new request carries the ORIGINAL future, lane,
        tenant, absolute deadline, enqueue time, digest, and retry
        budget — escalation changes which model serves, never the
        request's identity — and is marked ``escalated`` so it re-enters
        above the queue cap (it was admitted once, at submit) and the
        gate never runs twice.  Exactly-once: the original request's
        live-set entry is REPLACED by the escalated one in the same
        locked section, so a concurrent ``stop`` sweep resolves the
        shared future exactly once, from whichever entry it finds."""
        pol = self.cascade.policy
        if req.expired():
            self.metrics.inc("expired")
            self.metrics.record_lane(req.lane, expired=True)
            self.metrics.record_tenant(req.tenant, expired=True)
            self._resolve(req, exc=DeadlineExceeded(
                "deadline passed before escalation could re-enter"
            ))
            return
        try:
            req2 = self.runner.make_request(
                req.raw_image, deadline=req.deadline, model=pol.flagship
            )
        except Exception as e:  # noqa: BLE001 — flagship prep failed
            self._fail_one(req, e)
            return
        req2.future = req.future
        req2.seq = req.seq
        req2.lane = req.lane
        req2.tenant = req.tenant
        req2.enqueue_t = req.enqueue_t  # e2e spans both passes
        req2.digest = req.digest
        req2.budget = req.budget
        # stream identity rides the escalation: the flagship pass
        # settles the SAME (stream, frame) registration, so in-order
        # delivery survives the cascade re-entry
        req2.stream = req.stream
        req2.frame = req.frame
        req2.masks = req.masks
        req2.escalated = True
        if self.rollout is not None and self.rollout.active(pol.flagship):
            # a flagship rollout splits escalated traffic too — same
            # digest-deterministic assignment as submit, so a repeated
            # escalation lands on the same arm.  Submit only digests
            # when quarantine or a CHEAP-family rollout is on, so the
            # digest may still be missing here
            if req2.digest is None:
                req2.digest = request_digest(req.raw_image)
            arm_version = self.rollout.arm_for(pol.flagship, req2.digest)
            if arm_version is not None:
                req2.arm_version = arm_version
                req2.solo = True
        if self.response_cache is not None:
            version = (
                req2.arm_version if req2.arm_version is not None
                else self._live_version(pol.flagship)
            )
            if version is not None:
                req2.cache_key = self.response_cache.key_for(
                    req.raw_image, pol.flagship, version,
                    self._precision_tag(pol.flagship),
                )
        with self._live_lock:
            self._live.pop(id(req), None)
            self._live[id(req2)] = req2
        try:
            self.batcher.submit(req2)
        except Exception as e:  # noqa: BLE001 — closed batcher at stop
            self._fail_one(req2, e)

    # ----------------------------------------------------------- lifecycle
    def swap(
        self,
        model: str,
        checkpoint: str,
        block: bool = False,
        timeout: Optional[float] = None,
    ):
        """Hot-swap ``model`` to ``checkpoint`` while serving: launches a
        background :class:`~mx_rcnn_tpu.serve.registry.SwapController`
        (load → verify → warm → commit-between-batches → canary, with
        automatic rollback) targeting this engine's runner/pool.
        Returns the controller, or its result dict with ``block=True``
        (which raises ``SwapRolledBack``/``SwapCancelled`` inline)."""
        reg = getattr(self.runner, "registry", None)
        if reg is None:
            raise RuntimeError(
                "runner has no model registry — hot-swap needs a "
                "registry-backed ServeRunner/ReplicaPool"
            )
        return reg.swap(
            model, checkpoint, target=self.runner, block=block,
            timeout=timeout,
        )

    def admin(self, line: str):
        """Operator command surface (``tools/serve.py`` wires it):

        * ``swap <model> <checkpoint_dir>`` — blocking hot-swap
        * ``rollout <model> <checkpoint_dir>`` — blocking progressive
          rollout (attaches a default-policy controller on first use)
        * ``rollout status`` — rollout controller snapshot
        * ``models`` — registry snapshot
        """
        parts = line.split()
        if len(parts) == 3 and parts[0] == "swap":
            return self.swap(parts[1], parts[2], block=True)
        if parts == ["rollout", "status"]:
            return self.rollout.snapshot() if self.rollout else {}
        if len(parts) == 3 and parts[0] == "rollout":
            if self.rollout is None:
                self.attach_rollout()
            return self.rollout.start(parts[1], parts[2], block=True)
        if parts == ["models"]:
            reg = getattr(self.runner, "registry", None)
            return reg.snapshot() if reg is not None else {}
        raise ValueError(f"unknown admin command: {line!r}")

    # ---------------------------------------------------------- reporting
    def snapshot(self) -> Dict:
        out = self.metrics.snapshot(self.runner.compile_cache)
        out["scheduler"] = self.batcher.stats()
        streams = self.streams.snapshot()
        if streams["registered"]:
            out["streams"] = streams
        if self.response_cache is not None:
            out["response_cache"] = self.response_cache.snapshot()
        parity = getattr(self.runner, "parity", None)
        if parity:
            out["parity"] = dict(parity)
        if self._pool is not None:
            out["completion"] = self._pool.stats()
        if self._routed:
            out["pool"] = self.runner.snapshot()
        if self._quarantine is not None:
            out["quarantine"] = self._quarantine.snapshot()
        if self.tenants is not None:
            out["tenancy"] = self.tenants.snapshot()
        if self.autoscaler is not None:
            out["autoscaler"] = self.autoscaler.snapshot()
        if self.rollout is not None:
            out["rollout"] = self.rollout.snapshot()
        if self.cascade is not None:
            out["cascade"] = self.cascade.snapshot()
        dmm = getattr(self.runner, "device_ms_by_model", None)
        if dmm:
            # single-runner engines surface the cost counter directly;
            # routed pools already merge it into out["pool"]["overlap"]
            out["device_ms_by_model"] = {
                k: round(v, 3) for k, v in dmm.items()
            }
        reg = getattr(self.runner, "registry", None)
        if reg is not None:
            out["registry"] = reg.snapshot()
        return out
