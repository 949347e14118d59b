"""Resilience layer: guarded train loop, retry policy, step watchdog.

The reference framework had no failure recovery at all (SURVEY §5.4: a
GPU failure killed the run; restart was manual from the last epoch), and
the preemption-only story here left three live gaps: a non-finite loss
was only *warned about* after the run was destroyed, a hung step stalled
until an external ``timeout -k`` (the exact ``MULTICHIP_r04`` rc=124
failure), and nothing rolled training back past a poison batch.  This
module closes them:

- :class:`RetryPolicy` — deterministic (jitter-free) bounded retry,
  shared by the guarded loop and the data loader.
- :class:`GuardedLoop` — wraps a train ``step_fn``; per-step finite-loss
  and loss-spike checks on the already-fetched aux, retry with
  exponential LR backoff, rollback to the last good in-memory snapshot,
  and skip-forward past the poison batch, with a bad-batch budget so
  silent divergence can't masquerade as training.
- :class:`StepWatchdog` — wall-clock timer per step; on expiry dumps the
  last good snapshot as a resumable checkpoint and aborts the process
  with :data:`WATCHDOG_EXIT_CODE` (distinct from ``timeout``'s 124), so
  the scheduler can tell "hung step" from "killed externally".

Fault injection for all of these lives in ``mx_rcnn_tpu/utils/faults.py``
(env-driven, deterministic); ``tests/test_resilience.py`` exercises every
recovery path on CPU.
"""

from __future__ import annotations

import logging
import sys
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from mx_rcnn_tpu.utils import faults

logger = logging.getLogger(__name__)

# exit status of a watchdog abort.  75 = EX_TEMPFAIL ("try again later"):
# the run dumped a resumable checkpoint, so a supervisor should restart
# with --resume.  Distinct from timeout(1)'s 124 and the test harness's 70.
WATCHDOG_EXIT_CODE = 75

# exit status of a run that COMPLETED but on a shrunken mesh (elastic
# degraded-continue: a replica was lost mid-run and never regrew).  The
# work finished — checkpoints are valid — but throughput and the
# effective global batch were reduced, so a supervisor may want to
# reschedule at full size.  Distinct from 75 ("restart me") and 0.
DEGRADED_EXIT_CODE = 76


class TrainingDiverged(RuntimeError):
    """Raised when the bad-batch budget is exhausted: the run is not
    recovering by skipping, so continuing would silently train garbage."""


def host_copy(tree):
    """Host-side snapshot of ``tree`` that OWNS its memory.

    ``jax.device_get`` on the CPU backend returns zero-copy numpy VIEWS
    of the runtime buffers.  A donating train step hands exactly those
    buffers back to XLA for reuse, so a snapshot (or a returned param
    tree) taken as a bare ``device_get`` silently mutates under the
    caller — or segfaults once the buffer is unmapped.  Every host tree
    that must outlive the device state (rollback snapshots, ``fit``'s
    returned params, best-checkpoint captures) goes through this copy;
    re-placement is safe because ``device_put`` copies host memory.
    """
    import jax

    return jax.tree_util.tree_map(np.array, jax.device_get(tree))


@dataclass(frozen=True)
class RetryPolicy:
    """Bounded deterministic retry — no jitter by design, so a replayed
    run retries at the identical points and the fault-injection tests are
    exactly reproducible.

    ``tries`` is the total attempt count; ``delay`` sleeps before retry
    ``k`` for ``delay * backoff**(k-1)`` seconds (0 = no sleep, the
    default: loader retries are disk/NFS hiccups where immediate retry is
    right, and tests must not sleep).
    """

    tries: int = 3
    delay: float = 0.0
    backoff: float = 2.0

    def run(self, fn: Callable[[int], Any]) -> Any:
        """Call ``fn(attempt)`` until it returns; re-raise the last
        exception once ``tries`` attempts failed."""
        for attempt in range(max(1, self.tries)):
            try:
                return fn(attempt)
            except Exception:
                if attempt + 1 >= max(1, self.tries):
                    raise
                if self.delay:
                    time.sleep(self.delay * self.backoff**attempt)


# The one retry tuning surface (ISSUE 6 satellite): every bounded-retry
# site — the loader's record reads, the serving engine's single-runner
# batch retry, and a pool replica's in-place predict retry — constructs
# its policy here, so serve and train faults share one set of constants
# instead of the per-module literals they used to duplicate.
#
# "replica" is deliberately tighter than "serve": a pooled dispatch that
# keeps failing should fail over to ANOTHER replica (the router's job)
# rather than burn its latency budget retrying in place.
RETRY_PRESETS: Dict[str, RetryPolicy] = {
    "loader": RetryPolicy(tries=3, delay=0.0),
    "serve": RetryPolicy(tries=3, delay=0.0),
    "replica": RetryPolicy(tries=2, delay=0.0),
}


def make_retry_policy(kind: str, **overrides) -> RetryPolicy:
    """Preset :class:`RetryPolicy` by site kind, with per-call field
    overrides (``make_retry_policy("replica", tries=1)``)."""
    import dataclasses

    base = RETRY_PRESETS[kind]
    return dataclasses.replace(base, **overrides) if overrides else base


class StepWatchdog:
    """Wall-clock guard for a single train step.

    Arm before the step, disarm after; if the step wedges (device hang,
    deadlocked collective), the timer thread dumps the caller-provided
    checkpoint and ``os._exit``s with a distinct code instead of hanging
    until an external ``timeout -k`` (MULTICHIP_r04's rc=124).  A thread
    timer rather than SIGALRM: the signal would only be delivered at a
    Python bytecode boundary, which never comes while the main thread is
    wedged inside native XLA code (same reasoning as the test harness
    watchdog in ``tests/conftest.py``).

    ``dump_fn`` runs in the timer thread and must not touch the (possibly
    wedged) device — dump a host-side snapshot, not live device state.
    """

    def __init__(
        self,
        timeout: float,
        dump_fn: Optional[Callable[[], Any]] = None,
        exit_code: int = WATCHDOG_EXIT_CODE,
        exit_fn: Optional[Callable[[int], None]] = None,
    ):
        import os

        self.timeout = float(timeout)
        self.dump_fn = dump_fn
        self.exit_code = exit_code
        self._exit = exit_fn if exit_fn is not None else os._exit
        self._timer: Optional[threading.Timer] = None

    def arm(self, tag: str = "") -> None:
        self.disarm()
        t = threading.Timer(self.timeout, self._expired, args=(tag,))
        t.daemon = True
        t.start()
        self._timer = t

    def disarm(self) -> None:
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None

    def _expired(self, tag: str) -> None:
        import faulthandler

        sys.stderr.write(
            f"\n=== StepWatchdog: step {tag or '?'} exceeded "
            f"{self.timeout:.1f}s — dumping checkpoint and aborting "
            f"(exit {self.exit_code}) ===\n"
        )
        try:
            if self.dump_fn is not None:
                path = self.dump_fn()
                if path:
                    sys.stderr.write(f"watchdog checkpoint -> {path}\n")
        except Exception as e:  # noqa: BLE001 — must still exit
            sys.stderr.write(f"watchdog checkpoint dump failed: {e!r}\n")
        faulthandler.dump_traceback(file=sys.stderr)
        sys.stderr.flush()
        self._exit(self.exit_code)


@dataclass(frozen=True)
class DivergencePolicy:
    """What the guarded loop does when a step's loss is NaN/Inf or spikes
    above ``spike_factor ×`` the running EMA.

    A bad step is retried ``retries`` times from the last snapshot, each
    retry with a fresh sampling rng and the step's effective LR scaled by
    ``lr_backoff**attempt`` (exponential backoff; a transient spike from
    a hard batch usually survives a smaller step).  Retries exhausted →
    roll back to the last good snapshot and skip the poison batch; the
    data stream continues past it.  More than ``max_bad_batches`` skips
    raise :class:`TrainingDiverged` — bounded data loss, never silent.
    """

    retries: int = 2
    lr_backoff: float = 0.5
    spike_factor: float = 20.0
    ema_decay: float = 0.9
    warmup_steps: int = 5
    max_bad_batches: int = 8


def _supports_lr_scale(fn) -> bool:
    import inspect

    try:
        return "lr_scale" in inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return False


class GuardedLoop:
    """Wrap a functional train step with divergence recovery.

    Usage (both ``core/fit.py`` and ``tools/train_end2end.py``)::

        guard = GuardedLoop(step_fn, policy=DivergencePolicy(), ...)
        for batch in loader:
            state, aux, ok = guard.step(state, batch, rng)
            if not ok:      # batch skipped after rollback
                continue    # aux/state are from the rolled-back point

    ``step_fn(state, batch, rng[, lr_scale])`` may donate its input state
    (the flagship step does), so rollback cannot simply reuse the caller's
    ``state`` — the loop keeps a host-side snapshot refreshed every
    ``snapshot_every`` accepted steps and restores from it.  A rollback
    therefore loses at most ``snapshot_every - 1`` steps of progress; the
    default of 1 is exact (and cheap on CPU); raise it where a
    full-state device→host fetch per step is the bottleneck.

    ``place_fn`` re-places a host snapshot for the device step (e.g.
    ``lambda t: replicate(t, mesh)`` under data parallelism; the default
    hands numpy arrays straight to jit, which commits them itself).
    """

    def __init__(
        self,
        step_fn: Callable,
        policy: Optional[DivergencePolicy] = None,
        watchdog: Optional[StepWatchdog] = None,
        snapshot_every: int = 1,
        place_fn: Optional[Callable[[Any], Any]] = None,
    ):
        self._step_fn = step_fn
        self.policy = policy or DivergencePolicy()
        self.watchdog = watchdog
        self.snapshot_every = max(1, int(snapshot_every))
        self._place = place_fn or (lambda tree: tree)
        self._lr_scale_ok = _supports_lr_scale(step_fn)
        self._snapshot = None
        self._since_snapshot = 0
        self._ema: Optional[float] = None
        self._seen = 0
        # counters (read by callers / tests)
        self.step_index = 0
        self.retried_steps = 0
        self.rollbacks = 0
        self.skipped_batches = 0
        self.last_loss = float("nan")

    @property
    def last_snapshot(self):
        """Newest host-side good state — what the watchdog dumps."""
        return self._snapshot

    @property
    def steps_since_snapshot(self) -> int:
        """Accepted steps since the snapshot was taken — lets a watchdog
        dump name the stream position the snapshot actually corresponds
        to (resume re-consumes, never silently skips ahead)."""
        return self._since_snapshot

    def _is_bad(self, loss: float) -> Tuple[bool, str]:
        if not np.isfinite(loss):
            return True, "non-finite"
        if (
            self._ema is not None
            and self._seen >= self.policy.warmup_steps
            and loss > self.policy.spike_factor * self._ema
        ):
            return True, f"spike {loss:.4g} > {self.policy.spike_factor}x ema {self._ema:.4g}"
        return False, ""

    # The check/accept pair is public so core/pipeline.py's deferred
    # flush applies the IDENTICAL divergence policy K steps late.
    def check_loss(self, loss: float) -> Tuple[bool, str]:
        """Divergence check against the current EMA/warmup state; returns
        ``(bad, reason)`` without mutating anything."""
        return self._is_bad(loss)

    def note_good(self, loss: float) -> None:
        """Record an accepted loss: advance the EMA, warmup counter, and
        snapshot age exactly as an accepted in-loop step would."""
        self._seen += 1
        self._since_snapshot += 1
        self._ema = (
            loss
            if self._ema is None
            else self.policy.ema_decay * self._ema
            + (1.0 - self.policy.ema_decay) * loss
        )
        self.last_loss = loss

    def step(
        self, state: Any, batch: Dict[str, Any], rng: Any
    ) -> Tuple[Any, Dict[str, Any], bool]:
        """Run one guarded step.  Returns ``(state, host_aux, accepted)``;
        on a skipped (poison) batch, ``state`` is the rolled-back state
        and ``accepted`` is False."""
        import jax

        idx = self.step_index
        self.step_index += 1
        if self._snapshot is None or self._since_snapshot >= self.snapshot_every:
            # BEFORE the step, and as an owning copy: the step may donate
            # these buffers, and a device_get view would alias them
            self._snapshot = host_copy(state)
            self._since_snapshot = 0

        aux_host: Dict[str, Any] = {}
        try:
            if self.watchdog is not None:
                self.watchdog.arm(tag=str(idx))
            for attempt in range(self.policy.retries + 1):
                if attempt == 0:
                    a_state, a_rng = state, rng
                else:
                    # fresh in-graph sampling draw; restart from snapshot
                    # (the failed attempt may have consumed donated buffers)
                    a_state = self._place(self._snapshot)
                    a_rng = jax.random.fold_in(rng, 7919 + attempt)
                kwargs = {}
                if attempt > 0 and self._lr_scale_ok:
                    kwargs["lr_scale"] = self.policy.lr_backoff**attempt
                faults.stall(idx)
                new_state, aux = self._step_fn(a_state, batch, a_rng, **kwargs)
                aux_host = dict(jax.device_get(aux))
                loss = float(np.mean(np.asarray(aux_host.get("loss", np.nan))))
                loss = faults.corrupt_loss(idx, loss)
                aux_host["loss"] = loss
                bad, why = self._is_bad(loss)
                if not bad:
                    self.note_good(loss)
                    return new_state, aux_host, True
                self.retried_steps += 1
                logger.warning(
                    "guarded step %d attempt %d diverged (%s)%s",
                    idx, attempt, why,
                    "" if attempt >= self.policy.retries
                    else f" — retrying with lr x{self.policy.lr_backoff**(attempt + 1):g}",
                )
        finally:
            if self.watchdog is not None:
                self.watchdog.disarm()

        # retries exhausted: roll back and skip the poison batch
        self.rollbacks += 1
        self.skipped_batches += 1
        logger.error(
            "guarded step %d: retries exhausted — rolling back to last "
            "snapshot and skipping the batch (%d/%d skips used)",
            idx, self.skipped_batches, self.policy.max_bad_batches,
        )
        if self.skipped_batches > self.policy.max_bad_batches:
            raise TrainingDiverged(
                f"{self.skipped_batches} batches skipped after rollback "
                f"(budget {self.policy.max_bad_batches}) — loss is not "
                f"recovering; aborting instead of silently training garbage"
            )
        return self._place(self._snapshot), aux_host, False
