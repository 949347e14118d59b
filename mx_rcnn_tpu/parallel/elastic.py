"""Elastic data parallelism: survive device loss mid-run.

The reference trainer (``train_end2end.py``'s ``Module.fit`` over
KVStore('device')) died whole-job on any device error; our static
``parallel/mesh.py`` mesh kept that failure mode — one lost or wedged
replica aborts the run and throws away the surviving chips.  This module
makes the mesh a *membership*:

- :class:`MeshMonitor` — replica bookkeeping.  Detection is the per-step
  heartbeat the DP step already is: every train step ends in a pmean
  over ``'data'``, so a dead or wedged replica surfaces as the dispatch
  raising (injected deterministically via ``MX_RCNN_FAULTS``
  ``device_lost@STEP.REPLICA`` / ``device_wedge@STEP.REPLICA:DUR``, or a
  real XlaRuntimeError).  Health probes for regrow come from
  ``faults.down_replicas`` — a pure function of (spec, step), never wall
  clock — and regrow is gated behind the PR 6 circuit-breaker idiom:
  cooldown counted in checkpoint boundaries, doubled per flap, capped.
- :class:`ElasticLoop` — wraps the PR 4 :class:`PipelinedLoop`.  On a
  device fault it drains nothing from the broken mesh: the in-flight
  window's device aux handles are discarded, an **emergency committed
  checkpoint** is written from the loop's host-side window anchor, the
  execution context is rebuilt over the survivors (pmean renormalizes
  itself — ``make_train_step`` divides grads by a runtime
  ``psum(1, 'data')``), state is re-placed from the anchor, and the
  window **including the poison step** is replayed at the same stream
  coordinates.  Replay is bit-identical to a fresh run started on the
  small mesh at the anchor (the PR 2/PR 4 byte-equivalence bar): the
  sampling rng folds ``state.step``, the anchor restores it, and
  :func:`~mx_rcnn_tpu.parallel.mesh.take_replica_rows` keeps the batch a
  pure function of the survivor COUNT.  At most the K-step pipeline
  window is re-executed; no step is lost.
- :func:`make_elastic_factory` — builds the real shard_map substrate for
  an active-ordinal set; tests drive :class:`ElasticLoop` with cheap
  numpy factories through the same interface.

Multi-host, the survivor set is agreed through
``distributed.agree_on_down`` (one allgather on the shrink path) so
every process rebuilds the identical mesh.
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from mx_rcnn_tpu.core.pipeline import PipelinedLoop
from mx_rcnn_tpu.core.resilience import (
    DivergencePolicy,
    StepWatchdog,
    host_copy,
)
from mx_rcnn_tpu.parallel import distributed
from mx_rcnn_tpu.utils import faults

logger = logging.getLogger(__name__)


class NoSurvivorsError(RuntimeError):
    """A device fault left no replicas to shrink onto (or the victim
    could not be identified) — the run cannot continue degraded."""


@dataclass(frozen=True)
class ElasticContext:
    """Execution substrate for one active-replica set: the jitted step,
    state placement (replicate onto the survivor mesh), and batch
    placement (truncate the base-sized global batch, then shard)."""

    active: Tuple[int, ...]
    step_fn: Callable
    place_state: Callable[[Any], Any]
    place_batch: Callable[[Any], Any]
    mesh: Any = None


def classify_device_fault(exc: BaseException):
    """``(kind, victim_ordinal_or_None)`` when ``exc`` is a device-level
    failure the elastic loop should absorb, else None (the exception is
    not ours — divergence, watchdog, injection of another phase — and
    must propagate to the resilience layer that owns it)."""
    if isinstance(exc, faults.InjectedDeviceFault):
        return exc.fault_kind, exc.replica
    if type(exc).__name__ in ("XlaRuntimeError", "JaxRuntimeError"):
        msg = str(exc).lower()
        if any(
            t in msg
            for t in ("device", "halted", "ici", "dcn", "collective",
                      "slice health", "preempted worker")
        ):
            return "device_lost", None
    return None


@dataclass(frozen=True)
class RegrowPolicy:
    """Circuit-breaker gating for mesh re-expansion.

    Counted in checkpoint BOUNDARIES — deterministic run coordinates,
    the elastic twin of ``serve/replica.py``'s wall-clock breaker
    (backoff doubled per trip inside a flap window, capped).  A "flap"
    is a shrink that lands within ``flap_window`` boundaries of a
    regrow: the replica came back, rejoined, and died again — each flap
    doubles the boundary cooldown up to ``max_backoff``.
    """

    cooldown: int = 1
    flap_window: int = 8
    max_backoff: int = 8


class MeshMonitor:
    """Replica membership, health probing, and the regrow breaker.

    ``probe_fn(step) -> iterable of down ordinals`` defaults to the
    deterministic ``faults.down_replicas`` injector probe; a real
    deployment can wire a hardware health source with the same shape.
    """

    def __init__(
        self,
        n_replicas: int,
        policy: Optional[RegrowPolicy] = None,
        probe_fn: Optional[Callable[[int], Sequence[int]]] = None,
    ):
        self.base = tuple(range(int(n_replicas)))
        self.active = self.base
        self.policy = policy or RegrowPolicy()
        self._probe = probe_fn or (lambda step: faults.down_replicas(step))
        self.transitions: List[Dict[str, Any]] = []
        self.boundaries = 0
        self.shrinks = 0
        self.regrows = 0
        self.flaps = 0
        self._last_shrink_boundary: Optional[int] = None
        self._last_regrow_boundary: Optional[int] = None
        self._last_flap_boundary: Optional[int] = None
        self._backoff = self.policy.cooldown

    @property
    def degraded(self) -> bool:
        return len(self.active) < len(self.base)

    def probe_down(self, step: int) -> frozenset:
        """Base ordinals reported down at stream position ``step``."""
        return frozenset(int(r) for r in self._probe(step))

    def note_shrink(self, step: int, lost, kind: str) -> None:
        survivors = tuple(o for o in self.active if o not in lost)
        if not survivors:
            raise NoSurvivorsError(
                f"step {step}: {sorted(lost)} lost and no replicas remain"
            )
        self.active = survivors
        self.shrinks += 1
        if (
            self._last_regrow_boundary is not None
            and self.boundaries - self._last_regrow_boundary
            <= self.policy.flap_window
        ):
            # the replica flapped: rejoined at a boundary, died again —
            # double the boundary cooldown before the next attempt
            self.flaps += 1
            self._last_flap_boundary = self.boundaries
            self._backoff = min(self._backoff * 2, self.policy.max_backoff)
        self._last_shrink_boundary = self.boundaries
        self.transitions.append(
            {"step": step, "event": "shrink", "kind": kind,
             "lost": sorted(int(o) for o in lost),
             "active": list(self.active)}
        )

    def note_boundary(self) -> None:
        self.boundaries += 1
        if (
            self._last_flap_boundary is not None
            and self.boundaries - self._last_flap_boundary
            > self.policy.flap_window
        ):
            # flap history aged out: the breaker closes back down
            self._last_flap_boundary = None
            self._backoff = self.policy.cooldown

    def want_regrow(self, step: int) -> Optional[Tuple[int, ...]]:
        """The target active set when a regrow is allowed at this
        boundary, else None (still down, or the breaker is open)."""
        missing = set(self.base) - set(self.active)
        if not missing:
            return None
        back = missing - self.probe_down(step)
        if not back:
            return None
        if (
            self._last_shrink_boundary is not None
            and self.boundaries - self._last_shrink_boundary < self._backoff
        ):
            return None
        return tuple(sorted(set(self.active) | back))

    def note_regrow(self, step: int, active: Tuple[int, ...]) -> None:
        self.active = tuple(sorted(active))
        self.regrows += 1
        self._last_regrow_boundary = self.boundaries
        self.transitions.append(
            {"step": step, "event": "regrow", "active": list(self.active)}
        )

    def stats(self) -> Dict[str, Any]:
        return {
            "base_replicas": len(self.base),
            "active_replicas": len(self.active),
            "shrinks": self.shrinks,
            "regrows": self.regrows,
            "flaps": self.flaps,
            "boundaries": self.boundaries,
            "transitions": list(self.transitions),
        }


class ElasticLoop:
    """A :class:`PipelinedLoop` that survives device loss.

    ``factory(active) -> ElasticContext`` builds the execution substrate
    for an active-ordinal tuple; the loop rebuilds it on every
    membership change.  ``checkpoint_fn(host_state, stream_step, meta)``
    (optional) writes the emergency committed checkpoint on shrink and
    returns its path.

    Recovery contract: a fault at stream step S inside a window anchored
    at W costs re-executing steps [W, S] on the survivor mesh — with the
    default ``aux_interval=1`` the anchor IS the poison step, so exactly
    one step replays.  The replay equals a fresh run started on the
    small mesh from the emergency checkpoint (tests/test_elastic.py compares
    the two final states).

    Call :meth:`flush` then :meth:`checkpoint_boundary` wherever the
    trainer checkpoints; regrow happens only there, behind the monitor's
    breaker.
    """

    def __init__(
        self,
        factory: Callable[[Tuple[int, ...]], ElasticContext],
        n_replicas: int,
        *,
        policy: Optional[DivergencePolicy] = None,
        watchdog: Optional[StepWatchdog] = None,
        aux_interval: int = 1,
        regrow: Optional[RegrowPolicy] = None,
        monitor: Optional[MeshMonitor] = None,
        checkpoint_fn: Optional[Callable[[Any, int, Dict], Optional[str]]] = None,
        agree_fn: Optional[Callable[[Any], frozenset]] = None,
    ):
        self.factory = factory
        self.monitor = monitor or MeshMonitor(n_replicas, policy=regrow)
        self.ctx = factory(self.monitor.active)
        # snapshot_every=1: the guard's own snapshot is never the elastic
        # anchor (the loop keeps its own), but exact per-step snapshots
        # keep the divergence-retry path's rollback exact too
        self.pipe = PipelinedLoop(
            self.ctx.step_fn,
            policy=policy,
            watchdog=watchdog,
            snapshot_every=1,
            place_fn=self.ctx.place_state,
            aux_interval=aux_interval,
        )
        self._ckpt = checkpoint_fn
        self._agree = agree_fn or (
            lambda down: distributed.agree_on_down(down, n_replicas)
        )
        # dispatched-but-uncommitted (idx, host batch, rng), re-playable
        # against the host anchor — never device handles
        self._window: List[Tuple[int, Any, Any]] = []
        self._anchor: Any = None
        self._anchor_idx = 0
        self.emergency_ckpts: List[str] = []
        self.replayed_steps = 0
        self.recovery_s = 0.0
        self.last_recovery_s = 0.0

    @property
    def active(self) -> Tuple[int, ...]:
        return self.monitor.active

    @property
    def degraded(self) -> bool:
        return self.monitor.degraded

    # -- stepping ------------------------------------------------------
    def step(self, state, batch, rng):
        """Guarded elastic step; same ``(state, ready, ok)`` contract as
        :class:`PipelinedLoop`.  ``batch`` is the HOST global batch at
        the base size; placement (truncate + shard) happens here so a
        replay re-places against whatever mesh is current."""
        if not self._window:
            # anchor BEFORE the first dispatch of a window, as an owning
            # copy: the step donates the buffers a device_get view of
            # this state would alias
            self._anchor = host_copy(state)
            self._anchor_idx = self.pipe.next_index
        self._window.append((self.pipe.next_index, batch, rng))
        return self._drain(state, len(self._window) - 1)

    def flush(self, state):
        """Flush the pipeline window (epoch end / pre-checkpoint)."""
        try:
            state, ready, ok = self.pipe.flush(state)
        except Exception as e:  # noqa: BLE001 — classified below
            got = classify_device_fault(e)
            if got is None:
                raise
            self.replayed_steps += len(self._window)
            state = self._shrink(state, got[0], got[1],
                                 at_step=self.pipe.next_index)
            state, ready, ok = self._drain(state, 0)
            state, r2, ok2 = self.pipe.flush(state)
            ready, ok = ready + r2, ok and ok2
        if self.pipe.pending == 0:
            self._window.clear()
        return state, ready, ok

    def _drain(self, state, start: int):
        """Dispatch window entries from position ``start``; on a device
        fault, shrink and restart from the anchor (position 0)."""
        ready_out: List[Tuple[int, Dict]] = []
        ok_out = True
        i = start
        while i < len(self._window):
            idx, batch, rng = self._window[i]
            try:
                # the injected heartbeat: a dead replica fails its step
                faults.device_fault(idx, active=self.monitor.active)
                state, ready, ok = self.pipe.step(
                    state, self.ctx.place_batch(batch), rng
                )
            except Exception as e:  # noqa: BLE001 — classified below
                got = classify_device_fault(e)
                if got is None:
                    raise
                self.replayed_steps += i
                state = self._shrink(state, got[0], got[1], at_step=idx)
                i = 0
                continue
            ready_out.extend(ready)
            ok_out = ok_out and ok
            i += 1
        if self.pipe.pending == 0:
            self._window.clear()
        return state, ready_out, ok_out

    # -- membership changes --------------------------------------------
    def _shrink(self, state, kind: str, victim: Optional[int], at_step: int):
        t0 = time.perf_counter()
        down = {victim} if victim is not None else set(
            self.monitor.probe_down(at_step)
        ) & set(self.monitor.active)
        down = self._agree(down)
        if not down:
            raise NoSurvivorsError(
                f"step {at_step}: {kind} with unidentifiable victim — "
                f"cannot choose a survivor set"
            )
        prev = self.monitor.active
        self.monitor.note_shrink(at_step, down, kind)
        logger.warning(
            "elastic: %s at step %d — lost replica(s) %s; shrinking mesh "
            "%s -> %s and replaying the window from step %d",
            kind, at_step, sorted(down), list(prev),
            list(self.monitor.active), self._anchor_idx,
        )
        # emergency committed checkpoint from the HOST anchor — device
        # buffers on the broken mesh are never trusted, and the anchor's
        # stream position is exactly where a restarted run would resume
        if self._ckpt is not None:
            path = self._ckpt(
                self._anchor, self._anchor_idx,
                {"event": "shrink", "kind": kind,
                 "lost": sorted(int(o) for o in down), "step": at_step,
                 "active": list(self.monitor.active)},
            )
            if path:
                self.emergency_ckpts.append(path)
        self.ctx = self.factory(self.monitor.active)
        self.pipe.rebind(self.ctx.step_fn, self.ctx.place_state)
        self.pipe.rewind(self._anchor_idx)
        state = self.ctx.place_state(self._anchor)
        dt = time.perf_counter() - t0
        self.last_recovery_s = dt
        self.recovery_s += dt
        return state

    def checkpoint_boundary(self, state, step: Optional[int] = None):
        """Count a checkpoint boundary and regrow when allowed.

        Call AFTER :meth:`flush` (a pending window would straddle the
        mesh change).  Returns ``(state, regrown)``; on regrow the state
        was host-copied and re-placed on the expanded mesh, so the next
        step compiles (or cache-hits) the full-mesh executable.
        """
        if self.pipe.pending:
            raise RuntimeError(
                "checkpoint_boundary called with a pending pipeline "
                "window — flush first"
            )
        self.monitor.note_boundary()
        step = self.pipe.next_index if step is None else step
        target = self.monitor.want_regrow(step)
        if target is None:
            return state, False
        t0 = time.perf_counter()
        snap = host_copy(state)
        prev = self.monitor.active
        self.ctx = self.factory(target)
        self.pipe.rebind(self.ctx.step_fn, self.ctx.place_state)
        self.pipe.rewind(self.pipe.next_index)
        state = self.ctx.place_state(snap)
        self.monitor.note_regrow(step, target)
        self._window.clear()
        self._anchor, self._anchor_idx = snap, step
        dt = time.perf_counter() - t0
        self.last_recovery_s = dt
        self.recovery_s += dt
        logger.info(
            "elastic: regrow at boundary %d (step %d): %s -> %s",
            self.monitor.boundaries, step, list(prev), list(target),
        )
        return state, True

    # -- reporting -----------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        return {
            **self.monitor.stats(),
            "replayed_steps": self.replayed_steps,
            "emergency_checkpoints": len(self.emergency_ckpts),
            "recovery_s": round(self.recovery_s, 4),
            "pipeline": self.pipe.stats(),
        }


def make_elastic_factory(
    model,
    tx,
    *,
    devices=None,
    accum_steps: int = 1,
    donate: bool = True,
) -> Callable[[Tuple[int, ...]], ElasticContext]:
    """Real shard_map substrate for :class:`ElasticLoop`.

    ``devices`` fixes the base ordinal→device assignment (default: all
    of ``jax.devices()``); ``factory(active)`` builds the survivor mesh
    over exactly those devices, the DP train step on it (whose runtime
    ``psum(1, 'data')`` renormalizes the pmean to the new replica
    count), and placement functions that replicate state / truncate +
    shard the base-sized global batch.
    """
    import jax

    from mx_rcnn_tpu.parallel.mesh import (
        make_mesh,
        make_parallel_train_step,
        replicate,
        shard_batch,
        take_replica_rows,
    )

    devices = list(devices if devices is not None else jax.devices())
    n_base = len(devices)

    def factory(active: Tuple[int, ...]) -> ElasticContext:
        active = tuple(int(o) for o in active)
        mesh = make_mesh(
            n_data=len(active), n_model=1,
            devices=[devices[o] for o in active],
        )
        step_fn = make_parallel_train_step(
            model, tx, mesh, accum_steps=accum_steps, donate=donate,
        )

        def place_batch(batch):
            return shard_batch(
                take_replica_rows(batch, len(active), n_base), mesh
            )

        return ElasticContext(
            active=active,
            step_fn=step_fn,
            place_state=lambda tree: replicate(tree, mesh),
            place_batch=place_batch,
            mesh=mesh,
        )

    return factory
