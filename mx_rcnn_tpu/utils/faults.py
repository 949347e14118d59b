"""Deterministic fault injection for the resilience test harness.

Every injector is driven by the ``MX_RCNN_FAULTS`` env var (so a child
process — the watchdog subprocess test — inherits the spec) and is
keyed on deterministic run coordinates (train step index, roidb record
index, save-call ordinal), never on wall clock or an RNG: a replayed run
injects the identical faults at the identical points, which is what lets
``tests/test_resilience.py`` assert exact recovery behavior.

Spec grammar — comma-separated entries ``KIND@KEY[xTIMES][:ARG]``::

    nan_loss@STEP          NaN the observed loss at guarded step STEP
                           (every attempt: a poison batch)
    spike@STEP[xN][:F]     multiply the loss by F (default 1e4) at STEP;
                           xN bounds how many attempts fire (x1 = a
                           transient spike that a retry survives)
    record_fail@IDX[xN]    raise IOError loading roidb record IDX
                           (unbounded = permanently corrupt record;
                           x2 = two flaky reads, then the retry succeeds)
    save_crash@NCALL       raise SimulatedCrash inside the NCALLth
                           save_checkpoint (1-based), after the data is
                           written but before the atomic commit — the
                           "killed mid-save" torn state
    stall@STEP:SECONDS     sleep SECONDS at guarded step STEP (drives the
                           step past the watchdog deadline)

Serve-phase injectors (ISSUE 6) are keyed ``REPLICA.ORDINAL`` — the
replica index and its per-replica batch ordinal (every dispatch the
replica predicts, probe batches included, counts one ordinal; retry
attempts within a dispatch share the ordinal, so ``xN`` spans attempts).
``ORDINAL`` may be ``*`` to match every batch on that replica::

    predict_fail@R.B[xN]     raise InjectedPredictFault on replica R's
                             batch B (x1 = transient, absorbed by the
                             replica's RetryPolicy; unbounded = the
                             dispatch fails and the router fails over)
    predict_stall@R.B:SECS   sleep SECS inside replica R's predict of
                             batch B (default 0.25 — past the hedge
                             timeout but under the stall watchdog:
                             the hedge-win path)
    replica_wedge@R.B:SECS   sleep SECS (default 5.0 — past the stall
                             watchdog: the replica trips DRAINING, its
                             in-flight batch is requeued, and it
                             rewarms/rejoins once the wedge releases)

Poison-input injectors (ISSUE 12, query-of-death containment) are keyed
by the *request digest* — the hex string ``serve.quarantine.request_digest``
computes over the raw submitted image (a unique prefix is enough).  They
fire inside a replica's predict whenever the dispatched batch contains a
matching digest, which is what makes the poison follow the request
through requeues, hedges, and isolation probes instead of striking a
fixed (replica, ordinal) coordinate::

    poison_fail@DIGEST[xN]     raise InjectedPredictFault whenever a
                               batch containing DIGEST is predicted
                               (unbounded = a deterministic query of
                               death; x1 = a one-off coincidence the
                               quarantine table must NOT blacklist)
    poison_stall@DIGEST:SECS   sleep SECS (default 0.25 — past the hedge
                               timeout, under the stall watchdog)
    poison_wedge@DIGEST:SECS   sleep SECS (default 5.0 — past the stall
                               watchdog: the replica trips, the digest
                               is recorded as a suspect, and attribution
                               drives it to quarantine)

Swap-phase injectors (ISSUE 7) are keyed by the registry-wide swap
ordinal (1-based: the Nth ``SwapController`` the registry launches, any
model), or ``*`` for every swap.  Each fires once per swap at its
pipeline stage and raises :class:`InjectedSwapFault`, driving the
controller's rollback path::

    swap_verify_fail@N       fail swap N's manifest-verification stage
                             (the candidate never reaches the device)
    swap_warm_fail@N         fail swap N after its warmup rungs ran
                             (staged device buffers must be discarded)
    canary_fail@N            fail swap N's post-commit canary probe —
                             the committed version must roll back to
                             the previous LIVE between batches

Device-phase injectors (ISSUE 9, elastic training) are keyed
``STEP.REPLICA`` — the global train-step index at which the fault
strikes and the victim replica's ordinal in the BASE (full) mesh.  The
optional ``:DUR`` argument is a deterministic *down-window in steps*:
the replica answers :func:`down_replicas` probes as dead for stream
positions in ``[STEP, STEP+DUR)`` and healthy after, which is what
drives regrow without a single wall-clock sleep::

    device_lost@S.R[:DUR]    raise InjectedDeviceFault("device_lost")
                             when step S dispatches while replica R is
                             active.  DUR 0 (the default) = the replica
                             never returns.
    device_wedge@S.R[:DUR]   the wedged-collective flavor (default DUR
                             8: the hang clears and the replica is
                             eligible to rejoin at a later checkpoint
                             boundary).

Example::

    MX_RCNN_FAULTS="nan_loss@5,record_fail@3,save_crash@2,stall@7:30"
    MX_RCNN_FAULTS="predict_fail@0.2x1,replica_wedge@1.0:3,predict_stall@2.*x4:0.4"
    MX_RCNN_FAULTS="swap_verify_fail@1,canary_fail@2"
    MX_RCNN_FAULTS="device_lost@4.2,device_wedge@3.5:4"

Injection sites are no-ops (one env lookup) when the variable is unset,
so production paths pay nothing.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

ENV_VAR = "MX_RCNN_FAULTS"


class InjectedFault(IOError):
    """Raised by the record-load injector (an IOError so real retry
    handling treats it exactly like a disk/decode failure)."""


class SimulatedCrash(RuntimeError):
    """Raised by the save injector: stands in for SIGKILL mid-save (the
    writer cannot clean up, the ``.tmp`` dir is left uncommitted)."""


class InjectedPredictFault(RuntimeError):
    """Raised by the serve-phase injector inside a replica's predict — a
    RuntimeError, so real retry/failover handling treats it exactly like
    a device fault."""


class InjectedSwapFault(RuntimeError):
    """Raised by the swap-phase injector inside a SwapController stage —
    a RuntimeError, so the controller's rollback handling treats it
    exactly like a real verification/warmup/canary failure."""


class InjectedDeviceFault(RuntimeError):
    """Raised by the device-phase injector at a train-step dispatch — a
    RuntimeError (like jax's XlaRuntimeError), so the elastic loop's
    classification treats it exactly like a real device loss.  Carries
    the victim coordinates: ``replica`` (base-mesh ordinal) and
    ``fault_kind`` ("device_lost" | "device_wedge")."""

    def __init__(self, msg: str, replica: int, fault_kind: str):
        super().__init__(msg)
        self.replica = replica
        self.fault_kind = fault_kind


# serve-phase kinds take the compound REPLICA.ORDINAL key
_SERVE_KINDS = ("predict_fail", "predict_stall", "replica_wedge")

# poison kinds are keyed by request digest (hex-prefix string match)
_POISON_KINDS = ("poison_fail", "poison_stall", "poison_wedge")

# swap-phase kinds, keyed by the 1-based registry-wide swap ordinal
_SWAP_KINDS = {
    "verify": "swap_verify_fail",
    "warm": "swap_warm_fail",
    "canary": "canary_fail",
}

# device-phase kinds (elastic training) take the compound STEP.REPLICA key
_DEVICE_KINDS = ("device_lost", "device_wedge")

# every kind some hook consults — graftlint R6 cross-checks this against
# the hook bodies, so the whitelist cannot drift from the implementation
_KNOWN_KINDS = frozenset(
    {
        "nan_loss",
        "spike",
        "record_fail",
        "save_crash",
        "stall",
    }
    | set(_SERVE_KINDS)
    | set(_POISON_KINDS)
    | set(_SWAP_KINDS.values())
    | set(_DEVICE_KINDS)
)


@dataclass
class _Fault:
    kind: str
    key: object  # int (step/record/call) or (replica, ordinal|None) tuple
    times: Optional[int]  # None = unbounded
    arg: float
    fired: int = 0

    def fire(self) -> bool:
        if self.times is not None and self.fired >= self.times:
            return False
        self.fired += 1
        return True


@dataclass
class _Registry:
    spec: str
    faults: List[_Fault] = field(default_factory=list)
    save_calls: int = 0


_registry: Optional[_Registry] = None


def _parse_key(s: str, kind: Optional[str] = None):
    """``R.B`` / ``R.*`` → (replica, ordinal|None); bare ``*`` → None
    (match-any, the swap kinds); a raw hex-prefix string for the
    digest-keyed poison kinds; plain int otherwise."""
    if kind in _POISON_KINDS:
        return s
    if "." in s:
        r, _, o = s.partition(".")
        return (int(r), None if o == "*" else int(o))
    if s == "*":
        return None
    return int(s)


def _parse(spec: str) -> List[_Fault]:
    out = []
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        kind, _, rest = entry.partition("@")
        if kind not in _KNOWN_KINDS:
            # a typo'd injector (``predict_fial@...``) must be a hard
            # error, not a fault matrix that silently tests nothing
            raise ValueError(
                f"MX_RCNN_FAULTS: unknown injector kind {kind!r} in entry "
                f"{entry!r}; known kinds: {', '.join(sorted(_KNOWN_KINDS))}"
            )
        arg_s = None
        if ":" in rest:
            rest, _, arg_s = rest.partition(":")
        times: Optional[int] = None
        if "x" in rest:
            rest, _, times_s = rest.partition("x")
            times = int(times_s)
        defaults = {"spike": 1e4, "stall": 5.0,
                    "predict_stall": 0.25, "replica_wedge": 5.0,
                    "poison_stall": 0.25, "poison_wedge": 5.0,
                    "device_wedge": 8.0}
        out.append(
            _Fault(
                kind=kind,
                key=_parse_key(rest, kind),
                times=times,
                arg=float(arg_s) if arg_s is not None else defaults.get(kind, 0.0),
            )
        )
    return out


def _active() -> Optional[_Registry]:
    """Parse-once registry, re-parsed (with fresh fire counts) whenever
    the env var's value changes — monkeypatch.setenv in a test starts a
    clean injection state."""
    global _registry
    spec = os.environ.get(ENV_VAR, "")
    if not spec:
        _registry = None
        return None
    if _registry is None or _registry.spec != spec:
        _registry = _Registry(spec=spec, faults=_parse(spec))
    return _registry


def reset() -> None:
    """Forget fire counts (tests reusing an identical spec string)."""
    global _registry
    _registry = None


def corrupt_loss(step: int, loss: float) -> float:
    """GuardedLoop's observed-loss hook: NaN or spike injection."""
    reg = _active()
    if reg is None:
        return loss
    for f in reg.faults:
        if f.key != step:
            continue
        if f.kind == "nan_loss" and f.fire():
            return float("nan")
        if f.kind == "spike" and f.fire():
            return loss * f.arg if loss else f.arg
    return loss


def fail_record(index: int) -> None:
    """Loader hook: raise for a corrupt/missing record."""
    reg = _active()
    if reg is None:
        return
    for f in reg.faults:
        if f.kind == "record_fail" and f.key == index and f.fire():
            raise InjectedFault(f"injected read failure for record {index}")


def crash_save() -> None:
    """Checkpoint hook, called once per save_checkpoint AFTER the data
    write but BEFORE the atomic commit."""
    reg = _active()
    if reg is None:
        return
    reg.save_calls += 1
    for f in reg.faults:
        if f.kind == "save_crash" and f.key == reg.save_calls and f.fire():
            raise SimulatedCrash(
                f"injected crash during save #{reg.save_calls} "
                f"(uncommitted .tmp left behind)"
            )


def stall(step: int) -> None:
    """GuardedLoop hook: wedge this step (watchdog exercise)."""
    reg = _active()
    if reg is None:
        return
    for f in reg.faults:
        if f.kind == "stall" and f.key == step and f.fire():
            time.sleep(f.arg)


def predict_fault(replica: int, ordinal: int) -> None:
    """Replica predict hook (``serve/replica.py``): raise or stall this
    attempt.  Called once per predict ATTEMPT with the dispatch's
    (replica, ordinal) coordinates; the first matching un-exhausted
    fault fires (raise for ``predict_fail``, sleep for ``predict_stall``
    / ``replica_wedge`` — the two stalls differ only in their default
    duration relative to the hedge timeout vs the stall watchdog)."""
    reg = _active()
    if reg is None:
        return
    for f in reg.faults:
        if f.kind not in _SERVE_KINDS or not isinstance(f.key, tuple):
            continue
        r, o = f.key
        if r != replica or (o is not None and o != ordinal):
            continue
        if not f.fire():
            continue
        if f.kind == "predict_fail":
            raise InjectedPredictFault(
                f"injected predict failure: replica {replica} batch {ordinal}"
            )
        time.sleep(f.arg)
        return


def poison_input(digests) -> None:
    """Replica predict hook (``serve/replica.py``): strike any predict
    whose batch carries a matching request digest.  ``digests`` is the
    dispatch's tuple of member digests (empty when containment is off —
    one env lookup, then a no-op).  The spec key is a hex prefix of the
    full digest, so fault specs stay readable; the first matching
    un-exhausted fault fires (raise for ``poison_fail``, sleep for
    ``poison_stall`` / ``poison_wedge``)."""
    reg = _active()
    if reg is None or not digests:
        return
    for f in reg.faults:
        if f.kind not in _POISON_KINDS or not isinstance(f.key, str):
            continue
        hit = next((d for d in digests if d and d.startswith(f.key)), None)
        if hit is None:
            continue
        if not f.fire():
            continue
        if f.kind == "poison_fail":
            raise InjectedPredictFault(
                f"injected poison failure: digest {hit[:12]}"
            )
        time.sleep(f.arg)
        return


def device_fault(step: int, active=None) -> None:
    """Elastic-loop dispatch hook (``parallel/elastic.py``): strike a
    replica at train step ``step``.  ``active`` is the sequence of
    base-mesh ordinals currently IN the mesh — a fault whose victim has
    already been shrunk away cannot fire again, which is exactly what
    makes the post-shrink replay of the poison step deterministic (the
    same coordinate re-dispatches, the dead replica is gone, no raise).
    The first matching un-exhausted fault raises
    :class:`InjectedDeviceFault` carrying the victim ordinal."""
    reg = _active()
    if reg is None:
        return
    for f in reg.faults:
        if f.kind not in _DEVICE_KINDS or not isinstance(f.key, tuple):
            continue
        s, r = f.key
        if s != step or r is None:
            continue
        if active is not None and r not in active:
            continue
        if f.fire():
            raise InjectedDeviceFault(
                f"injected {f.kind}: replica {r} at step {step}"
                + (f" (down for {int(f.arg)} step(s))" if f.arg else ""),
                replica=r, fault_kind=f.kind,
            )


def down_replicas(step: int) -> frozenset:
    """Non-raising probe: which base-mesh replica ordinals are inside a
    device fault's down-window at stream position ``step``.  Purely a
    function of the spec and the step index — a replayed run sees the
    identical health timeline, so regrow decisions (taken at checkpoint
    boundaries against this probe) are deterministic.  A ``device_lost``
    with no ``:DUR`` never clears."""
    reg = _active()
    if reg is None:
        return frozenset()
    down = set()
    for f in reg.faults:
        if f.kind not in _DEVICE_KINDS or not isinstance(f.key, tuple):
            continue
        s, r = f.key
        if r is None or step < s:
            continue
        if f.arg <= 0 or step < s + int(f.arg):
            down.add(r)
    return frozenset(down)


def swap_fault(stage: str, ordinal: int) -> None:
    """SwapController hook (``serve/registry.py``): fail this swap's
    ``stage`` ("verify" | "warm" | "canary").  Called once per swap per
    stage with the registry-wide 1-based swap ordinal; a matching
    un-exhausted fault raises :class:`InjectedSwapFault`, which the
    controller handles exactly like a real gate failure (rollback)."""
    reg = _active()
    if reg is None:
        return
    kind = _SWAP_KINDS[stage]
    for f in reg.faults:
        if f.kind != kind:
            continue
        if f.key is not None and f.key != ordinal:
            continue
        if f.fire():
            raise InjectedSwapFault(
                f"injected {kind}: swap #{ordinal} ({stage} stage)"
            )
