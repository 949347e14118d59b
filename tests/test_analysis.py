"""graftlint self-tests: per-rule good/bad fixture matrix, suppression
machinery (inline pragma, baseline, stale detection), the whole-tree
zero-noise guarantee, the runtime lock-order proxy, the faults-spec
hard error, and the BENCH artifact parse guard.

Everything here is stdlib + numpy speed — no jax execution, so the
whole file runs in well under a second of tier-1 budget."""

import threading
import time
from pathlib import Path

import pytest

from mx_rcnn_tpu.analysis import engine as eng
from mx_rcnn_tpu.analysis import lockcheck
from mx_rcnn_tpu.analysis.rules_faults import FaultCoverage
from mx_rcnn_tpu.analysis.rules_futures import ExactlyOnce
from mx_rcnn_tpu.analysis.rules_hostcopy import HostCopyEscape, UseAfterDonate
from mx_rcnn_tpu.analysis.rules_jit import JitPurity
from mx_rcnn_tpu.analysis.rules_locks import LockOrder
from mx_rcnn_tpu.analysis.rules_requeue import BoundedRequeue
from mx_rcnn_tpu.analysis.rules_signals import SignalSafety

REPO = Path(__file__).resolve().parents[1]


def run_rule(src, rule, path="mx_rcnn_tpu/core/mod.py"):
    report = eng.analyze_snippets({path: src}, [rule])
    return report.findings


# ---------------------------------------------------------------- R1

R1_BAD_RETURN = """
import jax

def f(fn, batch):
    return jax.device_get(fn(batch))
"""

R1_BAD_CLOSURE = """
import jax

def g(params):
    host = jax.device_get(params)

    def rebuild():
        return host

    return rebuild
"""

R1_BAD_STORE = """
import jax

class Holder:
    def grab(self, tree):
        self.snapshot = jax.device_get(tree)
"""

R1_GOOD = """
import jax
import numpy as np

def f(fn, batch):
    out = jax.device_get(fn(batch))
    return float(out["loss"].mean())

def g(fn, batch):
    return jax.tree_util.tree_map(np.array, jax.device_get(fn(batch)))

def h(fn, batch, consume):
    consume(jax.device_get(fn(batch)))
"""


def test_r1_fires_on_returned_view():
    fs = run_rule(R1_BAD_RETURN, HostCopyEscape())
    assert len(fs) == 1 and fs[0].rule == "R1" and fs[0].scope == "f"


def test_r1_fires_on_closure_capture():
    fs = run_rule(R1_BAD_CLOSURE, HostCopyEscape())
    assert len(fs) == 1 and "nested function" in fs[0].message


def test_r1_fires_on_attribute_store():
    fs = run_rule(R1_BAD_STORE, HostCopyEscape())
    assert len(fs) == 1 and "stored" in fs[0].message


def test_r1_silent_on_consumed_and_copied():
    assert run_rule(R1_GOOD, HostCopyEscape()) == []


# R1 against the ISSUE 13 split dispatch/complete shape: the completion
# half is exactly where a bare device_get view would escape to a caller
# that outlives the donated buffers

R1_SPLIT_BAD = """
import jax

class Runner:
    def dispatch(self, batch):
        return self._fn(self.params, batch)

    def complete(self, handle):
        return jax.device_get(handle)
"""

R1_SPLIT_GOOD = """
from mx_rcnn_tpu.core.resilience import host_copy

class Runner:
    def dispatch(self, batch):
        return self._fn(self.params, batch)

    def complete(self, handle):
        return host_copy(handle)
"""


def test_r1_fires_on_split_complete_returning_view():
    fs = run_rule(R1_SPLIT_BAD, HostCopyEscape())
    assert len(fs) == 1 and fs[0].rule == "R1"
    assert fs[0].scope == "Runner.complete"


def test_r1_silent_on_split_complete_host_copy():
    assert run_rule(R1_SPLIT_GOOD, HostCopyEscape()) == []


# R1 against the ISSUE 14 mask-fetch shape: the selected det_masks
# tensor crosses to host exactly once, through the owning-copy
# discipline — a bare device_get view of the grids escaping complete()
# is the regression the rule must keep catching
R1_MASK_BAD = """
import jax

class Runner:
    def complete(self, handle):
        out = jax.device_get(handle.outputs)
        return out["det_masks"]
"""

R1_MASK_GOOD = """
from mx_rcnn_tpu.core.resilience import host_copy

class Runner:
    def complete(self, handle):
        out = host_copy(handle.outputs)
        return out["det_masks"]
"""


def test_r1_fires_on_mask_fetch_device_get_view():
    fs = run_rule(R1_MASK_BAD, HostCopyEscape())
    assert len(fs) == 1 and fs[0].rule == "R1"
    assert fs[0].scope == "Runner.complete"


def test_r1_silent_on_mask_fetch_host_copy():
    assert run_rule(R1_MASK_GOOD, HostCopyEscape()) == []


# ---------------------------------------------------------------- R2

R2_BAD = """
import jax

def train(step, state, batch):
    step2 = jax.jit(step, donate_argnums=(0,))
    out = step2(state, batch)
    return state, out
"""

R2_BAD_FACTORY = """
from mx_rcnn_tpu.core.train import make_train_step

def train(model, tx, state, batch, rng):
    step = make_train_step(model, tx, donate=True)
    new_state, aux = step(state, batch, rng)
    print(state)
    return new_state, aux
"""

R2_GOOD = """
import jax

def train(step, state, batch):
    step2 = jax.jit(step, donate_argnums=(0,))
    state = step2(state, batch)
    return state
"""


def test_r2_fires_on_use_after_donate():
    fs = run_rule(R2_BAD, UseAfterDonate())
    assert len(fs) == 1 and "`state` read after being donated" in fs[0].message


def test_r2_fires_on_factory_donation():
    fs = run_rule(R2_BAD_FACTORY, UseAfterDonate())
    assert len(fs) == 1
    assert "`state` read after being donated to `step`" in fs[0].message


def test_r2_silent_on_rebind():
    assert run_rule(R2_GOOD, UseAfterDonate()) == []


# ---------------------------------------------------------------- R3

R3_BAD = """
import jax
from mx_rcnn_tpu.utils import faults

seen = []

@jax.jit
def step(x):
    global seen
    faults.stall(0)
    if float(x.sum()) > 0:
        x = -x
    return x
"""

R3_BAD_WRAPPED = """
import jax

def fwd(p, b):
    if b["flag"].item() > 0:
        return p
    return b

f = jax.jit(fwd, donate_argnums=(1,))
"""

R3_GOOD = """
import jax
import jax.numpy as jnp

@jax.jit
def step(x):
    y = jnp.where(x > 0, -x, x)
    return y

def helper(state):
    # not jitted: host branching is fine here
    if float(state.loss) > 1e4:
        return None
    return state
"""


def test_r3_fires_on_impure_jit_body():
    fs = run_rule(R3_BAD, JitPurity())
    msgs = " | ".join(f.message for f in fs)
    assert len(fs) == 3
    assert "global" in msgs and "faults.stall" in msgs and "float()" in msgs


def test_r3_finds_wrapper_form_jit():
    fs = run_rule(R3_BAD_WRAPPED, JitPurity())
    assert len(fs) == 1 and ".item()" in fs[0].message


def test_r3_silent_on_clean_and_unjitted():
    assert run_rule(R3_GOOD, JitPurity()) == []


# ---------------------------------------------------------------- R4

R4_CYCLE = """
import threading

class Alpha:
    def __init__(self):
        self._lock = threading.Lock()
        self.beta = None

    def do_alpha(self):
        with self._lock:
            self.beta.do_beta()

class Beta:
    def __init__(self):
        self._lock = threading.Lock()
        self.alpha = None

    def do_beta(self):
        with self._lock:
            pass

    def call_back(self):
        with self._lock:
            self.alpha.do_alpha()
"""

R4_DEVICE = """
import threading
import jax

class Holder:
    def __init__(self):
        self._lock = threading.Lock()

    def bad(self, tree):
        with self._lock:
            return jax.device_put(tree)

    def good(self, tree):
        out = jax.device_put(tree)
        with self._lock:
            self.count = 1
        return out
"""

R4_GOOD = """
import threading

class Alpha:
    def __init__(self):
        self._lock = threading.Lock()
        self.beta = None

    def do_alpha(self):
        with self._lock:
            self.beta.do_beta()

class Beta:
    def __init__(self):
        self._lock = threading.Lock()

    def do_beta(self):
        with self._lock:
            pass
"""

R4_MAKE_LOCK = """
from mx_rcnn_tpu.analysis.lockcheck import make_lock
import jax

class Holder:
    def __init__(self):
        self._lock = make_lock("Holder._lock")

    def bad(self, tree):
        with self._lock:
            return jax.jit(tree)
"""


def test_r4_fires_on_lock_cycle():
    fs = run_rule(R4_CYCLE, LockOrder(), path="mx_rcnn_tpu/serve/fx.py")
    assert any("cycle" in f.message for f in fs)


def test_r4_fires_on_device_put_under_lock():
    fs = run_rule(R4_DEVICE, LockOrder(), path="mx_rcnn_tpu/serve/fx.py")
    assert len(fs) == 1
    assert fs[0].scope == "Holder.bad" and "device" in fs[0].message


def test_r4_recognizes_make_lock_spelling():
    fs = run_rule(R4_MAKE_LOCK, LockOrder(), path="mx_rcnn_tpu/serve/fx.py")
    assert len(fs) == 1 and "Holder._lock" in fs[0].message


def test_r4_silent_on_one_way_order():
    assert run_rule(R4_GOOD, LockOrder(), path="mx_rcnn_tpu/serve/fx.py") == []


def test_r4_ignores_non_serve_modules():
    assert run_rule(R4_DEVICE, LockOrder(), path="mx_rcnn_tpu/core/fx.py") == []


# R4 against the ISSUE 16 tenancy shape: the batcher's WFQ release path
# holds the batcher condition and calls the tenant table's weight()
# (which takes TenantTable._lock as a leaf).  One-way is the shipped
# design; a table method that calls BACK into the batcher under its own
# lock closes the cycle graftlint must flag.

R4_TENANCY_BAD = """
from mx_rcnn_tpu.analysis.lockcheck import make_lock

class Batcher:
    def __init__(self):
        self._cond = make_lock("Batcher._cond")
        self.table = None

    def release(self):
        with self._cond:
            return self.table.weight("acme")

class Table:
    def __init__(self):
        self._lock = make_lock("Table._lock")
        self.batcher = None

    def weight(self, tenant):
        with self._lock:
            return 1.0

    def over_share(self, tenant):
        with self._lock:
            return self.batcher.release()
"""

R4_TENANCY_GOOD = """
from mx_rcnn_tpu.analysis.lockcheck import make_lock

class Batcher:
    def __init__(self):
        self._cond = make_lock("Batcher._cond")
        self.table = None

    def release(self):
        with self._cond:
            return self.table.weight("acme")

class Table:
    def __init__(self):
        self._lock = make_lock("Table._lock")

    def weight(self, tenant):
        with self._lock:
            return 1.0
"""


def test_r4_fires_on_tenancy_lock_cycle():
    fs = run_rule(R4_TENANCY_BAD, LockOrder(),
                  path="mx_rcnn_tpu/serve/tenancy.py")
    assert any("cycle" in f.message for f in fs)


def test_r4_silent_on_tenancy_leaf_order():
    assert run_rule(R4_TENANCY_GOOD, LockOrder(),
                    path="mx_rcnn_tpu/serve/tenancy.py") == []


# ---------------------------------------------------------------- R5

R5_BAD = """
class Worker:
    def loop(self):
        while True:
            d = self._inbox.get()
            if self._stop:
                return
            d.resolve(1)
"""

R5_GOOD = """
class Worker:
    def loop(self):
        while True:
            d = self._inbox.get(timeout=0.02)
            if d is None:
                break
            self._serve(d)

    def drain(self):
        while True:
            try:
                d = self._inbox.get_nowait()
            except Exception:
                break
            if d is not None:
                d.resolve(None)
"""


def test_r5_fires_on_droppable_take():
    fs = run_rule(R5_BAD, ExactlyOnce(), path="mx_rcnn_tpu/serve/fx.py")
    assert len(fs) == 1 and "`d`" in fs[0].message


def test_r5_silent_on_sentinel_and_drain():
    assert run_rule(R5_GOOD, ExactlyOnce(), path="mx_rcnn_tpu/serve/fx.py") == []


# R5 against the ISSUE 13 overlapped window: the local ``pending`` deque
# is a take source too — popping the oldest entry and then leaving the
# scope without settling it drops a windowed dispatch

R5_OVERLAP_BAD = """
class Worker:
    def loop(self):
        pending = deque()
        while True:
            d = self._inbox.get(timeout=0.02)
            if d is None:
                break
            pending.append(self._begin(d))
            entry = pending.popleft()
            if self._stop:
                return
            self._finish(entry)
"""

R5_OVERLAP_GOOD = """
class Worker:
    def loop(self):
        pending = deque()
        while not self._stop:
            d = self._inbox.get(timeout=0.02)
            if d is None:
                break
            pending.append(self._begin(d))
            if pending:
                entry = pending.popleft()
                self._finish(entry)
"""


def test_r5_fires_on_droppable_window_entry():
    fs = run_rule(R5_OVERLAP_BAD, ExactlyOnce(),
                  path="mx_rcnn_tpu/serve/fx.py")
    assert len(fs) == 1 and "`entry`" in fs[0].message


def test_r5_silent_on_settled_window_entry():
    assert run_rule(R5_OVERLAP_GOOD, ExactlyOnce(),
                    path="mx_rcnn_tpu/serve/fx.py") == []


# R5 against the ISSUE 16 scale-down drain: the victim replica's queued
# dispatches are a take source; popping one and bailing on the stop
# flag without requeuing it on a sibling is a dropped request — exactly
# the loss the zero-loss shrink bench would catch after the fact, and
# graftlint flags at review time

R5_DRAIN_BAD = """
class Drainer:
    def drain_victim(self):
        while True:
            d = self._victim_queue.get(timeout=0.02)
            if self._stop:
                return
            if d is None:
                break
            self._sibling.dispatch(d)
"""

R5_DRAIN_GOOD = """
class Drainer:
    def drain_victim(self):
        while True:
            d = self._victim_queue.get(timeout=0.02)
            if d is None:
                break
            self._sibling.dispatch(d)
"""


def test_r5_fires_on_dropped_drain_dispatch():
    fs = run_rule(R5_DRAIN_BAD, ExactlyOnce(),
                  path="mx_rcnn_tpu/serve/autoscaler.py")
    assert len(fs) == 1 and "`d`" in fs[0].message


def test_r5_silent_on_requeued_drain_dispatch():
    assert run_rule(R5_DRAIN_GOOD, ExactlyOnce(),
                    path="mx_rcnn_tpu/serve/autoscaler.py") == []


# R4 against the ISSUE 20 streaming gate: the engine resolves a request
# under Engine._lock and calls StreamTable.settle (a leaf); a table
# that fires the settlement callback while still HOLDING
# StreamTable._lock calls back into the engine and closes the cycle.
# The drainer discipline (collect the ready run under the lock, fire
# after release) is the shipped one-way design.

R4_STREAMS_BAD = """
from mx_rcnn_tpu.analysis.lockcheck import make_lock

class Engine:
    def __init__(self):
        self._lock = make_lock("Engine._lock")
        self.streams = None

    def resolve(self, req):
        with self._lock:
            return self.streams.settle(req)

class StreamTable:
    def __init__(self):
        self._lock = make_lock("StreamTable._lock")
        self.engine = None

    def settle(self, req):
        with self._lock:
            return self.engine.resolve(req)
"""

R4_STREAMS_GOOD = """
from mx_rcnn_tpu.analysis.lockcheck import make_lock

class Engine:
    def __init__(self):
        self._lock = make_lock("Engine._lock")
        self.streams = None

    def resolve(self, req):
        with self._lock:
            return self.streams.settle(req)

class StreamTable:
    def __init__(self):
        self._lock = make_lock("StreamTable._lock")

    def settle(self, req):
        with self._lock:
            run = [req]
        for fire in run:
            fire()
        return True
"""


def test_r4_fires_on_stream_settle_cycle():
    fs = run_rule(R4_STREAMS_BAD, LockOrder(),
                  path="mx_rcnn_tpu/serve/streams.py")
    assert any("cycle" in f.message for f in fs)


def test_r4_silent_on_stream_drainer_discipline():
    assert run_rule(R4_STREAMS_GOOD, LockOrder(),
                    path="mx_rcnn_tpu/serve/streams.py") == []


# R5 against the ISSUE 20 in-order buffer: a parked settlement callback
# popped off the buffer and then dropped on a shutdown flag is a frame
# the client never hears about — the stream's successors are wedged
# behind the gap forever.  The shipped flush() drains every taken
# callback (sentinel break + resolve-all drain).

R5_STREAMS_BAD = """
class StreamTable:
    def flush(self):
        while True:
            fire = self._pending.get(timeout=0.02)
            if self._closed:
                return
            fire.resolve(None)
"""

R5_STREAMS_GOOD = """
class StreamTable:
    def loop(self):
        while True:
            fire = self._pending.get(timeout=0.02)
            if fire is None:
                break
            self._fire(fire)

    def flush(self):
        while True:
            try:
                fire = self._pending.get_nowait()
            except Exception:
                break
            if fire is not None:
                fire.resolve(None)
"""


def test_r5_fires_on_dropped_buffered_settlement():
    fs = run_rule(R5_STREAMS_BAD, ExactlyOnce(),
                  path="mx_rcnn_tpu/serve/streams.py")
    assert len(fs) == 1 and "`fire`" in fs[0].message


def test_r5_silent_on_stream_flush_drain():
    assert run_rule(R5_STREAMS_GOOD, ExactlyOnce(),
                    path="mx_rcnn_tpu/serve/streams.py") == []


# ---------------------------------------------------------------- R6

R6_FAULTS = """
def _active():
    return []

def hook_a():
    for f in _active():
        if f.kind == "ka":
            pass

def hook_b():
    for f in _active():
        if f.kind == "kb":
            pass
"""

R6_CALLER_OK = """
from mx_rcnn_tpu.utils import faults

def run():
    faults.hook_a()
    faults.hook_b()
"""

R6_CALLER_BAD = """
from mx_rcnn_tpu.utils import faults

def run():
    faults.hook_a()
    faults.missing_hook()
"""

FAULTS_PATH = "mx_rcnn_tpu/utils/faults.py"


def test_r6_fires_on_uncovered_and_nonexistent_hooks():
    report = eng.analyze_snippets(
        {FAULTS_PATH: R6_FAULTS, "mx_rcnn_tpu/core/use.py": R6_CALLER_BAD},
        [FaultCoverage()],
    )
    msgs = " | ".join(f.message for f in report.findings)
    assert "missing_hook" in msgs and "hook_b" in msgs


def test_r6_silent_when_hooks_covered():
    report = eng.analyze_snippets(
        {FAULTS_PATH: R6_FAULTS, "mx_rcnn_tpu/core/use.py": R6_CALLER_OK},
        [FaultCoverage()],
    )
    assert report.findings == []


def test_r6_fires_on_known_kinds_drift():
    drift = R6_FAULTS + '\n_KNOWN_KINDS = frozenset({"ka"})\n'
    report = eng.analyze_snippets(
        {FAULTS_PATH: drift, "mx_rcnn_tpu/core/use.py": R6_CALLER_OK},
        [FaultCoverage()],
    )
    assert any("_KNOWN_KINDS drift" in f.message for f in report.findings)
    assert any("'kb'" in f.message for f in report.findings)


# ---------------------------------------------------------------- R7

R7_BAD = """
import signal
import threading
import jax
from mx_rcnn_tpu.utils import faults

class Guard:
    def __init__(self):
        self._lock = threading.Lock()
        signal.signal(signal.SIGTERM, self._handle)

    def _handle(self, signum, frame):
        with self._lock:
            self.flag = True
        faults.crash_save()
        self._snapshot()

    def _snapshot(self):
        self.snap = jax.device_get(self.state)
"""

R7_BAD_MODULE_FN = """
import signal

def _save():
    from mx_rcnn_tpu.core.resilience import host_copy
    return host_copy({})

def handler(signum, frame):
    _save()

signal.signal(signal.SIGINT, handler)
"""

R7_BAD_ACQUIRE = """
import signal

class G:
    def _handle(self, signum, frame):
        self.mu.acquire()

    def install(self):
        signal.signal(signal.SIGTERM, self._handle)
"""

R7_GOOD = """
import os
import signal

class Guard:
    def __init__(self, signals=(signal.SIGTERM,)):
        self.should_stop = False
        self._prev = {}
        for s in signals:
            self._prev[s] = signal.signal(s, self._handle)

    def _handle(self, signum, frame):
        if self.should_stop:
            signal.signal(signum, self._prev[signum])
            os.kill(os.getpid(), signum)
        self.should_stop = True
"""


def test_r7_fires_on_lock_device_and_faults_in_handler():
    fs = run_rule(R7_BAD, SignalSafety())
    msgs = " | ".join(f.message for f in fs)
    assert "acquires lock `_lock`" in msgs
    assert "fault-injection hook `faults.crash_save`" in msgs
    # transitive: the device_get lives in a self.* callee of the handler
    assert "device/placement work `jax.device_get`" in msgs
    assert all("signal handler `Guard._handle`" in f.message for f in fs)


def test_r7_follows_module_function_handler():
    fs = run_rule(R7_BAD_MODULE_FN, SignalSafety())
    assert len(fs) == 1 and "host_copy" in fs[0].message


def test_r7_fires_on_explicit_acquire():
    fs = run_rule(R7_BAD_ACQUIRE, SignalSafety())
    assert len(fs) == 1 and ".acquire()" in fs[0].message


def test_r7_silent_on_flag_flip_handler():
    """The PreemptionGuard shape — flag, handler restore, os.kill
    re-raise — is the sanctioned handler body and must be clean."""
    assert run_rule(R7_GOOD, SignalSafety()) == []


# ---------------------------------------------------------------- R8

R8_BAD_LOOP = """
class Router:
    def run(self, batch):
        while True:
            try:
                d = self.replica.submit(batch)
                return d.future.result()
            except Exception:
                continue
"""

R8_BAD_RETRY_FN = """
class Engine:
    def _resubmit(self, req):
        self.batcher.submit(req)
"""

R8_GOOD_DIRECT_SPEND = """
class Router:
    def run(self, batch, budget):
        while True:
            try:
                d = self.replica.submit(batch)
                return d.future.result()
            except Exception:
                budget.spend("requeue")
"""

R8_GOOD_INDIRECT_SPEND = """
class Engine:
    def _charge(self, req):
        req.budget.spend("resubmit")

    def _resubmit(self, req):
        self._charge(req)
        self.batcher.submit(req)
"""

R8_GOOD_INTAKE = """
def client(engine, im):
    while True:
        try:
            return engine.submit(im)
        except Exception:
            continue
"""

SERVE_PATH = "mx_rcnn_tpu/serve/fx.py"


def test_r8_fires_on_looped_requeue_without_budget():
    fs = run_rule(R8_BAD_LOOP, BoundedRequeue(), path=SERVE_PATH)
    assert len(fs) == 1 and fs[0].rule == "R8"
    assert "inside a loop" in fs[0].message


def test_r8_fires_in_retry_named_function():
    fs = run_rule(R8_BAD_RETRY_FN, BoundedRequeue(), path=SERVE_PATH)
    assert len(fs) == 1 and "retry path" in fs[0].message


def test_r8_silent_when_budget_spent_directly():
    assert run_rule(R8_GOOD_DIRECT_SPEND, BoundedRequeue(),
                    path=SERVE_PATH) == []


def test_r8_silent_when_spend_reached_through_helper():
    assert run_rule(R8_GOOD_INDIRECT_SPEND, BoundedRequeue(),
                    path=SERVE_PATH) == []


def test_r8_silent_on_intake_submit_and_out_of_scope():
    # engine.submit is intake, not re-dispatch — not a requeue receiver
    assert run_rule(R8_GOOD_INTAKE, BoundedRequeue(), path=SERVE_PATH) == []
    # same unbounded loop outside /serve/ is out of scope
    assert run_rule(R8_BAD_LOOP, BoundedRequeue()) == []


# ------------------------------------------------- suppression layers


def test_inline_pragma_suppresses_with_reason():
    src = R1_BAD_RETURN.replace(
        "return jax.device_get(fn(batch))",
        "return jax.device_get(fn(batch))  "
        "# graftlint: disable=R1(outputs never donated)",
    )
    report = eng.analyze_snippets(
        {"mx_rcnn_tpu/core/mod.py": src}, [HostCopyEscape()]
    )
    assert report.findings == []
    assert len(report.inline_suppressed) == 1
    assert report.inline_suppressed[0][1] == "outputs never donated"


def test_inline_pragma_without_reason_is_ignored():
    src = R1_BAD_RETURN.replace(
        "return jax.device_get(fn(batch))",
        "return jax.device_get(fn(batch))  # graftlint: disable=R1",
    )
    report = eng.analyze_snippets(
        {"mx_rcnn_tpu/core/mod.py": src}, [HostCopyEscape()]
    )
    assert len(report.findings) == 1


def test_baseline_suppresses_and_flags_stale():
    good = eng.BaselineEntry(
        rule="R1", path="mx_rcnn_tpu/core/mod.py", scope="f", reason="known"
    )
    stale = eng.BaselineEntry(
        rule="R1", path="mx_rcnn_tpu/core/gone.py", scope="g", reason="old"
    )
    report = eng.analyze_snippets(
        {"mx_rcnn_tpu/core/mod.py": R1_BAD_RETURN},
        [HostCopyEscape()],
        baseline=[good, stale],
    )
    assert report.findings == []
    assert len(report.baseline_suppressed) == 1
    assert report.stale_baseline == [stale]
    assert not report.ok  # stale entries fail the run


# ------------------------------------------------- whole-tree guards


@pytest.fixture(scope="module")
def tree():
    modules, errors = eng.load_modules(REPO)
    baseline = eng.load_baseline(REPO / "tools" / "lint_baseline.json")
    return modules, baseline, errors


def test_tree_is_clean(tree):
    modules, baseline, errors = tree
    report = eng.analyze(modules, eng.default_rules(), baseline, errors)
    detail = "\n".join(f.format() for f in report.findings)
    assert report.ok, f"{report.summary()}\n{detail}"


def test_fresh_r1_violation_fails_the_tree(tree):
    modules, baseline, errors = tree
    injected = eng.Module("mx_rcnn_tpu/core/_fresh_violation.py", R1_BAD_RETURN)
    report = eng.analyze(
        list(modules) + [injected], eng.default_rules(), baseline, errors
    )
    assert not report.ok
    assert any(
        f.rule == "R1" and f.path.endswith("_fresh_violation.py")
        for f in report.findings
    )


def test_fabricated_stale_entry_fails_the_tree(tree):
    modules, baseline, errors = tree
    fake = eng.BaselineEntry(
        rule="R1", path="mx_rcnn_tpu/core/nope.py", scope="*", reason="stale"
    )
    report = eng.analyze(
        modules, eng.default_rules(), list(baseline) + [fake], errors
    )
    assert not report.ok and fake in report.stale_baseline


# ------------------------------------------------- runtime lock check


@pytest.fixture(autouse=True)
def _fresh_lock_graph():
    lockcheck.reset()
    yield
    lockcheck.reset()


def test_lockcheck_raises_on_inversion():
    a = lockcheck.OrderedLock("A")
    b = lockcheck.OrderedLock("B")
    with a:
        with b:
            pass
    with b:
        with pytest.raises(lockcheck.LockOrderViolation):
            a.acquire()


def test_lockcheck_allows_consistent_order():
    a = lockcheck.OrderedLock("A")
    b = lockcheck.OrderedLock("B")
    for _ in range(3):
        with a:
            with b:
                pass


def test_lockcheck_same_name_instances_nest():
    # LatencyHistogram.merge holds two instances of the same lock class
    h1 = lockcheck.OrderedLock("H")
    h2 = lockcheck.OrderedLock("H")
    with h1:
        with h2:
            pass


def test_lockcheck_rlock_reentry_ok_plain_reentry_raises():
    r = lockcheck.OrderedLock("R", rlock=True)
    with r:
        with r:
            pass
    p = lockcheck.OrderedLock("P")
    with p:
        with pytest.raises(lockcheck.LockOrderViolation):
            p.acquire()


def test_lockcheck_disabled_returns_plain_primitives(monkeypatch):
    monkeypatch.delenv("MX_RCNN_LOCK_CHECK", raising=False)
    assert not isinstance(lockcheck.make_lock("X"), lockcheck.OrderedLock)
    monkeypatch.setenv("MX_RCNN_LOCK_CHECK", "1")
    assert isinstance(lockcheck.make_lock("X"), lockcheck.OrderedLock)


def test_lockcheck_condition_proxy_wait_notify(monkeypatch):
    monkeypatch.setenv("MX_RCNN_LOCK_CHECK", "1")
    cond = lockcheck.make_condition("C")
    hits = []

    def waiter():
        with cond:
            hits.append(cond.wait(timeout=2.0))

    t = threading.Thread(target=waiter)
    t.start()
    deadline = time.time() + 2.0
    while time.time() < deadline:
        with cond:
            cond.notify_all()
        if hits:
            break
        time.sleep(0.01)
    t.join(timeout=2.0)
    assert hits == [True]


# ------------------------------------------------- faults spec errors


def test_unknown_fault_kind_is_hard_error(monkeypatch):
    from mx_rcnn_tpu.utils import faults

    monkeypatch.setenv("MX_RCNN_FAULTS", "predict_fial@0.1")
    faults.reset()
    with pytest.raises(ValueError, match="predict_fial"):
        faults.predict_fault(0, 1)
    monkeypatch.setenv("MX_RCNN_FAULTS", "")
    faults.reset()


def test_valid_fault_specs_still_parse(monkeypatch):
    from mx_rcnn_tpu.utils import faults

    monkeypatch.setenv(
        "MX_RCNN_FAULTS", "nan_loss@3,predict_fail@0.1x2,swap_verify_fail@*"
    )
    faults.reset()
    # wrong keys: parses fine, fires nothing
    faults.corrupt_loss(0.5, None)
    monkeypatch.setenv("MX_RCNN_FAULTS", "")
    faults.reset()


# R4 against the ISSUE 17 rollout shape: the controller lock guards
# only the split/shadow tables — device work (shadow scoring, warm
# placement) and registry calls happen OUTSIDE it.  A controller that
# scores under its own lock, or a registry→runner→registry call chain
# that closes the lock cycle the promote path walks, is exactly what
# R4 must flag.

R4_ROLLOUT_BAD = """
from mx_rcnn_tpu.analysis.lockcheck import make_lock
import jax

class RolloutController:
    def __init__(self):
        self._lock = make_lock("RolloutController._lock")
        self.registry = None

    def score_shadow(self, tree):
        with self._lock:
            return jax.device_put(tree)

class ModelRegistry:
    def __init__(self):
        self._lock = make_lock("ModelRegistry._lock")
        self.runner = None

    def commit(self):
        with self._lock:
            return self.runner.sync()

class ServeRunner:
    def __init__(self):
        self._lock = make_lock("ServeRunner._lock")
        self.registry = None

    def sync(self):
        with self._lock:
            return self.registry.commit()
"""

R4_ROLLOUT_GOOD = """
from mx_rcnn_tpu.analysis.lockcheck import make_lock
import jax

class RolloutController:
    def __init__(self):
        self._lock = make_lock("RolloutController._lock")
        self.registry = None
        self._split = {}

    def close_tables(self):
        with self._lock:
            self._split.clear()

    def promote(self):
        self.close_tables()
        self.registry.commit()

    def score_shadow(self, tree):
        placed = jax.device_put(tree)
        with self._lock:
            self.scored = 1
        return placed

class ModelRegistry:
    def __init__(self):
        self._lock = make_lock("ModelRegistry._lock")

    def commit(self):
        with self._lock:
            return True
"""


def test_r4_fires_on_rollout_device_work_under_controller_lock():
    fs = run_rule(R4_ROLLOUT_BAD, LockOrder(),
                  path="mx_rcnn_tpu/serve/rollout.py")
    assert any(
        f.scope == "RolloutController.score_shadow" and "device" in f.message
        for f in fs
    )
    assert any("cycle" in f.message for f in fs)


def test_r4_silent_on_rollout_tables_then_registry_order():
    assert run_rule(R4_ROLLOUT_GOOD, LockOrder(),
                    path="mx_rcnn_tpu/serve/rollout.py") == []


# R5 against the ISSUE 17 shadow lane: the mirror queue is a take
# source; popping an item under the condition and then bailing on the
# stop flag without scoring it silently drops a comparison the
# promote/rollback verdict was waiting on.  The shipped worker checks
# stop-and-empty BEFORE the pop, so every popped item reaches the
# scorer on every path.

R5_SHADOW_BAD = """
class ShadowWorker:
    def loop(self):
        while True:
            with self._cond:
                item = self._shadow_queue.popleft()
            if self._stop:
                return
            self._score(item)
"""

R5_SHADOW_GOOD = """
class ShadowWorker:
    def loop(self):
        while True:
            with self._cond:
                while not self._shadow_queue and not self._stop:
                    self._cond.wait(0.05)
                if not self._shadow_queue and self._stop:
                    return
                item = self._shadow_queue.popleft()
            self._score(item)
"""


def test_r5_fires_on_droppable_shadow_item():
    fs = run_rule(R5_SHADOW_BAD, ExactlyOnce(),
                  path="mx_rcnn_tpu/serve/rollout.py")
    assert len(fs) == 1 and "`item`" in fs[0].message


def test_r5_silent_on_pop_after_stop_check():
    assert run_rule(R5_SHADOW_GOOD, ExactlyOnce(),
                    path="mx_rcnn_tpu/serve/rollout.py") == []


# R4 against the ISSUE 18 cascade shape: the router lock is a LEAF
# guarding only the gate counters — the confidence gate itself runs on
# host arrays and escalation re-entry goes back through the engine
# OUTSIDE the lock.  A router that touches the device under its own
# lock, or an engine->router->engine call chain that closes a lock
# cycle on the escalation path, is exactly what R4 must flag.

R4_CASCADE_BAD = """
from mx_rcnn_tpu.analysis.lockcheck import make_lock
import jax

class CascadeRouter:
    def __init__(self):
        self._lock = make_lock("CascadeRouter._lock")
        self.engine = None

    def gate(self, dets):
        with self._lock:
            return jax.device_get(dets)

    def record(self, req):
        with self._lock:
            return self.engine.escalate(req)

class ServeEngine:
    def __init__(self):
        self._lock = make_lock("ServeEngine._lock")
        self.router = None

    def escalate(self, req):
        with self._lock:
            return self.router.record(req)
"""

R4_CASCADE_GOOD = """
from mx_rcnn_tpu.analysis.lockcheck import make_lock
import jax

class CascadeRouter:
    def __init__(self):
        self._lock = make_lock("CascadeRouter._lock")
        self.engine = None
        self.escalations = 0

    def gate(self, dets):
        host = jax.device_get(dets)
        with self._lock:
            self.escalations += 1
        return host

    def route(self, req):
        verdict = self.gate(req.dets)
        self.engine.escalate(req)
        return verdict

class ServeEngine:
    def __init__(self):
        self._lock = make_lock("ServeEngine._lock")

    def escalate(self, req):
        with self._lock:
            return True
"""


def test_r4_fires_on_cascade_device_gate_under_router_lock():
    fs = run_rule(R4_CASCADE_BAD, LockOrder(),
                  path="mx_rcnn_tpu/serve/cascade.py")
    assert any(
        f.scope == "CascadeRouter.gate" and "device" in f.message
        for f in fs
    )
    assert any("cycle" in f.message for f in fs)


def test_r4_silent_on_cascade_leaf_lock_counters():
    assert run_rule(R4_CASCADE_GOOD, LockOrder(),
                    path="mx_rcnn_tpu/serve/cascade.py") == []


# R5 against the ISSUE 18 escalation lane: an escalated request popped
# off the re-entry queue and then dropped on the drain flag loses the
# caller's future forever — first-pass results were already discarded
# by the gate, so nobody else will ever settle it.  The shipped path
# checks drain-and-empty BEFORE the pop.

R5_CASCADE_BAD = """
class EscalationWorker:
    def loop(self):
        while True:
            with self._cond:
                req = self._escalation_queue.popleft()
            if self._draining:
                return
            self._resubmit(req)
"""

R5_CASCADE_GOOD = """
class EscalationWorker:
    def loop(self):
        while True:
            with self._cond:
                while not self._escalation_queue and not self._draining:
                    self._cond.wait(0.05)
                if not self._escalation_queue and self._draining:
                    return
                req = self._escalation_queue.popleft()
            self._resubmit(req)
"""


def test_r5_fires_on_droppable_escalated_request():
    fs = run_rule(R5_CASCADE_BAD, ExactlyOnce(),
                  path="mx_rcnn_tpu/serve/cascade.py")
    assert len(fs) == 1 and "`req`" in fs[0].message


def test_r5_silent_on_escalation_pop_after_drain_check():
    assert run_rule(R5_CASCADE_GOOD, ExactlyOnce(),
                    path="mx_rcnn_tpu/serve/cascade.py") == []


# R4 against the ISSUE 19 fleet gateway: the gateway routes by calling
# into per-backend links, each with its own lock.  Calling a link
# method while holding the gateway lock (or an upcall re-entering the
# gateway under the link lock) closes a gateway->link->gateway cycle —
# the reader thread's response upcall then deadlocks against a
# concurrent submit.  The shipped code computes routing state under
# the gateway lock but always DISPATCHES and upcalls with no lock held.

R4_FLEET_BAD = """
from mx_rcnn_tpu.analysis.lockcheck import make_lock

class BackendLink:
    def __init__(self):
        self._lock = make_lock("BackendLink._lock")
        self.gw = None

    def on_response(self, resp):
        with self._lock:
            return self.gw.finish(resp)

class Gateway:
    def __init__(self):
        self._lock = make_lock("Gateway._lock")
        self.links = [BackendLink()]

    def finish(self, resp):
        with self._lock:
            return resp

    def route(self, req):
        with self._lock:
            return self.links[0].on_response(req)
"""

R4_FLEET_GOOD = """
from mx_rcnn_tpu.analysis.lockcheck import make_lock

class BackendLink:
    def __init__(self):
        self._lock = make_lock("BackendLink._lock")
        self.gw = None
        self.completed = 0

    def on_response(self, resp):
        with self._lock:
            self.completed += 1
        self.gw.finish(resp)

class Gateway:
    def __init__(self):
        self._lock = make_lock("Gateway._lock")
        self.links = [BackendLink()]
        self.routed = 0

    def finish(self, resp):
        with self._lock:
            self.routed += 1

    def route(self, req):
        with self._lock:
            target = self.links[0]
        target.on_response(req)
"""


def test_r4_fires_on_gateway_link_lock_cycle():
    fs = run_rule(R4_FLEET_BAD, LockOrder(),
                  path="mx_rcnn_tpu/serve/fleet.py")
    assert any("cycle" in f.message for f in fs)


def test_r4_silent_on_lockless_gateway_dispatch():
    assert run_rule(R4_FLEET_GOOD, LockOrder(),
                    path="mx_rcnn_tpu/serve/fleet.py") == []


# R5 against the fleet connection pool: a response popped off the
# in-flight correlation map and then dropped on the stopping flag
# strands the caller's future forever — the backend already answered,
# so no requeue path will ever touch that request again.  The shipped
# reader hands EVERY popped entry to the link upcall.

R5_FLEET_BAD = """
class ConnReader:
    def loop(self):
        while True:
            resp = self.read_frame()
            with self._lock:
                entry = self.pending.get(resp["id"])
            if self._stopping:
                return
            self.owner.on_response(entry, resp)
"""

R5_FLEET_GOOD = """
class ConnReader:
    def loop(self):
        while True:
            resp = self.read_frame()
            with self._lock:
                entry = self.pending.get(resp["id"])
            if entry is not None:
                self.owner.on_response(entry, resp)
"""


def test_r5_fires_on_droppable_correlated_response():
    fs = run_rule(R5_FLEET_BAD, ExactlyOnce(),
                  path="mx_rcnn_tpu/serve/fleet.py")
    assert len(fs) == 1 and "`entry`" in fs[0].message


def test_r5_silent_on_response_always_handed_off():
    assert run_rule(R5_FLEET_GOOD, ExactlyOnce(),
                    path="mx_rcnn_tpu/serve/fleet.py") == []
