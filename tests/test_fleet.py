"""Multi-host fleet gateway (ISSUE 19): pipelined wire fan-out,
host-level failover, and the chaos guarantees.

Test split, cheapest first:

* pure pieces — the wire-code → typed-exception map, the EWMA slow
  gate, affinity-stable picking (no sockets);
* in-process backends — real ``Frontend`` + ``ServingEngine`` on
  ephemeral ports inside this process (deterministic gating of the
  backend runner), covering N=1 byte-identity vs the direct engine,
  requeue exactly-once when a connection is severed mid-flight,
  hedge-win accounting, typed-error propagation through the gateway,
  admission parity (``QueueFull``), and the fleet-merged snapshot;
* one real process kill — ``spawn_stub_backends`` + SIGKILL mid-load,
  the requeue-never-drop guarantee with an actual dead PID.

Every test runs with the lock-order checker armed.
"""

import time

import numpy as np
import pytest

from mx_rcnn_tpu.serve.engine import ServingEngine
from mx_rcnn_tpu.serve.frontend import Frontend
from mx_rcnn_tpu.serve.fleet import (
    FleetGateway,
    NoHealthyBackend,
    _FleetStubRunner,
    error_for_code,
    spawn_stub_backends,
)


@pytest.fixture(autouse=True)
def _lock_order_check(monkeypatch):
    from mx_rcnn_tpu.analysis import lockcheck

    monkeypatch.setenv("MX_RCNN_LOCK_CHECK", "1")
    lockcheck.reset()
    yield


def image(i: int, h: int = 24, w: int = 24) -> np.ndarray:
    rng = np.random.default_rng(i)
    return rng.integers(0, 255, size=(h, w, 3)).astype(np.float32)


def dets_equal(a, b) -> bool:
    return (
        len(a) == len(b)
        and all(
            x.dtype == y.dtype and x.shape == y.shape
            and x.tobytes() == y.tobytes()
            for x, y in zip(a, b)
        )
    )


class GatedStub(_FleetStubRunner):
    """Stub runner whose device stalls until the test releases the
    gate — deterministic in-flight requests."""

    def __init__(self, gate, **kw):
        super().__init__(**kw)
        self.gate = gate

    def run(self, batch):
        self.gate.wait(timeout=30.0)
        return super().run(batch)


class Backend:
    """One in-process backend: engine + frontend on an ephemeral
    port."""

    def __init__(self, runner=None, service_ms: float = 1.0, **fe_kw):
        self.runner = runner or _FleetStubRunner(service_ms=service_ms)
        self.engine = ServingEngine(
            self.runner, max_linger=0.002, max_queue=512
        )
        self.engine.start()
        self.fe = Frontend(self.engine, port=0, **fe_kw)
        self.fe.start()

    @property
    def addr(self):
        return ("127.0.0.1", self.fe.port)

    def stop(self):
        self.fe.stop()
        self.engine.stop()


# ------------------------------------------------------------- pure
class TestErrorTaxonomy:
    def test_wire_codes_rebuild_the_engine_exceptions(self):
        from mx_rcnn_tpu.serve.batcher import QueueFull
        from mx_rcnn_tpu.serve.engine import DeadlineExceeded
        from mx_rcnn_tpu.serve.quarantine import PoisonRequest
        from mx_rcnn_tpu.serve.tenancy import TenantOverBudget, UnknownTenant

        for code, cls in [
            ("unknown_tenant", UnknownTenant),
            ("over_budget", TenantOverBudget),
            ("poison", PoisonRequest),
            ("queue_full", QueueFull),
            ("deadline", DeadlineExceeded),
        ]:
            err = error_for_code(code, "msg")
            assert isinstance(err, cls), code
            assert "msg" in str(err)

    def test_unknown_code_stays_generic(self):
        from mx_rcnn_tpu.serve.fleet import GatewayError

        err = error_for_code("haywire", "???")
        assert type(err) is GatewayError


class TestRoutingPure:
    def _gw(self, n=3):
        # never started: _pick/_affinity are pure given link state
        return FleetGateway([("127.0.0.1", 1 + i) for i in range(n)])

    def test_affinity_is_stable_and_spreads(self):
        gw = self._gw(3)
        a1 = gw._affinity("t", "bulk", "det", (24, 24, 3))
        a2 = gw._affinity("t", "bulk", "det", (24, 24, 3))
        assert a1 == a2
        keys = {
            gw._affinity(t, l, m, s)
            for t in ("a", "b", "c")
            for l in (None, "bulk")
            for m in (None, "det")
            for s in ((24, 24, 3), (32, 48, 3))
        }
        assert len(keys) > 1  # traffic keys do not all pile on one host

    def test_pick_prefers_least_loaded_then_affinity(self):
        gw = self._gw(2)
        req = gw._links  # build a fake request via submit-shape fields
        from mx_rcnn_tpu.serve.fleet import _FleetRequest

        r = _FleetRequest(b"", "float32", (24, 24, 3), "t", None, None,
                          None)
        aff = gw._affinity("t", None, None, (24, 24, 3))
        assert gw._pick(r).index == aff
        gw._links[aff].inflight = 5
        assert gw._pick(r).index != aff

    def test_ewma_slow_gate_routes_around_outlier(self):
        gw = self._gw(2)
        from mx_rcnn_tpu.serve.fleet import _FleetRequest

        r = _FleetRequest(b"", "float32", (24, 24, 3), "t", None, None,
                          None)
        aff = gw._affinity("t", None, None, (24, 24, 3))
        slow, fast = gw._links[aff], gw._links[1 - aff]
        for link, ms in ((slow, 500.0), (fast, 10.0)):
            link._ewma_ms = ms
            link._ewma_n = gw.ewma_warmup
        # 500ms > slow_factor(8) × 10ms floor → affinity loses to health
        assert gw._pick(r) is fast

    def test_pick_skips_down_and_excluded(self):
        gw = self._gw(2)
        from mx_rcnn_tpu.serve.fleet import _FleetRequest

        r = _FleetRequest(b"", "float32", (24, 24, 3), "t", None, None,
                          None)
        gw._links[0].state = "down"
        assert gw._pick(r) is gw._links[1]
        assert gw._pick(r, exclude=(gw._links[1],)) is None


# -------------------------------------------------- in-process backends
class TestGatewayServing:
    def test_n1_byte_identical_to_direct_engine(self):
        imgs = [image(i, 16 + i % 16, 16 + (i * 7) % 16)
                for i in range(24)]
        direct_engine = ServingEngine(
            _FleetStubRunner(service_ms=1.0), max_linger=0.002,
            max_queue=512,
        )
        with direct_engine:
            direct = [direct_engine.submit(im).result(timeout=10.0)
                      for im in imgs]
        b = Backend()
        gw = FleetGateway([b.addr]).start()
        try:
            futs = [gw.submit(im) for im in imgs]
            via_wire = [f.result(timeout=30.0) for f in futs]
        finally:
            gw.stop()
            b.stop()
        assert all(dets_equal(d, w) for d, w in zip(direct, via_wire))

    def test_typed_errors_propagate_verbatim(self):
        from mx_rcnn_tpu.serve.tenancy import TenantTable, UnknownTenant

        table = TenantTable(strict=True)
        table.register("acme")
        runner = _FleetStubRunner(service_ms=1.0)
        engine = ServingEngine(runner, max_linger=0.002, tenants=table)
        engine.start()
        fe = Frontend(engine, port=0)
        fe.start()
        gw = FleetGateway([("127.0.0.1", fe.port)]).start()
        try:
            ok = gw.submit(image(1), tenant="acme").result(timeout=10.0)
            assert len(ok) == 1
            with pytest.raises(UnknownTenant):
                gw.submit(image(2), tenant="nobody").result(timeout=10.0)
        finally:
            gw.stop()
            fe.stop()
            engine.stop()

    def test_admission_cap_raises_queue_full(self):
        import threading

        from mx_rcnn_tpu.serve.batcher import QueueFull

        gate = threading.Event()
        b = Backend(runner=GatedStub(gate))
        gw = FleetGateway([b.addr], max_inflight=1).start()
        try:
            first = gw.submit(image(3))
            with pytest.raises(QueueFull):
                gw.submit(image(4))
            assert gw.shed == 1
            gate.set()
            first.result(timeout=10.0)
        finally:
            gate.set()
            gw.stop()
            b.stop()

    def test_requeue_exactly_once_on_severed_connection(self):
        import threading

        gate = threading.Event()
        victim = Backend(runner=GatedStub(gate))
        survivor = Backend()
        # the test owns the survivor's state: no revive probe (the
        # monitor would ping the healthy survivor back "up" within a
        # revive interval and route some of the six there) and no hedge
        # (a gated request older than the hedge clock would reach the
        # survivor a second time)
        gw = FleetGateway(
            [victim.addr, survivor.addr], fail_threshold=1,
            revive_interval=float("inf"), hedge_timeout=float("inf"),
        )
        gw._links[1].state = "down"
        gw.start()
        try:
            # force every dispatch onto the gated victim, then sever its
            # connections with responses still in flight
            victim_link = gw._links[0]
            futs = [gw.submit(image(10 + i)) for i in range(6)]
            t_end = time.monotonic() + 30.0
            while victim_link.load() < 6 and time.monotonic() < t_end:
                time.sleep(0.005)
            assert victim_link.load() == 6
            assert gw._links[1].dispatched == 0
            gw._links[1].state = "up"
            with victim_link._lock:
                conns = list(victim_link._conns)
            for c in conns:
                c.kill()
            results = [f.result(timeout=30.0) for f in futs]
            assert all(len(r) == 1 for r in results)
            snap = gw.snapshot()["gateway"]
            # every orphan requeued exactly once, none lost, none dropped
            assert snap["requeued"] == 6
            assert snap["completed"] == 6
            assert snap["failed"] == 0
            assert snap["abandoned"] == 0
            assert gw._links[1].completed == 6
        finally:
            gate.set()
            gw.stop()
            victim.stop()
            survivor.stop()

    def test_hedge_win_accounting(self):
        import threading

        gate = threading.Event()
        shape = (24, 24, 3)
        backends = [Backend(runner=GatedStub(gate)), Backend()]
        gw = FleetGateway(
            [b.addr for b in backends], hedge_timeout=0.05,
            min_hedge_timeout=0.01,
        ).start()
        aff = gw._affinity("fleet", None, None, shape)
        if aff != 0:
            # make the gated backend the affinity target
            gw._links[0], gw._links[1] = gw._links[1], gw._links[0]
            gw._links[0].index, gw._links[1].index = 0, 1
            backends.reverse()
        try:
            fut = gw.submit(image(5))
            dets = fut.result(timeout=30.0)
            assert len(dets) == 1
            snap = gw.snapshot()["gateway"]
            assert snap["hedged"] == 1
            assert snap["hedge_wins"] == 1  # the un-gated host answered
            assert snap["completed"] == 1
        finally:
            gate.set()
            gw.stop()
            for b in backends:
                b.stop()

    def test_all_backends_down_is_typed_not_hung(self):
        b = Backend()
        gw = FleetGateway(
            [b.addr], fail_threshold=1, no_healthy_timeout=0.2,
            revive_interval=30.0,
        ).start()
        b.stop()  # dead before any traffic
        try:
            with pytest.raises((NoHealthyBackend, ConnectionError)):
                gw.submit(image(6)).result(timeout=30.0)
        finally:
            gw.stop()

    def test_fleet_snapshot_merges_backend_counters(self):
        backends = [Backend(), Backend()]
        gw = FleetGateway([b.addr for b in backends]).start()
        try:
            futs = [gw.submit(image(20 + i)) for i in range(8)]
            for f in futs:
                f.result(timeout=30.0)
            fs = gw.fleet_snapshot()
            assert fs["reachable"] == 2
            assert fs["engines"]["n_sources"] == 2
            # merged counters sum across hosts: every request landed
            assert fs["engines"]["requests"]["submitted"] == 8
            assert fs["frontends"]["frames"] >= 8
            assert fs["gateway"]["gateway"]["completed"] == 8
        finally:
            gw.stop()
            for b in backends:
                b.stop()


# ------------------------------------------------------- real processes
class TestChaosProcessKill:
    def test_sigkill_mid_load_loses_nothing(self):
        procs = spawn_stub_backends(2, service_ms=30.0)
        gw = FleetGateway(
            [p.addr for p in procs], fail_threshold=2
        ).start()
        try:
            imgs = [image(100 + i) for i in range(60)]
            futs = [gw.submit(im, deadline_s=120.0) for im in imgs]
            time.sleep(0.08)
            procs[0].kill()  # SIGKILL: no goodbye on the wire
            results = [f.result(timeout=120.0) for f in futs]
            assert all(len(r) == 1 for r in results)
            # every answer, requeued or not, is the unfaulted run's bytes
            # (the digest is a pure function of the pixels)
            ref = _FleetStubRunner(service_ms=0.0)
            for im, got in zip(imgs, results):
                batch = ref.assemble([ref.make_request(im)])
                want = ref.detections_for(ref.run(batch), batch, 0)
                assert dets_equal(got, want)
            snap = gw.snapshot()["gateway"]
            assert snap["completed"] == 60
            assert snap["failed"] == 0
            # the survivor carried everything that was cut off
            assert gw._links[1].completed >= 30
        finally:
            gw.stop()
            procs[0].stop()
            procs[1].stop()
