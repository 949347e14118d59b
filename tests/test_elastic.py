"""Elastic training (parallel/elastic.py, ISSUE 9): deterministic mesh
shrink on injected device loss, window replay from the host anchor,
emergency committed checkpoints, and breaker-gated regrow.

The loop logic runs here against cheap NUMPY factories through the same
ElasticContext interface the real shard_map substrate implements — every
membership/replay/breaker assertion is jax-free and fast.  One @slow
test at the bottom drives the REAL ``make_elastic_factory`` (two
shard_map compiles).
"""

import os

import numpy as np
import pytest

from mx_rcnn_tpu.core.checkpoint import (
    is_committed,
    load_restorable,
    save_checkpoint,
)
from mx_rcnn_tpu.parallel import distributed
from mx_rcnn_tpu.parallel.elastic import (
    ElasticContext,
    ElasticLoop,
    MeshMonitor,
    NoSurvivorsError,
    RegrowPolicy,
    classify_device_fault,
    make_elastic_factory,
)
from mx_rcnn_tpu.utils import faults


def set_faults(monkeypatch, spec):
    monkeypatch.setenv(faults.ENV_VAR, spec)
    faults.reset()


# ---------------------------------------------------------------------
# numpy stand-in for the shard_map substrate: place_batch truncates the
# base-sized batch to the survivor fraction (take_replica_rows
# semantics) and the step is pure arithmetic, so "what the survivors
# computed" is exactly reproducible by hand
# ---------------------------------------------------------------------


def fake_factory(n_base, built=None):
    def factory(active):
        active = tuple(active)
        if built is not None:
            built.append(active)
        n = len(active)

        def step_fn(state, batch, rng, lr_scale=1.0):
            w = state["w"] + float(np.sum(batch["x"]))
            return (
                {"w": w, "step": state["step"] + 1},
                {"loss": abs(w) + 1.0},
            )

        def place_batch(batch):
            rows = batch["x"].shape[0] * n // n_base
            return {"x": batch["x"][:rows]}

        return ElasticContext(
            active=active,
            step_fn=step_fn,
            place_state=lambda t: {k: np.array(v) for k, v in t.items()},
            place_batch=place_batch,
        )

    return factory


def fake_state():
    return {"w": np.float32(0.0), "step": np.int32(0)}


def batches(n, rows=8):
    return [
        {"x": np.arange(rows, dtype=np.float32) + 10.0 * i} for i in range(n)
    ]


def run_ctx(ctx, state, bs, start=0):
    """Reference: plain synchronous stepping on a fixed context."""
    for b in bs[start:]:
        state, _aux = ctx.step_fn(state, ctx.place_batch(b), None)
    return state


# ---------------------------------------------------------------- unit


def test_classify_device_fault():
    exc = faults.InjectedDeviceFault("x", replica=3, fault_kind="device_wedge")
    assert classify_device_fault(exc) == ("device_wedge", 3)

    class XlaRuntimeError(RuntimeError):
        pass

    assert classify_device_fault(
        XlaRuntimeError("collective timed out on slice health check")
    ) == ("device_lost", None)
    assert classify_device_fault(XlaRuntimeError("bad argument")) is None
    assert classify_device_fault(ValueError("device lost")) is None


def test_agree_on_down_single_process():
    assert distributed.agree_on_down({2, "5"}, 8) == frozenset({2, 5})
    assert distributed.agree_on_down(set(), 8) == frozenset()


def test_take_replica_rows_pure_function_of_count():
    from mx_rcnn_tpu.parallel.mesh import take_replica_rows

    b = {"x": np.arange(16).reshape(8, 2), "y": np.arange(8)}
    out = take_replica_rows(b, 7, 8)
    assert out["x"].shape[0] == 7 and out["y"].shape[0] == 7
    np.testing.assert_array_equal(out["x"], b["x"][:7])
    # identity at full strength; same COUNT -> same rows regardless of
    # WHICH ordinal died (the determinism bar depends on this)
    assert take_replica_rows(b, 8, 8)["x"].shape[0] == 8
    np.testing.assert_array_equal(
        take_replica_rows(b, 6, 8)["x"], take_replica_rows(b, 6, 8)["x"]
    )


# ------------------------------------------------------------- monitor


def test_monitor_shrink_and_regrow_bookkeeping():
    m = MeshMonitor(4, probe_fn=lambda step: ())
    assert m.active == (0, 1, 2, 3) and not m.degraded
    m.note_shrink(5, {1}, "device_lost")
    assert m.active == (0, 2, 3) and m.degraded and m.shrinks == 1
    m.note_boundary()
    target = m.want_regrow(6)
    assert target == (0, 1, 2, 3)
    m.note_regrow(6, target)
    assert m.active == (0, 1, 2, 3) and m.regrows == 1
    events = [t["event"] for t in m.transitions]
    assert events == ["shrink", "regrow"]


def test_monitor_no_survivors():
    m = MeshMonitor(2)
    with pytest.raises(NoSurvivorsError):
        m.note_shrink(0, {0, 1}, "device_lost")


def test_monitor_regrow_blocked_while_probe_reports_down():
    m = MeshMonitor(4, probe_fn=lambda step: (1,))
    m.note_shrink(5, {1}, "device_lost")
    m.note_boundary()
    assert m.want_regrow(6) is None


def test_monitor_breaker_backoff_doubles_on_flap_and_ages_out():
    pol = RegrowPolicy(cooldown=1, flap_window=3, max_backoff=4)
    m = MeshMonitor(2, policy=pol, probe_fn=lambda step: ())
    m.note_shrink(0, {1}, "device_lost")
    m.note_boundary()
    assert m.want_regrow(1) == (0, 1)  # cooldown of 1 boundary satisfied
    m.note_regrow(1, (0, 1))
    # the replica dies again right away: a flap — cooldown doubles
    m.note_shrink(2, {1}, "device_lost")
    assert m.flaps == 1
    m.note_boundary()
    assert m.want_regrow(3) is None  # 1 boundary since shrink < backoff 2
    m.note_boundary()
    assert m.want_regrow(4) == (0, 1)
    m.note_regrow(4, (0, 1))
    m.note_shrink(5, {1}, "device_lost")  # second flap -> backoff 4
    assert m.flaps == 2
    for _ in range(3):
        m.note_boundary()
        assert m.want_regrow(6) is None
    # flap history ages out after flap_window clean boundaries: the
    # breaker closes back down to the base cooldown
    m.note_boundary()
    assert m.want_regrow(7) == (0, 1)


# ---------------------------------------------------------------- loop


def test_shrink_replays_poison_step_and_loses_nothing(monkeypatch):
    set_faults(monkeypatch, "device_lost@3.2")
    built = []
    loop = ElasticLoop(fake_factory(8, built), 8)
    state = loop.ctx.place_state(fake_state())
    bs = batches(6)
    delivered = []
    for i, b in enumerate(bs):
        state, ready, ok = loop.step(state, b, None)
        delivered += [idx for idx, _aux in ready]
        assert ok
    state, ready, _ok = loop.flush(state)
    delivered += [idx for idx, _aux in ready]

    assert delivered == list(range(6))  # every step exactly once
    assert loop.monitor.shrinks == 1 and loop.active == tuple(
        o for o in range(8) if o != 2
    )
    assert built == [tuple(range(8)), loop.active]
    # aux_interval=1: the anchor IS the poison step — nothing besides it
    # re-executes
    assert loop.replayed_steps == 0
    assert int(state["step"]) == 6
    assert loop.last_recovery_s >= 0 and loop.recovery_s > 0

    # bitwise equivalence: steps 0-2 on the full mesh, then 3-5 on a
    # FRESH survivor context, must land on the identical state
    f = fake_factory(8)
    ref = run_ctx(f(tuple(range(8))), fake_state(), bs[:3])
    ref = run_ctx(f(loop.active), ref, bs, start=3)
    assert ref["w"] == state["w"]


def test_wedge_is_indistinguishable_from_loss(monkeypatch):
    final = {}
    for spec in ("device_lost@3.2", "device_wedge@3.2:2"):
        set_faults(monkeypatch, spec)
        loop = ElasticLoop(fake_factory(8), 8)
        state = loop.ctx.place_state(fake_state())
        for b in batches(6):
            state, _r, _ok = loop.step(state, b, None)
        final[spec] = float(state["w"])
        kind = loop.monitor.transitions[0]["kind"]
        assert kind == spec.split("@")[0]
    # mid-run dynamics must not depend on WHY the replica vanished
    assert final["device_lost@3.2"] == final["device_wedge@3.2:2"]


def test_emergency_checkpoint_is_committed_and_restorable(
    monkeypatch, tmp_path
):
    set_faults(monkeypatch, "device_lost@2.1")
    td = str(tmp_path)
    seen_meta = {}

    def ckpt(host_state, idx, meta):
        seen_meta.update(meta)
        return save_checkpoint(td, host_state, 0, idx, meta=meta)

    loop = ElasticLoop(fake_factory(8), 8, checkpoint_fn=ckpt)
    state = loop.ctx.place_state(fake_state())
    bs = batches(4)
    for b in bs:
        state, _r, _ok = loop.step(state, b, None)

    assert len(loop.emergency_ckpts) == 1
    path = loop.emergency_ckpts[0]
    assert is_committed(path)
    assert seen_meta["event"] == "shrink" and seen_meta["lost"] == [1]
    assert seen_meta["kind"] == "device_lost" and seen_meta["step"] == 2

    # a restarted job restores the anchor: stream position 2, the state
    # BEFORE the poison step — replaying 2..3 reproduces the elastic end
    got = load_restorable(td, fake_state())
    assert got is not None
    (epoch, pos), restored = got
    assert (epoch, pos) == (0, 2)
    ref = run_ctx(fake_factory(8)(loop.active), restored, bs, start=2)
    assert ref["w"] == state["w"]


def test_crash_inside_the_emergency_save_resumes_to_the_same_state(
    monkeypatch, tmp_path
):
    """The shrink's emergency save is itself killed between its data
    write and its commit: the orphan stays uncommitted, the restarted
    job resumes from the last committed dump, meets the same device
    fault, shrinks cleanly this time, and ends on the state of a run
    whose save was never interrupted."""
    td = str(tmp_path)
    bs = batches(5)

    def ckpt(host_state, idx, meta):
        return save_checkpoint(td, host_state, 0, idx, meta=meta)

    set_faults(monkeypatch, "device_lost@2.1")
    clean = ElasticLoop(fake_factory(8), 8)
    want = clean.ctx.place_state(fake_state())
    for b in bs:
        want, _r, _ok = clean.step(want, b, None)

    save_checkpoint(td, fake_state(), 0, 0)  # the last committed dump
    set_faults(monkeypatch, "device_lost@2.1,save_crash@1")
    loop = ElasticLoop(fake_factory(8), 8, checkpoint_fn=ckpt)
    state = loop.ctx.place_state(fake_state())
    with pytest.raises(faults.SimulatedCrash):
        for b in bs:
            state, _r, _ok = loop.step(state, b, None)
    assert any(d.endswith(".tmp") for d in os.listdir(td))
    assert loop.emergency_ckpts == []

    # the restart, in the same fault registry: ``save_crash@1`` is spent,
    # the device fault is still live
    (epoch, pos), restored = load_restorable(td, fake_state())
    assert (epoch, pos) == (0, 0)
    again = ElasticLoop(fake_factory(8), 8, checkpoint_fn=ckpt)
    state = again.ctx.place_state(restored)
    for b in bs[pos:]:
        state, _r, _ok = again.step(state, b, None)
    assert again.monitor.shrinks == 1 and again.active == clean.active
    assert len(again.emergency_ckpts) == 1
    assert is_committed(again.emergency_ckpts[0])
    assert state["w"] == want["w"] and int(state["step"]) == len(bs)


def test_window_replay_with_deferred_aux(monkeypatch):
    """aux_interval=2: the fault strikes the second step of a window —
    the already-dispatched first step re-executes too, and every aux is
    still delivered exactly once."""
    set_faults(monkeypatch, "device_lost@3.1")
    loop = ElasticLoop(fake_factory(8), 8, aux_interval=2)
    state = loop.ctx.place_state(fake_state())
    delivered = []
    for b in batches(6):
        state, ready, _ok = loop.step(state, b, None)
        delivered += [idx for idx, _aux in ready]
    state, ready, _ok = loop.flush(state)
    delivered += [idx for idx, _aux in ready]
    assert sorted(delivered) == list(range(6))
    assert len(delivered) == len(set(delivered))
    assert loop.replayed_steps == 1  # step 2 (dispatched, aux pending)
    assert int(state["step"]) == 6


def test_cascading_faults_shrink_twice(monkeypatch):
    set_faults(monkeypatch, "device_lost@3.2,device_lost@3.5")
    loop = ElasticLoop(fake_factory(8), 8)
    state = loop.ctx.place_state(fake_state())
    delivered = []
    for b in batches(6):
        state, ready, _ok = loop.step(state, b, None)
        delivered += [idx for idx, _aux in ready]
    assert delivered == list(range(6))
    assert loop.monitor.shrinks == 2
    assert loop.active == tuple(o for o in range(8) if o not in (2, 5))


def test_regrow_at_boundary_after_wedge_clears(monkeypatch):
    set_faults(monkeypatch, "device_wedge@2.1:3")  # down for steps [2, 5)
    built = []
    loop = ElasticLoop(fake_factory(8, built), 8)
    state = loop.ctx.place_state(fake_state())
    bs = batches(8)
    for b in bs[:6]:
        state, _r, _ok = loop.step(state, b, None)
    state, _r, _ok = loop.flush(state)
    state, regrown = loop.checkpoint_boundary(state)  # probe at step 6
    assert regrown and loop.active == tuple(range(8))
    assert loop.monitor.regrows == 1 and not loop.degraded
    for b in bs[6:]:
        state, _r, _ok = loop.step(state, b, None)
    assert int(state["step"]) == 8
    assert built == [tuple(range(8)),
                     tuple(o for o in range(8) if o != 1),
                     tuple(range(8))]

    # the regrown run equals the piecewise reference: full/survivor/full
    f = fake_factory(8)
    ref = run_ctx(f(tuple(range(8))), fake_state(), bs[:2])
    ref = run_ctx(f(tuple(o for o in range(8) if o != 1)), ref, bs[2:6])
    ref = run_ctx(f(tuple(range(8))), ref, bs[6:])
    assert ref["w"] == state["w"]


def test_regrow_blocked_while_replica_still_down(monkeypatch):
    set_faults(monkeypatch, "device_lost@2.1")  # no DUR: down forever
    loop = ElasticLoop(fake_factory(8), 8)
    state = loop.ctx.place_state(fake_state())
    for b in batches(6):
        state, _r, _ok = loop.step(state, b, None)
    state, _r, _ok = loop.flush(state)
    state, regrown = loop.checkpoint_boundary(state)
    assert not regrown and loop.degraded
    assert loop.monitor.boundaries == 1


def test_checkpoint_boundary_refuses_pending_window(monkeypatch):
    monkeypatch.delenv(faults.ENV_VAR, raising=False)
    faults.reset()
    loop = ElasticLoop(fake_factory(8), 8, aux_interval=4)
    state = loop.ctx.place_state(fake_state())
    state, _r, _ok = loop.step(state, batches(1)[0], None)
    with pytest.raises(RuntimeError, match="flush first"):
        loop.checkpoint_boundary(state)


def test_no_survivors_raises(monkeypatch):
    set_faults(monkeypatch, "device_lost@0.0")
    loop = ElasticLoop(fake_factory(1), 1)
    state = loop.ctx.place_state(fake_state())
    with pytest.raises(NoSurvivorsError):
        loop.step(state, batches(1, rows=1)[0], None)


def test_unrelated_exception_propagates(monkeypatch):
    monkeypatch.delenv(faults.ENV_VAR, raising=False)
    faults.reset()

    def broken_factory(active):
        ctx = fake_factory(8)(active)

        def step_fn(state, batch, rng, lr_scale=1.0):
            raise ValueError("not a device fault")

        return ElasticContext(
            active=ctx.active, step_fn=step_fn,
            place_state=ctx.place_state, place_batch=ctx.place_batch,
        )

    loop = ElasticLoop(broken_factory, 8)
    state = loop.ctx.place_state(fake_state())
    with pytest.raises(ValueError, match="not a device fault"):
        loop.step(state, batches(1)[0], None)
    assert loop.monitor.shrinks == 0  # no membership change on foreign errors


def test_stats_shape(monkeypatch):
    set_faults(monkeypatch, "device_lost@1.3")
    loop = ElasticLoop(fake_factory(8), 8)
    state = loop.ctx.place_state(fake_state())
    for b in batches(3):
        state, _r, _ok = loop.step(state, b, None)
    s = loop.stats()
    assert s["base_replicas"] == 8 and s["active_replicas"] == 7
    assert s["shrinks"] == 1 and s["emergency_checkpoints"] == 0
    assert s["recovery_s"] >= 0 and "pipeline" in s


# ----------------------------------------------------- real shard_map


@pytest.mark.slow
@pytest.mark.deadline(1800)
def test_real_mesh_shrink_bitwise(monkeypatch, tmp_path):
    """One real shard_map scenario: lose 1 of 8 mid-run, finish on 7, and match a fresh
    survivor-mesh run restored from the emergency checkpoint bytewise."""
    import jax

    from mx_rcnn_tpu.core.resilience import host_copy
    from mx_rcnn_tpu.core.train import create_train_state, make_optimizer
    from mx_rcnn_tpu.data.loader import TrainLoader
    from mx_rcnn_tpu.data.synthetic import SyntheticDataset
    from mx_rcnn_tpu.models import build_model
    from tests.test_loader import small_cfg

    cfg = small_cfg()
    roidb = SyntheticDataset(
        num_images=8, num_classes=4,
        image_size=cfg.SHAPE_BUCKETS[0], max_boxes=2,
    ).gt_roidb()
    model = build_model(cfg)
    h, w = cfg.SHAPE_BUCKETS[0]
    params = model.init(
        {"params": jax.random.key(0), "sampling": jax.random.key(1)},
        images=np.zeros((1, h, w, 3), np.float32),
        im_info=np.array([[h, w, 1.0]], np.float32),
        gt_boxes=np.zeros((1, cfg.dataset.MAX_GT_BOXES, 5), np.float32),
        gt_valid=np.zeros((1, cfg.dataset.MAX_GT_BOXES), bool),
        train=True,
    )["params"]
    tx = make_optimizer(cfg, lambda s: cfg.TRAIN.LEARNING_RATE)
    host_params = host_copy(params)
    loader = TrainLoader(roidb, cfg, 8, shuffle=True, seed=0, prefetch=0)
    bs = []
    while len(bs) < 4:
        bs += list(loader)
    bs = bs[:4]
    rng = jax.random.key(0)

    def state_bytes(state):
        return b"".join(
            np.asarray(x).tobytes()
            for x in jax.tree_util.tree_leaves(jax.device_get(state))
        )

    set_faults(monkeypatch, "device_lost@1.4")
    td = str(tmp_path)
    factory = make_elastic_factory(model, tx)
    loop = ElasticLoop(
        factory, 8,
        checkpoint_fn=lambda s, i, m: save_checkpoint(td, s, 0, i, meta=m),
    )
    state = loop.ctx.place_state(
        host_copy(create_train_state(host_params, tx))
    )
    for b in bs:
        state, _r, _ok = loop.step(state, b, rng)
    assert loop.monitor.shrinks == 1 and len(loop.active) == 7
    elastic_bytes = state_bytes(state)

    got = load_restorable(
        td, host_copy(create_train_state(host_params, tx))
    )
    assert got is not None
    (_e, anchor), restored = got
    assert anchor == 1
    ctx = factory(loop.active)
    st = ctx.place_state(restored)
    for b in bs[anchor:]:
        st, _aux = ctx.step_fn(st, ctx.place_batch(b), rng)
    assert state_bytes(st) == elastic_bytes
