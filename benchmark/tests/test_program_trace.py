"""The program-trace readers on a written trace whose every interval and
stat is known by hand (``xplane_stats_writer``): each of the metrics
to the digit, None on a trace without the program's spans and scopes, and
the once-per-run cache."""

import os

import pytest

import xplane_stats_writer as xw
from harness import spec


def _op(name, tf_op, offset, duration):
    """A device operation as the profiler writes it: named by its HLO
    line, its scope path a stat of the event's metadata entry."""
    line = f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop"
    return (line, offset, duration, {"@tf_op": tf_op + ":"} if tf_op else {})


STEP = "jit(step_fn)/jit(main)"
FWD = STEP + "/jvp(FasterRCNN)/FasterRCNN.train_forward"
BWD = STEP + "/transpose(jvp(FasterRCNN))/FasterRCNN.train_forward"

# two steps of 1000 ns; the loop under `proposal` is counted by its body's
# events (110..140 and 130..160: 50 ns of the loop's 80)
TRAIN_PLANES = [
    ("/device:TPU:0", [
        ("XLA Ops", 0, [
            _op("fusion.1", FWD + "/backbone/conv", 0, 100),
            _op("while.2", "", 100, 80),   # a loop has no path of its own
            _op("fusion.3", FWD + "/proposal/vmap(jit(nms))/while/body/add",
                110, 30),
            _op("fusion.8", FWD + "/proposal/vmap(jit(nms))/while/body/mul",
                130, 30),
            _op("pallas_roi_features_fwd.1",
                FWD + "/roi_head/FasterRCNN._roi_features/"
                "pallas_roi_features_fwd/pallas_call", 200, 40),
            _op("fusion.4", FWD + "/roi_head/rcnn/dot_general", 240, 10),
            _op("fusion.5", BWD + "/roi_head/rcnn/dot_general", 300, 20),
            _op("fusion.6", BWD + "/backbone/conv", 320, 200),
            _op("fusion.7", STEP + "/update/mul", 520, 30),
            _op("fusion.1", FWD + "/backbone/conv", 1000, 100),
            _op("fusion.6", BWD + "/backbone/conv", 1320, 100),
            _op("copy.9", "", 1500, 10),
        ]),
        ("XLA Modules", 0, [("jit_step_fn(7)", 0, 550),
                            ("jit_step_fn(7)", 1000, 510),
                            ("jit_norms(9)", 1600, 10)]),
    ]),
    ("/host:CPU", [
        ("python", 0, [
            ("rcnn.step.dispatch", 0, 50, {"step": 66, "tag": "step"}),
            ("rcnn.feed.wait", 100, 300),
            ("rcnn.step.dispatch", 400, 50, {"step": 67, "tag": "step"}),
            ("rcnn.feed.wait", 900, 400),      # 900..1300, clipped at 1000
            ("rcnn.step.dispatch", 1000, 50, {"step": 68, "tag": "step"}),
            ("bench.dispatch_step", 0, 60),
        ]),
        ("python", 0, [
            ("rcnn.feed.place", 0, 10, {"batch": 66}),
            ("rcnn.feed.wait", 0, 1000),        # not the loop's thread
            ("rcnn.loader.wait", 20, 100),
        ]),
        ("python", 0, [("rcnn.loader.assemble", 0, 300, {"batch": 70}),
                       ("rcnn.loader.assemble", 300, 500, {"batch": 72})]),
        ("python", 0, [("rcnn.loader.assemble", 0, 400, {"batch": 71})]),
    ]),
]

PP = "jit(predict)/jit(main)/postprocess"
NMS = PP + "/vmap(class_nms)/vmap(jit(nms))/while"

# busy [0,100] [150,1000] [3000000,3000500]: one gap of 50 ns (too short)
# and one of 2999000 ns
SERVE_PLANES = [
    ("/device:TPU:0", [
        ("XLA Ops", 0, [
            _op("fusion.1", "jit(predict)/jit(main)/FasterRCNN/backbone/c",
                0, 100),
            _op("fusion.2", PP + "/vmap(decode)/mul", 150, 50),
            _op("while.13", "", 200, 800),
            _op("fusion.3", NMS + "/body/closed_call/select_n", 300, 100),
            _op("fusion.3", NMS + "/body/closed_call/select_n", 500, 100),
            _op("fusion.1", "jit(predict)/jit(main)/FasterRCNN/backbone/c",
                3_000_000, 500),
        ]),
        ("XLA Modules", 0, [("jit_fwd(3)", 0, 1000),
                            ("jit_fwd(3)", 600_000, 300_000),
                            ("jit__identity_fn(5)", 990_000, 10)]),
    ]),
    ("/host:CPU", [
        ("python", 0, [
            ("rcnn.serve.prepare", 0, 500, {"req": 1}),
            ("rcnn.serve.prepare", 2_000_000, 250_000, {"req": 4}),
            ("bench.wait_reply", 500, 5_000_000),
        ]),
        ("python", 0, [
            ("rcnn.serve.batch_wait", 0, 900),
            ("rcnn.serve.pickup", 900, 1,
             {"batch": 1, "n": 2, "reqs": "[1, 2]", "wait_ms": 30.5,
              "wait_ms_each": "[30.5, 10.0]"}),
            ("rcnn.serve.assemble", 1000, 200_000,
             {"batch": 1, "bucket": "(608, 1024)"}),
            ("rcnn.serve.batch_wait", 201_000, 4_000_000),
            ("rcnn.serve.pickup", 4_201_000, 1,
             {"batch": 2, "n": 1, "reqs": 3, "wait_ms": 70.0,
              "wait_ms_each": 70.0}),
            ("rcnn.serve.assemble", 4_202_000, 100_000,
             {"batch": 2, "bucket": "(608, 1024)"}),
        ]),
        ("python", 0, [
            ("rcnn.serve.dispatch", 300_000, 100_000, {"batch": 1}),
            ("rcnn.serve.fetch", 400_000, 600_000, {"batch": 1}),
            ("rcnn.serve.postprocess", 1_000_000, 500_000,
             {"batch": 1, "n": 2}),
            ("rcnn.serve.dispatch", 4_400_000, 50_000, {"batch": 2}),
        ]),
        # the runtime re-tiling batch 2's input: no span of the program's
        ("pjrt-tpu-tasks/7", 0, [("XlaLinearize", 2_500_000, 400_000),
                                 ("Transpose", 2_500_000, 10)]),
    ]),
]

BARE_PLANES = [
    ("/device:TPU:0", [
        ("XLA Ops", 0, [_op("fusion.1", "jit(step_fn)/jit(main)/mul", 0, 9),
                        _op("while.13", "", 2_000_000, 10)]),
        ("XLA Modules", 0, [("jit_step_fn(7)", 0, 9)]),
    ]),
    ("/host:CPU", [("python", 0, [("bench.dispatch_step", 0, 60)])]),
]

REPORT = {"pipeline": {"snapshots": 4, "snapshot_ms": 500.0}}

# (metric, kind of the run, planes, the run's report) → value
CASES = [
    # waits on the loop's thread inside [0, 1000]: 300 + 100 of 1000
    ("feed_wait_share.train", "train", TRAIN_PLANES, None, 40.0),
    ("loader_batch_ms.train", "train", TRAIN_PLANES, None, 400 / 1e6),
    ("guard_snapshot_ms.train", "train", TRAIN_PLANES, REPORT, 125.0),
    # forward 100 + backward 200, then 100 + 100, over two steps
    ("backbone_device_ms.train", "train", TRAIN_PLANES, None, 250 / 1e6),
    # the loop's body: 110..160, its overlap counted once, over two steps
    ("proposal_device_ms.train", "train", TRAIN_PLANES, None, 25 / 1e6),
    ("roi_head_device_ms.train", "train", TRAIN_PLANES, None, 35 / 1e6),
    ("queue_wait_p50_ms.serve", "serve", SERVE_PLANES, None, 30.5),
    # batch 1: 200000 + 100000 + 500000; batch 2 lacks its postprocess
    ("batch_host_ms.serve", "serve", SERVE_PLANES, None, 0.8),
    # the loop's body, 100 + 100, of busy 100 + 850 + 500
    ("class_nms_device_share.serve", "serve", SERVE_PLANES, None,
     100 * 200 / 1450),
    # batch 1 left the host at 400000; the last program done before its
    # fetch returned (1000000) began at 600000; batch 2 was not fetched
    ("dispatch_to_device_ms.serve", "serve", SERVE_PLANES, None, 0.2),
    # the gap 1000..3000000: assemble to 201000, dispatch .. postprocess
    # 300000..1500000, prepare 2000000..2250000 cover 1650000 of 2999000
    ("idle_unattributed_share.serve", "serve", SERVE_PLANES, None,
     100 * (2_999_000 - 1_650_000) / 2_999_000),
]


def _ctx(tmp_path, planes, kind, report=None, traced=True):
    d = tmp_path / "trace" / "plugins" / "profile" / "run1"
    d.mkdir(parents=True, exist_ok=True)
    xw.write(str(d / "host.xplane.pb"), planes)
    run = {"kind": kind, "trace_dir": str(tmp_path / "trace")}
    if report is not None:
        run["report"] = report
    return {"cell": None, "run": run, "device": {},
            "trace": object() if traced else None}


@pytest.mark.parametrize("metric,kind,planes,report,want", CASES,
                         ids=[c[0] for c in CASES])
def test_metric_to_the_digit(tmp_path, metric, kind, planes, report, want):
    ctx = _ctx(tmp_path, planes, kind, report)
    assert spec.read_metrics([metric], ctx) == {metric: pytest.approx(want)}


@pytest.mark.parametrize("metric,kind", [(c[0], c[1]) for c in CASES],
                         ids=[c[0] for c in CASES])
def test_none_without_the_programs_spans_and_scopes(tmp_path, metric, kind):
    """The parent of the PR that added them: no ``rcnn.*`` span, no stage
    scope, no ``pipeline`` in the report - and no exception."""
    ctx = _ctx(tmp_path, BARE_PLANES, kind, report={"steps": 3})
    assert spec.read_metrics([metric], ctx) == {}
    other = "serve" if kind == "train" else "train"
    planes = TRAIN_PLANES if kind == "train" else SERVE_PLANES
    assert spec.read_metrics([metric], _ctx(tmp_path, planes, other)) == {}
    untraced = _ctx(tmp_path, planes, kind, traced=False)
    assert spec.read_metrics([metric], untraced) == {}


def test_a_feed_that_never_waited_reads_zero_not_none(tmp_path):
    planes = [TRAIN_PLANES[0], ("/host:CPU", [("python", 0, [
        ("rcnn.step.dispatch", 0, 50, {"step": 1}),
        ("rcnn.step.dispatch", 400, 50, {"step": 2})])])]
    ctx = _ctx(tmp_path, planes, "train")
    assert spec.read_metrics(["feed_wait_share.train"], ctx) == {
        "feed_wait_share.train": 0.0}


def test_the_trace_is_parsed_once_a_run(tmp_path, monkeypatch):
    from jax.profiler import ProfileData

    opened = []
    from_file = ProfileData.from_file
    monkeypatch.setattr(
        ProfileData, "from_file",
        staticmethod(lambda p: opened.append(p) or from_file(p)))
    ctx = _ctx(tmp_path, SERVE_PLANES, "serve")
    names = [c[0] for c in CASES if c[1] == "serve"]
    assert len(spec.read_metrics(names, ctx)) == 5
    assert len(opened) == 1 and "program_trace" in ctx


def test_scope_path_strips_the_transforms_wrappers():
    import importlib.util

    path = os.path.join(spec.BENCH_DIR, "metrics", "program_trace.py")
    s = importlib.util.spec_from_file_location("pt", path)
    pt = importlib.util.module_from_spec(s)
    s.loader.exec_module(pt)
    assert pt.scope_path(
        "jit(step_fn)/transpose(jvp(FasterRCNN))/FasterRCNN.train_forward/"
        "roi_head/FasterRCNN._roi_features/pallas_call:"
    ) == ("step_fn", "FasterRCNN", "FasterRCNN.train_forward", "roi_head",
          "FasterRCNN._roi_features", "pallas_call")
    # a fusion of several instructions lists their paths: the first counts
    assert pt.scope_path("jit(f)/postprocess/vmap(decode)/reshape;"
                         "jit(f)/M/M.test_forward/reshape:")[1:3] == (
        "postprocess", "decode")
    ops = [pt.Op(pt.scope_path(n), 0, 1) for n in (
        "jit(f)/postprocess/vmap(class_nms)/while:", "jit(f)/class_nms/x:",
        "jit(f)/postprocess/decode/class_nms:")]
    assert len(pt.in_scope(ops, "postprocess/class_nms")) == 1
    assert len(pt.in_scope(ops, "class_nms")) == 3


def test_idle_by_span_tabulates_both_cells(tmp_path):
    import importlib.util

    path = os.path.join(spec.BENCH_DIR, "tools", "idle_by_span.py")
    s = importlib.util.spec_from_file_location("idle_by_span", path)
    tool = importlib.util.module_from_spec(s)
    s.loader.exec_module(tool)
    p = xw.write(str(tmp_path / "s.xplane.pb"), SERVE_PLANES)
    text = tool.tables(p)
    assert "1 gaps of 1 ms or more" in text
    assert "completion (1 thread(s))" in text and "caller" in text
    assert "postprocess/class_nms" in text
    # fetch 400000..1000000 lies inside the gap: 0.0006 s of it
    assert "rcnn.serve.fetch" in text and "0.0006" in text
    assert "runtime (1 thread(s))" in text and "XlaLinearize" in text
    p = xw.write(str(tmp_path / "t.xplane.pb"), TRAIN_PLANES)
    text = tool.tables(p)
    assert "backbone" in text and "(no stage scope)" in text
