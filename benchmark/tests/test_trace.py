"""The trace reducer on a written trace whose every interval is known."""

import pytest

import xplane_writer as xw
from harness import trace as tr

PLANES = [
    ("/device:TPU:0", [
        ("XLA Ops", 1000, [("fusion.1", 0, 100), ("roi_align_fwd", 150, 50),
                           ("fusion.1", 180, 40), ("roi_align_bwd", 300, 100),
                           ("copy.3", 1000, 100)]),
        ("XLA Modules", 1000, [("jit_step_fn(1)", 0, 220),
                               ("jit_step_fn(1)", 300, 100),
                               ("jit_other(2)", 1000, 100)]),
        ("Steps", 1000, [("0", 0, 1100)]),
    ]),
    ("/device:TPU:1", [
        ("XLA Ops", 1000, [("fusion.1", 0, 1100)]),
        ("XLA Modules", 1000, [("jit_step_fn(1)", 0, 1100)]),
    ]),
    ("/host:CPU", [
        ("main", 1000, [("bench.window", 0, 2000), ("bench.wait", 500, 400),
                        ("not_ours", 0, 5000)]),
    ]),
]


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    d = tmp_path_factory.mktemp("t") / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    xw.write(str(d / "host.xplane.pb"), PLANES)
    return tr.load(tr.find_xplane(str(d.parent.parent.parent)))


def test_planes_lines_and_spans_are_found(trace):
    assert sorted(trace.ops) == ["/device:TPU:0", "/device:TPU:1"]
    assert len(trace.ops["/device:TPU:0"]) == 5
    assert [n for n, _s, _d in trace.spans] == ["bench.window", "bench.wait"]


def test_busy_is_the_union_of_intervals():
    evs = [("a", 0, 100), ("b", 150, 50), ("c", 180, 40), ("d", 90, 20)]
    assert tr.union_ns(evs) == 110 + 70
    assert tr.union_ns([]) == 0


def test_busy_window_and_idle_share(trace):
    busy, window = tr.busy_and_window_s(trace)
    # chip 0: [0,100] + [150,220] + [300,400] + [1000,1100] = 370 of 1100;
    # chip 1: 1100 of 1100; averaged over the chips
    assert busy == pytest.approx((370 + 1100) / 2 / 1e9)
    assert window == pytest.approx(1100 / 1e9)
    assert tr.idle_share_pct(trace) == pytest.approx(100 * (1 - 735 / 1100))


def test_per_step_device_time_is_the_median_module_duration(trace):
    assert tr.module_median_ms(trace, "step_fn") == pytest.approx(160 / 1e6)
    assert tr.module_median_ms(trace, "no_such_program") is None


def test_event_lookup_by_name_and_failure_on_no_match(trace):
    secs, n = tr.ops_time_s(trace, "roi_align")
    assert n == 2 and secs == pytest.approx(150 / 2 / 1e9)
    assert tr.ops_time_s(trace, "nothing_like_it") == (0.0, 0)
    from harness import spec
    from metrics_readers import readers

    cell = spec.load_cell("c4_train_b8")
    ctx = {"trace": trace, "run": {"kind": "train"}, "cell": cell,
           "device": {"kind": "TPU v5 lite", "count": 1}}
    with pytest.raises(RuntimeError, match="no device operation matches"):
        readers.roi_align_roofline(ctx, "nothing_like_it", 2, 2)
    share = readers.roi_align_roofline(ctx, "roi_align", 2, 2)
    assert share is not None and share > 0


def test_breakdown_names_the_heaviest_ops_and_labels_gaps(trace):
    top = tr.top_ops(trace, 2)
    assert top[0] == ["fusion.1", pytest.approx(140 / 1e9)]
    gaps = tr.idle_gaps(trace, 3)
    assert gaps[0][1] == pytest.approx(600 / 1e9)   # 400 → 1000
    assert gaps[0][0] == "bench.wait"               # innermost open span
    assert gaps[1][0] == "bench.window"


def test_a_trace_with_no_device_operation_is_an_error(tmp_path):
    p = xw.write(str(tmp_path / "e.xplane.pb"),
                 [("/host:CPU", [("main", 0, [("x", 0, 1)])])])
    with pytest.raises(RuntimeError, match="no operation ran"):
        tr.busy_and_window_s(tr.load(p))
