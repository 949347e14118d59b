"""The benchmark's reader of the guard's flush cycle
(``benchmark/metrics/guard_cycle.py``) on the program's report: the
median of the host's records, the cycle the harness's closing call ended
left out, and None where the program files no cycles."""

from types import SimpleNamespace

import pytest

import benchmark_cases  # noqa: F401 — sys.path for the imports below

from harness import spec  # noqa: E402

METRIC = "guard_flush_idle_ms.train"

CYCLES = [
    {"step": 0, "n": 2, "idle_ms": 14.0},
    {"step": 64, "n": 64, "idle_ms": 30.0},
    {"step": 128, "n": 64, "idle_ms": 20.0},
]
REPORT = {"pipeline": {"snapshots": 4, "snapshot_ms": 50.0,
                       "cycles": CYCLES}}
PARENT_REPORT = {"pipeline": {"snapshots": 4, "snapshot_ms": 50.0}}


def _ctx(kind="train", report=REPORT, traffic=None, attempted=None):
    run = {"kind": kind}
    if report is not None:
        run["report"] = report
    if attempted is not None:
        run["attempted"] = attempted
    cell = None if traffic is None else SimpleNamespace(traffic=traffic)
    return {"cell": cell, "run": run, "device": {}, "trace": None}


@pytest.mark.parametrize("cycles,want", [
    (CYCLES, 20.0),
    (CYCLES[1:], 25.0),
    (CYCLES[:1], 14.0),
])
def test_median_of_the_records(cycles, want):
    ctx = _ctx(report={"pipeline": {"cycles": cycles}})
    assert spec.read_metrics([METRIC], ctx) == {METRIC: want}


@pytest.mark.parametrize("traffic,attempted,want", [
    # 66 warm steps, a window of 63 closes with the call that sends step
    # 128, the first after the flush of steps 64-127: that cycle's resume
    # holds the harness's wait (and its profiler's stop), so it is out
    ({"warm_steps": 66, "check_steps": 2}, 63, 17.0),
    # the window closed on step 127, before that flush: every cycle in
    ({"warm_steps": 66, "check_steps": 2}, 62, 20.0),
    # the hook opens its window after max(warm_steps, check_steps) calls
    ({"warm_steps": 2, "check_steps": 66}, 63, 17.0),
    ({"warm_steps": 2, "check_steps": 66}, 125, 20.0),
])
def test_the_cycle_the_harness_closed_is_left_out(traffic, attempted, want):
    ctx = _ctx(traffic=traffic, attempted=attempted)
    assert spec.read_metrics([METRIC], ctx) == {METRIC: want}


def test_none_where_the_harness_closed_the_only_cycle():
    ctx = _ctx(report={"pipeline": {"cycles": CYCLES[1:2]}},
               traffic={"warm_steps": 66, "check_steps": 2}, attempted=63)
    assert spec.read_metrics([METRIC], ctx) == {}


@pytest.mark.parametrize("kind,report", [
    ("train", PARENT_REPORT),  # the parent of the PR that added them
    ("train", None),
    ("train", {"steps": 3}),
    ("train", {"pipeline": {"cycles": []}}),
    ("serve", REPORT),
])
def test_none_where_the_program_files_no_cycles(kind, report):
    """No exception either: the reader leaves the metric out."""
    assert spec.read_metrics([METRIC], _ctx(kind, report)) == {}
