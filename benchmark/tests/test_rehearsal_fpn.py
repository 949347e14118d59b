"""The pyramid's cell at a tiny size on the CPU: driver, check against
``reference/models/fpn.py`` and the last line, end to end.  Nothing here
is a device number."""

import pytest

from harness import rehearsal, spec

pytestmark = pytest.mark.rehearsal


def test_fpn_train_cell_runs_end_to_end_and_is_correct():
    cell = rehearsal.tiny_cell(spec.load_cell("fpn_train_b8"))
    r = rehearsal.run_cell(cell, seed=2**31 + 11, seconds=2.0)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"train_img_per_s", "setup_s"}
    assert r["device"]["platform"] == "cpu"      # never a device number
    assert r["compared"]["batch_gap"]["value"] == 0.0
    assert r["compared"]["fg_anchors_gap"]["value"] == 0.0
    # float32 against float32 on the CPU: pool-four-and-mask against
    # pool-once differ by round-off alone (read 9e-7, 6e-5, 7e-5)
    assert r["compared"]["loss2_gap"]["value"] < 1e-4
    assert r["compared"]["grad1_gap"]["value"] < 1e-3
    assert r["compared"]["dparam_gap"]["value"] < 1e-3
    levels = r["detail"]["program_counts"][0]
    assert levels["num_fg_anchors"] > 0
