"""The Deformable ConvNets cell at a small size on the CPU: driver, check
against ``reference/models/dcn.py`` and the last line, end to end.  The
widths and the depth are the published ones (the reference holds
ResNet-101 alone); the images are 192x192, the smallest that holds a
128-pixel anchor inside its border.  Nothing here is a device number."""

import copy

import pytest

from harness import rehearsal, spec

pytestmark = pytest.mark.rehearsal

SMALL = copy.deepcopy(rehearsal.TINY)
SMALL[""]["SHAPE_BUCKETS"] = [[192, 192]]
SMALL["dataset"]["SCALES"] = [[192, 192]]
SMALL["network"]["depth"] = 101


def test_dcn_train_cell_runs_end_to_end_and_is_correct():
    cell = rehearsal.tiny_cell(spec.load_cell("dcn_train_b8"))
    cell.traffic.update(bucket=[192, 192])
    r = rehearsal.run_cell(cell, seed=2**31 + 13, seconds=2.0,
                           overrides=SMALL)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"train_img_per_s", "setup_s"}
    assert r["device"]["platform"] == "cpu"      # never a device number
    assert r["compared"]["batch_gap"]["value"] == 0.0
    assert r["compared"]["fg_anchors_gap"]["value"] == 0.0
    # float32 against float32 on the CPU, the same samples on both sides:
    # round-off alone
    assert r["compared"]["loss2_gap"]["value"] < 1e-4
    assert r["compared"]["grad1_gap"]["value"] < 1e-3
    assert r["compared"]["dparam_gap"]["value"] < 1e-3
    assert r["detail"]["program_counts"][0]["num_fg_anchors"] > 0
