"""``graphs/fpn.py`` against hand counts, and the cell ``fpn_train_b8`` as
``spec.load_cell`` assembles it from files found by name."""

import json
import os

import pytest

from harness import flops, spec

CONFIG = json.load(open(os.path.join(
    spec.BENCH_DIR, "configs", "frcnn_r50_fpn_coco.json")))
H, W, ROIS = 608, 1024, 128
#: per-layer metrics this configuration's cell names itself
FPN_METRICS = {
    "neck_device_ms.train", "roi_align_device_ms.train",
    "roi_align_stream_device_ms.train", "roi_align_roofline.fpn_train",
}
#: per-layer ``.train`` metrics without a ``workloads`` list: they attach
#: to every cell that reports ``train_img_per_s``
UNLISTED_TRAIN = {
    "step_device_ms.train", "step_mfu.train", "device_idle_share.train",
    "feed_wait_share.train", "loader_batch_ms.train",
    "guard_snapshot_ms.train", "backbone_device_ms.train",
    "proposal_device_ms.train", "roi_head_device_ms.train",
}


def _layer(name):
    (found,) = [l for l in flops.layers_of(CONFIG, H, W, ROIS)
                if l.name == name]
    return found


@pytest.mark.parametrize("name, want, trains, needs_dx", [
    # P2 is 152x256: a 1x1 conv from C2's 256 channels to 256; C2 comes
    # from the frozen stage1, so no gradient goes into it
    ("neck/lateral2", 2 * 152 * 256 * 256 * 256, True, False),
    # the shared RPN's 3x3 conv on P4 (38x64), 256 -> 256
    ("rpn/p4/rpn_conv", 2 * 38 * 64 * 9 * 256 * 256, True, True),
    # fc1 on 128 rois of 14x14x256
    ("fc1", 2 * 14 * 14 * 256 * 1024 * 128, True, True),
])
def test_three_layers_of_one_608x1024_image_by_hand(name, want, trains,
                                                    needs_dx):
    layer = _layer(name)
    assert layer.flops == want
    assert (layer.trains, layer.needs_dx) == (trains, needs_dx)


def test_the_pyramid_has_five_rpn_levels_and_four_necks():
    names = [l.name for l in flops.layers_of(CONFIG, H, W, ROIS)]
    assert sum(n.endswith("/rpn_conv") for n in names) == 5
    assert [n for n in names if n.startswith("neck/lateral")] == [
        f"neck/lateral{lv}" for lv in (2, 3, 4, 5)]
    # P6 is 10x16: half of P5's 19x32, by ceiling
    assert _layer("rpn/p6/rpn_cls_score").flops == 2 * 10 * 16 * 256 * 6
    # the trunk's stage4 is convolutional here, on the 19x32 map
    assert _layer("stage4/unit3/conv2").flops == 2 * 19 * 32 * 9 * 512 * 512
    frozen = {l.name.split("/")[0] for l in flops.layers_of(CONFIG, H, W, ROIS)
              if not l.trains}
    assert frozen == {"conv0", "stage1"}


def test_strides_the_maps_do_not_have_are_refused():
    bad = dict(CONFIG, model=dict(CONFIG["model"], strides=[4, 8, 16, 32, 32]))
    with pytest.raises(ValueError, match="strides"):
        flops.layers_of(bad, H, W, ROIS)


def test_roi_align_pools_bytes_by_hand():
    """bf16, forward and backward: each of the four maps once, the 128
    pooled rois once - not once a level."""
    maps = 152 * 256 + 76 * 128 + 38 * 64 + 19 * 32
    want = 2 * 2 * 256 * (maps + ROIS * 14 * 14)
    got = flops.roi_align_least_s(CONFIG, H, W, ROIS, 2, True, 197e12, 819e9)
    assert got["bytes"] == want == 78610432
    assert got["bound"] == "bytes"
    assert got["least_s"] == pytest.approx(want / 819e9)
    pools = flops.load_graph("fpn").roi_align_pools(CONFIG["model"], H, W, ROIS)
    assert [(p.map_h, p.map_w) for p in pools] == [
        (152, 256), (76, 128), (38, 64), (19, 32)]
    assert sum(p.rois for p in pools) == ROIS


def test_train_flops_of_one_image():
    """Some hundreds of GFLOP: less than the C4 flagship's 1098 (a
    ResNet-50 trunk, no res5 on 1024 rois), and the neck and the
    five-level RPN at stride 4 are more than half of it."""
    train = flops.train_flops(CONFIG, H, W, ROIS)
    assert 5e11 < train < 9e11
    neck_rpn = sum(l.flops * (1 + l.trains + l.needs_dx)
                   for l in flops.layers_of(CONFIG, H, W, ROIS)
                   if l.name.startswith(("neck/", "rpn/")))
    assert 0.5 < neck_rpn / train < 0.7


def test_the_cell_finds_every_file_and_its_metrics():
    cell = spec.load_cell("fpn_train_b8")
    assert cell.chips == 1 and cell.traffic["kind"] == "train"
    assert cell.config["name"] == "frcnn_r50_fpn_coco"
    assert cell.config["reduced"] == []
    assert cell.config["model"]["graph"] == "fpn"
    assert {m["name"] for m in cell.end_to_end} == {
        "train_img_per_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == (
        FPN_METRICS | UNLISTED_TRAIN | {"compile_s"})
    for m in cell.end_to_end + cell.per_layer:
        assert callable(spec.load_reader(m["name"]))
    assert set(cell.limits["limits"]) <= {
        "batch_gap", "fg_anchors_gap", "loss2_gap", "grad1_gap", "dparam_gap"}
    assert cell.limits["limits"]["batch_gap"] == 0.0
    for sub, name in (("graphs", "fpn.py"),
                      ("reference/models", "fpn.py")):
        assert os.path.exists(os.path.join(spec.BENCH_DIR, sub, name))


def test_the_flagship_cell_attaches_none_of_the_new_metrics():
    names = {m["name"] for m in spec.load_cell("c4_train_b8").per_layer}
    assert not names & FPN_METRICS
    assert "roi_align_roofline.train" in names
    assert "roi_align_roofline.train" not in {
        m["name"] for m in spec.load_cell("fpn_train_b8").per_layer}
