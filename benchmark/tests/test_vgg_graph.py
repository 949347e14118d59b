"""``graphs/vgg.py`` against hand counts, and the cell ``vgg_train_b8`` as
``spec.load_cell`` assembles it from files found by name."""

import json
import os

import pytest

from harness import flops, spec

CONFIG = json.load(open(os.path.join(
    spec.BENCH_DIR, "configs", "frcnn_vgg16_voc.json")))
H, W, ROIS = 608, 1024, 128
#: per-layer metrics this configuration's cell names itself
VGG_METRICS = {
    "roi_pool_device_ms.train", "top_head_device_ms.train",
    "roi_pool_roofline.vgg_train",
}


def _unlisted_train():
    """Per-layer metrics without a ``workloads`` list that move
    ``train_img_per_s``: they attach to every train cell by themselves."""
    return {m["name"] for m in spec.load_benchmark()["per_layer"]
            if "workloads" not in m and m["moves"] == "train_img_per_s"}


def _layer(name, h=H, w=W, rois=ROIS):
    (found,) = [l for l in flops.layers_of(CONFIG, h, w, rois)
                if l.name == name]
    return found


@pytest.mark.parametrize("name, want, trains, needs_dx", [
    # 608x1024 at full extent, 3 -> 64: fixed, nothing flows into it
    ("conv1_1", 2 * 608 * 1024 * 9 * 3 * 64, False, False),
    # the first trained layer reads the fixed conv2's output: dW, no dX
    ("conv3_1", 2 * 152 * 256 * 9 * 128 * 256, True, False),
    ("conv5_3", 2 * 38 * 64 * 9 * 512 * 512, True, True),
    ("rpn_conv", 2 * 38 * 64 * 9 * 512 * 512, True, True),
    # fc6 on 128 rois of 7x7x512
    ("fc6", 2 * 25088 * 4096 * 128, True, True),
    ("bbox_pred", 2 * 4096 * 84 * 128, True, True),
])
def test_layers_of_one_608x1024_image_by_hand(name, want, trains, needs_dx):
    layer = _layer(name)
    assert layer.flops == want
    assert (layer.trains, layer.needs_dx) == (trains, needs_dx)


def test_the_thirteen_convolutions_are_15_3_gmac_at_224():
    """Simonyan & Zisserman's configuration D at its own 224x224 input:
    15.35 G multiply-adds in the convolutions (15.47 with the three fully
    connected layers of the classifier, the figure usually quoted)."""
    convs = [l for l in flops.layers_of(CONFIG, 224, 224, 0)
             if l.name.startswith("conv")]
    assert len(convs) == 13
    by_hand = 224 * 224 * 9 * (
        3 * 64 + 64 * 64
        + (64 * 128 + 128 * 128) / 4
        + (128 * 256 + 2 * 256 * 256) / 16
        + (256 * 512 + 2 * 512 * 512) / 64
        + 3 * 512 * 512 / 256)
    assert sum(l.flops for l in convs) == 2 * by_hand
    assert by_hand == pytest.approx(15.35e9, rel=2e-3)
    frozen = {l.name for l in convs if not l.trains}
    assert frozen == {"conv1_1", "conv1_2", "conv2_1", "conv2_2"}


def test_train_flops_of_one_image():
    """Forward 423 GFLOP (trunk 381, RPN 23, fc6 + fc7 + outputs 31 on 128
    rois); trained: the fixed 117 once, conv3_1 twice, everything else
    three times = 1013 GFLOP, a little under the C4 flagship's 1098."""
    layers = flops.layers_of(CONFIG, H, W, ROIS)
    fixed = sum(l.flops for l in layers if not l.trains)
    first = _layer("conv3_1").flops
    forward = flops.forward_flops(CONFIG, H, W, ROIS)
    assert forward == pytest.approx(423.17e9, rel=1e-4)
    want = fixed + 2 * first + 3 * (forward - fixed - first)
    assert flops.train_flops(CONFIG, H, W, ROIS) == want
    assert want == pytest.approx(1012.7e9, rel=1e-4)


def test_a_stride_the_blocks_do_not_give_is_refused():
    bad = dict(CONFIG, model=dict(CONFIG["model"], feat_stride=32))
    with pytest.raises(ValueError, match="stride"):
        flops.layers_of(bad, H, W, ROIS)


def test_the_pool_s_bytes_by_hand():
    """bf16, forward and backward: the 38x64x512 map once and the 128
    pooled 7x7x512 rois once, each way; a maximum multiplies nothing."""
    want = 2 * 2 * 512 * (38 * 64 + ROIS * 7 * 7)
    got = flops.roi_align_least_s(CONFIG, H, W, ROIS, 2, True, 197e12, 819e9)
    assert got["bytes"] == want == 17825792
    assert got["flops"] == 0 and got["bound"] == "bytes"
    assert got["least_s"] == pytest.approx(want / 819e9)
    (pool,) = flops.load_graph("vgg").roi_align_pools(
        CONFIG["model"], H, W, ROIS)
    assert (pool.map_h, pool.map_w, pool.channels, pool.rois) == (
        38, 64, 512, ROIS)


def test_the_cell_finds_every_file_and_its_metrics():
    cell = spec.load_cell("vgg_train_b8")
    assert cell.chips == 1 and cell.traffic["kind"] == "train"
    assert cell.config["name"] == "frcnn_vgg16_voc"
    assert cell.config["reduced"] == []
    assert cell.config["model"]["graph"] == "vgg"
    assert "serve_argv" not in cell.config
    assert {m["name"] for m in cell.end_to_end} == {
        "train_img_per_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == (
        VGG_METRICS | _unlisted_train() | {"compile_s"})
    for m in cell.end_to_end + cell.per_layer:
        assert callable(spec.load_reader(m["name"]))
    assert set(cell.limits["limits"]) <= {
        "batch_gap", "fg_anchors_gap", "loss2_gap", "grad1_gap", "dparam_gap"}
    assert cell.limits["limits"]["batch_gap"] == 0.0
    for sub, name in (("graphs", "vgg.py"), ("reference/models", "vgg.py"),
                      ("metrics", "scope_roofline.py")):
        assert os.path.exists(os.path.join(spec.BENCH_DIR, sub, name))


def test_the_mix_is_the_accepted_one_shared_with_both_train_cells():
    mixes = {name: spec.load_cell(name).traffic
             for name in ("c4_train_b8", "fpn_train_b8", "vgg_train_b8")}
    assert mixes["vgg_train_b8"] == mixes["c4_train_b8"] == mixes[
        "fpn_train_b8"]


@pytest.mark.parametrize("other", ["c4_train_b8", "fpn_train_b8",
                                   "c4_serve_closed32"])
def test_no_other_cell_gained_one_of_the_new_metrics(other):
    names = {m["name"] for m in spec.load_cell(other).per_layer}
    assert not names & VGG_METRICS


def _op(name, tf_op, start_us, dur_us):
    """The writer counts in nanoseconds."""
    return (f"%{name} = bf16[8] fusion(...)", start_us * 1000, dur_us * 1000,
            {"@tf_op": tf_op})


_HEAD = "jit(step_fn)/jit(main)/jvp(FasterRCNN)/roi_head/FasterRCNN._roi_features"
#: two steps; under ``roi_pool`` a forward fusion of 100 us and a backward
#: one of 300 us a step, under ``top_head`` 50 us, elsewhere 1000 us
VGG_PLANES = [("/device:TPU:0", [
    ("XLA Modules", 0, [("jit_step_fn(3)", 0, 2_000_000),
                        ("jit_step_fn(3)", 10_000_000, 2_000_000)]),
    ("XLA Ops", 0, [
        op for t in (0, 10_000) for op in (
            _op("fusion.1", "jit(step_fn)/jit(main)/backbone/conv", t, 1_000),
            _op("select_reduce_fusion.2",
                f"{_HEAD}/roi_pool/while/body/checkpoint/reduce_max",
                t + 1_000, 100),
            _op("fusion.3", f"{_HEAD}/top_head/fc6/dot_general", t + 1_100, 50),
            _op("fusion.4",
                "jit(step_fn)/jit(main)/transpose(jvp(FasterRCNN))/roi_head/"
                "FasterRCNN._roi_features/roi_pool/while/body/select_n",
                t + 1_200, 300),
        )]),
])]
#: the parent of this PR: the same pooling under the name ``roi_align``
PARENT_PLANES = [(name, [
    (line, ts, [(e[0], e[1], e[2],
                 {"@tf_op": e[3]["@tf_op"].replace("roi_pool", "roi_align")})
                if len(e) > 3 else e for e in events])
    for line, ts, events in lines]) for name, lines in VGG_PLANES]


def _ctx(tmp_path, planes, traced=True):
    import xplane_stats_writer as xw

    d = tmp_path / "trace" / "plugins" / "profile" / "run1"
    d.mkdir(parents=True, exist_ok=True)
    xw.write(str(d / "host.xplane.pb"), planes)
    return {"cell": spec.load_cell("vgg_train_b8"),
            "run": {"kind": "train", "trace_dir": str(tmp_path / "trace")},
            "device": {"kind": "TPU v5 lite", "count": 1},
            "trace": object() if traced else None}


def test_the_three_new_metrics_to_the_digit(tmp_path):
    """0.4 ms a step under ``roi_pool``, 0.05 under ``top_head``; the
    least time of 8 images' pools is 8 x 17825792 B / 819 GB/s."""
    got = spec.read_metrics(sorted(VGG_METRICS), _ctx(tmp_path, VGG_PLANES))
    assert got == {
        "roi_pool_device_ms.train": pytest.approx(0.4),
        "top_head_device_ms.train": pytest.approx(0.05),
        "roi_pool_roofline.vgg_train": pytest.approx(
            100 * 8 * 17825792 / 819e9 / 0.4e-3),
    }


def test_the_readers_find_nothing_where_there_is_nothing_to_read(tmp_path):
    """A run without a trace, and the parent of this PR (the pooling under
    the scope ``roi_align``): the two ``roi_pool`` metrics are left out,
    never a raise; flax's ``top_head`` the parent has too."""
    untraced = _ctx(tmp_path, VGG_PLANES, traced=False)
    assert spec.read_metrics(sorted(VGG_METRICS), untraced) == {}
    parent = spec.read_metrics(sorted(VGG_METRICS),
                               _ctx(tmp_path, PARENT_PLANES))
    assert set(parent) == {"top_head_device_ms.train"}
