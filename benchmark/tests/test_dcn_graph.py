"""``graphs/dcn.py`` and ``metrics/deform_roofline.py`` against hand
counts, the cell ``dcn_train_b8`` as ``spec.load_cell`` assembles it from
files found by name, and the configuration's ``model`` against the
program's registry entry and the reference's constants."""

import importlib.util
import json
import os

import pytest

from harness import flops, spec

CONFIG = json.load(open(os.path.join(
    spec.BENCH_DIR, "configs", "frcnn_r101_dcn_voc.json")))
H, W, ROIS = 608, 1024, 128
FH, FW = 38, 64                     # the stride-16 map of a 608x1024 image
#: per-layer metrics this configuration's cell names itself
DCN_METRICS = {
    "deform_conv_device_ms.train", "deform_roi_pool_device_ms.train",
    "deform_conv_roofline.dcn_train", "deform_roi_pool_roofline.dcn_train",
}


def _unlisted_train():
    """Per-layer metrics without a ``workloads`` list that move
    ``train_img_per_s``: they attach to every train cell by themselves."""
    return {m["name"] for m in spec.load_benchmark()["per_layer"]
            if "workloads" not in m and m["moves"] == "train_img_per_s"}


def _layer(name):
    (found,) = [l for l in flops.layers_of(CONFIG, H, W, ROIS)
                if l.name == name]
    return found


@pytest.mark.parametrize("name, want, trains, needs_dx", [
    # conv5 on the whole map at stride 1: 2432 positions
    ("stage4/unit1/conv1", 2 * FH * FW * 1024 * 512, True, True),
    ("stage4/unit1/conv2", 2 * FH * FW * 9 * 512 * 512, True, True),
    ("stage4/unit1/sc", 2 * FH * FW * 1024 * 2048, True, True),
    ("stage4/unit3/conv2_offset", 2 * FH * FW * 9 * 512 * 72, True, True),
    ("conv_new_1", 2 * FH * FW * 2048 * 256, True, True),
    # the RPN reads conv4
    ("rpn_conv", 2 * FH * FW * 9 * 1024 * 512, True, True),
    ("roi_offset", 2 * 12544 * 98 * ROIS, True, True),
    ("fc_new_1", 2 * 12544 * 1024 * ROIS, True, True),
    ("fc_new_2", 2 * 1024 * 1024 * ROIS, True, True),
    ("bbox_pred", 2 * 1024 * 84 * ROIS, True, True),
])
def test_layers_of_one_608x1024_image_by_hand(name, want, trains, needs_dx):
    layer = _layer(name)
    assert layer.flops == want
    assert (layer.trains, layer.needs_dx) == (trains, needs_dx)


def test_conv1_to_conv4_are_the_c4_detector_s():
    """The trunk is ``frcnn_r101_c4_voc``'s, layer for layer."""
    c4 = json.load(open(os.path.join(
        spec.BENCH_DIR, "configs", "frcnn_r101_c4_voc.json")))
    trunk = lambda cfg: [l for l in flops.layers_of(cfg, H, W, ROIS)  # noqa: E731
                         if l.name.split("/")[0] in (
                             "conv0", "stage1", "stage2", "stage3")]
    assert trunk(CONFIG) == trunk(c4) and len(trunk(CONFIG)) == 1 + 3 * (
        3 + 4 + 23) + 3


def test_forward_and_train_flops_of_one_image():
    """conv5 14.94 M multiply-adds a position (unit 1: 6.03 M, units 2
    and 3: 4.46 M), the offsets 1.0 M, ``conv_new_1`` 0.52 M, the RPN
    4.75 M, on 2432 positions; the head 15.23 M a roi on 128; with the
    trunk that is 276.7 GFLOP forward and 787.9 trained (conv0 and stage1
    once, stage2's first 1x1 and shortcut twice, the rest three times),
    where C4 trains 1098."""
    per_position = (6029312 + 2 * 4456448) + 3 * 9 * 512 * 72 + (
        2048 * 256) + (9 * 1024 * 512 + 512 * 54)
    head = 12544 * 98 + 12544 * 1024 + 1024 * 1024 + 1024 * 105
    trunk = sum(l.flops for l in flops.layers_of(CONFIG, H, W, ROIS)
                if l.name.split("/")[0] in ("conv0", "stage1", "stage2",
                                             "stage3"))
    forward = flops.forward_flops(CONFIG, H, W, ROIS)
    assert forward == trunk + 2 * (per_position * FH * FW + head * ROIS)
    assert forward == pytest.approx(276.70e9, rel=1e-4)
    assert flops.train_flops(CONFIG, H, W, ROIS) == pytest.approx(
        787.90e9, rel=1e-4)


def test_the_two_pools_by_hand():
    """bf16, forward and backward, each pass: the 38x64x256 map once and
    the 128 pooled 7x7x256 rois once, each way; 16 bilinear samples of 4
    corners a pooled value."""
    pools = flops.load_graph("dcn").roi_align_pools(CONFIG["model"], H, W, ROIS)
    assert len(pools) == 2
    assert set(pools) == {flops.Pool(FH, FW, 256, ROIS, 7, 7, 4)}
    got = flops.roi_align_least_s(CONFIG, H, W, ROIS, 2, True, 197e12, 819e9)
    assert got["bytes"] == 2 * 2 * 2 * 256 * (FH * FW + ROIS * 49)
    assert got["flops"] == 2 * 2 * 2 * 4 * 16 * ROIS * 49 * 256
    assert got["bound"] == "bytes"


def _reader_module():
    path = os.path.join(spec.BENCH_DIR, "metrics", "deform_roofline.py")
    s = importlib.util.spec_from_file_location("bench_deform_roofline", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def test_the_deformable_layers_least_time_by_hand():
    """8 images, three layers: the 3x3 product and its offset convolution
    forward and both ways back at the bf16 peak (4.78 ms), against their
    maps, offsets, outputs and kernels both ways (0.36 ms): products."""
    layers = flops.load_graph("dcn").deform_convs(CONFIG["model"], H, W)
    assert layers == [(FH, FW, 512, 512, 72)] * 3
    got = _reader_module().least_s(CONFIG, H, W, 8, 2, 197e12, 819e9)
    ops = 3 * 3 * 2.0 * 8 * FH * FW * 9 * 512 * (512 + 72)
    nbytes = 3 * (2 * 2 * 8 * FH * FW * (512 + 72 + 512)
                  + 2 * 2 * 9 * 512 * (512 + 72))
    assert (got["flops"], got["bytes"], got["bound"]) == (ops, nbytes, "flops")
    assert got["least_s"] == pytest.approx(ops / 197e12)
    assert got["least_s"] == pytest.approx(4.784e-3, rel=1e-3)


def test_the_cell_finds_every_file_and_its_metrics():
    cell = spec.load_cell("dcn_train_b8")
    assert cell.chips == 1 and cell.traffic["kind"] == "train"
    assert cell.config["name"] == "frcnn_r101_dcn_voc"
    assert cell.config["reduced"] == []
    assert cell.config["model"]["graph"] == "dcn"
    assert "serve_argv" not in cell.config
    assert {m["name"] for m in cell.end_to_end} == {
        "train_img_per_s", "setup_s"}
    # the 2-fc head runs under flax's ``top_head`` as VGG's fc6/fc7 do
    assert {m["name"] for m in cell.per_layer} == (
        DCN_METRICS | _unlisted_train()
        | {"compile_s", "top_head_device_ms.train"})
    for m in cell.end_to_end + cell.per_layer:
        assert callable(spec.load_reader(m["name"]))
    assert set(cell.limits["limits"]) <= {
        "batch_gap", "fg_anchors_gap", "loss2_gap", "grad1_gap", "dparam_gap"}
    assert cell.limits["limits"]["batch_gap"] == 0.0
    for sub, name in (("graphs", "dcn.py"), ("reference/models", "dcn.py"),
                      ("metrics", "deform_roofline.py")):
        assert os.path.exists(os.path.join(spec.BENCH_DIR, sub, name))


def test_the_mix_is_the_accepted_one():
    assert spec.load_cell("dcn_train_b8").traffic == spec.load_cell(
        "c4_train_b8").traffic


@pytest.mark.parametrize("other", ["c4_train_b8", "fpn_train_b8",
                                   "vgg_train_b8", "c4_serve_closed32"])
def test_no_other_cell_gained_one_of_the_new_metrics(other):
    names = {m["name"] for m in spec.load_cell(other).per_layer}
    assert not names & DCN_METRICS


def test_the_configuration_the_program_and_the_reference_agree():
    """``"network": "resnet"`` for the reference's frozen registry; the
    program by ``train_argv``; the DCN settings as the configuration's
    ``model`` states them in the program's registry entry and modules and
    in the reference's constants."""
    from mx_rcnn_tpu.config import NETWORKS
    from mx_rcnn_tpu.models import resnet
    from mx_rcnn_tpu.ops import deform_roi_pool

    m = CONFIG["model"]
    assert CONFIG["network"] == "resnet"
    assert CONFIG["train_argv"] == [
        "--network", "resnet_dcn", "--dataset", "PascalVOC"]
    net = NETWORKS["resnet_dcn"]
    assert (net.depth, net.ROI_MODE, list(net.POOLED_SIZE),
            net.ROI_SAMPLE_RATIO, list(net.FIXED_PARAMS),
            net.NUM_ANCHORS, net.RPN_FEAT_STRIDE) == (
        m["depth"], m["roi_mode"], m["pooled_size"], m["sample_per_part"],
        m["fixed_params"], m["num_anchors"], m["feat_stride"])
    assert (resnet.DCN_DILATION, resnet.DCN_GROUPS, resnet.DCN_CHANNELS,
            deform_roi_pool.TRANS_STD) == (
        m["conv5_dilation"], m["deformable_groups"], m["conv_new_channels"],
        m["trans_std"])
    assert m["offset_channels"] == 2 * 9 * m["deformable_groups"]
    path = os.path.join(spec.BENCH_DIR, "reference", "models", "dcn.py")
    s = importlib.util.spec_from_file_location("reference.models.dcn", path)
    ref = importlib.util.module_from_spec(s)
    s.loader.exec_module(ref)
    assert (ref.DEPTH, ref.CONV5_UNITS, ref.CONV5_FILTERS, ref.DILATION,
            ref.GROUPS, ref.CHANNELS, list(ref.POOLED), ref.SAMPLE_PER_PART,
            ref.TRANS_STD, ref.HEAD_WIDTH) == (
        m["depth"], m["units"][3], m["stage_filters"][3],
        m["conv5_dilation"], m["deformable_groups"], m["conv_new_channels"],
        m["pooled_size"], m["sample_per_part"], m["trans_std"],
        m["head_channels"])
    from mx_rcnn_tpu.models import faster_rcnn
    assert (ref.OFFSET_INIT, ref.ROI_OFFSET_INIT) == (
        resnet.DCN_OFFSET_INIT, faster_rcnn.ROI_OFFSET_INIT)


def _op(name, tf_op, start_us, dur_us):
    """The writer counts in nanoseconds."""
    return (f"%{name} = bf16[8] fusion(...)", start_us * 1000, dur_us * 1000,
            {"@tf_op": tf_op})


_UNIT = "jit(step_fn)/jit(main)/jvp(FasterRCNN)/backbone/stage4/unit2"
_HEAD = "jit(step_fn)/jit(main)/jvp(FasterRCNN)/roi_head/FasterRCNN._roi_features"
#: two steps; under ``deform_conv`` a forward fusion of 3000 us and a
#: backward one of 7000 us a step, under ``deform_roi_pool`` 400 + 600 us,
#: elsewhere 1000 us
DCN_PLANES = [("/device:TPU:0", [
    ("XLA Modules", 0, [("jit_step_fn(3)", 0, 20_000_000),
                        ("jit_step_fn(3)", 30_000_000, 20_000_000)]),
    ("XLA Ops", 0, [
        op for t in (0, 30_000) for op in (
            _op("fusion.1", "jit(step_fn)/jit(main)/backbone/stage3/conv", t,
                1_000),
            _op("gather_fusion.2", f"{_UNIT}/deform_conv/while/body/gather",
                t + 1_000, 3_000),
            _op("fusion.3", f"{_HEAD}/deform_roi_pool/while/body/gather",
                t + 4_000, 400),
            _op("scatter.4",
                "jit(step_fn)/jit(main)/transpose(jvp(FasterRCNN))/roi_head/"
                "FasterRCNN._roi_features/deform_roi_pool/while/body/scatter",
                t + 4_400, 600),
            _op("scatter.5",
                "jit(step_fn)/jit(main)/transpose(jvp(FasterRCNN))/backbone/"
                "stage4/unit2/deform_conv/while/body/scatter-add",
                t + 5_000, 7_000),
        )]),
])]
#: the parent of this PR: no such scopes (the cell's configuration is not
#: one it can run; a trace of another graph)
PARENT_PLANES = [(name, [
    (line, ts, [(e[0], e[1], e[2],
                 {"@tf_op": e[3]["@tf_op"].replace("deform_", "")})
                if len(e) > 3 else e for e in events])
    for line, ts, events in lines]) for name, lines in DCN_PLANES]


def _ctx(tmp_path, planes, traced=True):
    import xplane_stats_writer as xw

    d = tmp_path / "trace" / "plugins" / "profile" / "run1"
    d.mkdir(parents=True, exist_ok=True)
    xw.write(str(d / "host.xplane.pb"), planes)
    return {"cell": spec.load_cell("dcn_train_b8"),
            "run": {"kind": "train", "trace_dir": str(tmp_path / "trace")},
            "device": {"kind": "TPU v5 lite", "count": 1},
            "trace": object() if traced else None}


def test_the_four_new_metrics_to_the_digit(tmp_path):
    """10 ms a step under ``deform_conv``, 1 under ``deform_roi_pool``;
    the least times are 4.784 ms (the layers) and 8 × 21.77 us (the two
    pools) a step."""
    got = spec.read_metrics(sorted(DCN_METRICS), _ctx(tmp_path, DCN_PLANES))
    layers = _reader_module().least_s(CONFIG, H, W, 8, 2, 197e12, 819e9)
    pools = flops.roi_align_least_s(CONFIG, H, W, ROIS, 2, True, 197e12, 819e9)
    assert got == {
        "deform_conv_device_ms.train": pytest.approx(10.0),
        "deform_roi_pool_device_ms.train": pytest.approx(1.0),
        "deform_conv_roofline.dcn_train": pytest.approx(
            100 * layers["least_s"] / 10e-3),
        "deform_roi_pool_roofline.dcn_train": pytest.approx(
            100 * 8 * pools["least_s"] / 1e-3),
    }


def test_the_readers_find_nothing_where_there_is_nothing_to_read(tmp_path):
    """A run without a trace, and a trace without the scopes: every new
    metric is left out, never a raise."""
    untraced = _ctx(tmp_path, DCN_PLANES, traced=False)
    assert spec.read_metrics(sorted(DCN_METRICS), untraced) == {}
    assert spec.read_metrics(sorted(DCN_METRICS),
                             _ctx(tmp_path, PARENT_PLANES)) == {}
