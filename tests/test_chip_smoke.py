"""CPU rehearsal of ``chip_smoke.py``: the same phase functions, the same
checks, at the tiny configurations of tests/test_train_cli.py and
``tools/serve.py --small`` — so a wrong path, argument or counter is found
here and not on chip time.  What only the chip can show (the kernels, the
layout feed, the real widths) is chip_smoke's own job."""

import dataclasses
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

import chip_smoke
from mx_rcnn_tpu.config import generate_config

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny_generate_config(network, dataset):
    cfg = generate_config(network, dataset)
    return cfg.replace(
        SHAPE_BUCKETS=((96, 96),),
        TRAIN=dataclasses.replace(
            cfg.TRAIN, RPN_PRE_NMS_TOP_N=256, RPN_POST_NMS_TOP_N=32,
            BATCH_ROIS=16, RPN_BATCH_SIZE=32,
        ),
        dataset=dataclasses.replace(
            cfg.dataset, SCALES=((96, 96),), MAX_GT_BOXES=8
        ),
    )


@pytest.fixture
def tiny_train_argv(tmp_path, monkeypatch):
    """chip_smoke's train arguments cut to the tiny config: one image per
    virtual device (global batch 8 under conftest's 8 devices), a gentle
    LR (the default diverges the tiny model within steps)."""
    from mx_rcnn_tpu.tools import train_end2end as cli

    monkeypatch.setattr(cli, "generate_config", _tiny_generate_config)
    return [
        "--network", "resnet50", "--dataset", "PascalVOC",
        "--synthetic", "16", "--epochs", "1", "--frequent", "1",
        "--batch_images", "1", "--lr", "0.0005", "--max_steps", "2",
        "--prefix", str(tmp_path / "ckpt"),
    ]


def test_train_phase_applies_every_step(tiny_train_argv):
    state, report = chip_smoke.train_phase(tiny_train_argv)
    assert report["steps"] == report["steps_applied"] == 2
    assert int(state.step) == 2
    assert [step for step, _loss in report["losses"]] == [0, 1]


def test_train_phase_fails_when_the_guard_skips(tiny_train_argv, monkeypatch):
    """A NaN step that the guard absorbs ends ``train_net`` cleanly — the
    smoke must not."""
    monkeypatch.setenv("MX_RCNN_FAULTS", "nan_loss@1")
    with pytest.raises(RuntimeError, match="applied to the optimizer|guard"):
        chip_smoke.train_phase(tiny_train_argv)


def test_kernels_phase_in_interpret_mode():
    """Same inputs, same references, same bounds as on the chip — the
    Pallas interpreter standing in for Mosaic."""
    errs = chip_smoke.kernels_phase(interpret=True)
    assert set(errs) == {
        f"{kernel}_{dtype}_{pass_}"
        for kernel in ("resident", "stream")
        for dtype in ("f32", "bf16") for pass_ in ("fwd", "bwd")
    } | {f"resident_valid_hw_{dtype}_fwd" for dtype in ("f32", "bf16")} | {
        "roi_pool_f32_fwd", "roi_pool_f32_bwd"}
    assert max(v for k, v in errs.items() if "f32" in k) < 1e-5
    # ROI max pooling against the sweep: equal, not close
    assert errs["roi_pool_f32_fwd"] == errs["roi_pool_f32_bwd"] == 0.0


def test_kernels_phase_span_cases_in_interpret_mode():
    """The train-shape cases' second half (the streaming pair with a span,
    as ``pool_levels`` calls it) on a map small enough to interpret."""
    errs = chip_smoke.kernels_phase(
        interpret=True, train_maps=(("stream_tiny", (2, 24, 32, 128), 4),))
    span = {k: v for k, v in errs.items() if k.startswith("stream_tiny_span")}
    assert set(span) == {
        f"stream_tiny_span_{dtype}_{pass_}"
        for dtype in ("f32", "bf16") for pass_ in ("fwd", "bwd")}
    assert max(v for k, v in span.items() if "f32" in k) < 1e-5


def test_pyramid_phases_ask_for_the_cell_s_configuration():
    """The two pyramid phases' argv through the CLI's own parser: the
    network and dataset of ``frcnn_r50_fpn_coco``, batch 8 in bf16 as the
    cell ``fpn_train_b8`` and the family's default batch 1 in f32."""
    from mx_rcnn_tpu.tools import train_end2end as cli

    for argv, batch, dtype, steps in (
            (chip_smoke.TRAIN_FPN_BF16_ARGV, 8, "bfloat16", 6),
            (chip_smoke.TRAIN_FPN_F32_ARGV, 1, "float32", 2)):
        args = cli.parse_args(argv + ["--prefix", "/nowhere"])
        cfg = cli.config_from_args(args)
        assert cfg.network.USE_FPN and not cfg.network.USE_MASK
        assert cfg.network.depth == 50 and cfg.dataset.NUM_CLASSES == 81
        assert cfg.TRAIN.BATCH_IMAGES == batch
        assert cfg.network.COMPUTE_DTYPE == dtype
        assert args.max_steps == steps and args.lr == 1e-05
    with open(os.path.join(REPO_ROOT, "benchmark", "configs",
                           "frcnn_r50_fpn_coco.json")) as f:
        cell_argv = json.load(f)["train_argv"]
    assert chip_smoke.TRAIN_FPN_BF16_ARGV[:4] == cell_argv


def test_vgg_phase_asks_for_the_cell_s_configuration():
    """The VGG phase's argv through the CLI's own parser: the network and
    dataset of ``frcnn_vgg16_voc``, batch 8 in bf16 as the cell
    ``vgg_train_b8``, ROI max pooling, conv1-conv2 fixed."""
    from mx_rcnn_tpu.tools import train_end2end as cli

    args = cli.parse_args(chip_smoke.TRAIN_VGG_BF16_ARGV
                          + ["--prefix", "/nowhere"])
    cfg = cli.config_from_args(args)
    assert cfg.network.name == "vgg" and cfg.network.ROI_MODE == "roi_pool"
    assert cfg.network.FIXED_PARAMS == ("conv1", "conv2")
    assert cfg.dataset.NUM_CLASSES == 21 and cfg.TRAIN.BATCH_IMAGES == 8
    assert cfg.network.COMPUTE_DTYPE == "bfloat16"
    assert args.max_steps == 6 and args.lr == 1e-05
    with open(os.path.join(REPO_ROOT, "benchmark", "configs",
                           "frcnn_vgg16_voc.json")) as f:
        cell_argv = json.load(f)["train_argv"]
    assert chip_smoke.TRAIN_VGG_BF16_ARGV[:4] == cell_argv
    # what the compiled step must hold: the NMS and the pooling's pair
    assert chip_smoke.VGG_STEP_KERNELS == (
        "pallas_nms_mask", "pallas_roi_pool_fwd", "pallas_roi_pool_bwd")


def test_roi_pool_kernel_case_is_the_vgg_cell_s_shape():
    from mx_rcnn_tpu.ops.pallas.roi_pool import fits_vmem

    b, h, w, c = chip_smoke.ROI_POOL_TRAIN_MAP
    assert (b, h * 16, w * 16, c) == (8, 608, 1024, 512)
    for shape in (chip_smoke.ROI_POOL_TRAIN_MAP, chip_smoke.ROI_POOL_TINY_MAP):
        assert fits_vmem(*shape[1:], (7, 7), 4)


def test_dcn_phase_asks_for_the_cell_s_configuration():
    """The Deformable ConvNets phase's argv through the CLI's own parser:
    the program ``frcnn_r101_dcn_voc`` names in its ``train_argv``, batch
    8 in bf16 as the cell ``dcn_train_b8``, the deformable pooling."""
    from mx_rcnn_tpu.tools import train_end2end as cli

    args = cli.parse_args(chip_smoke.TRAIN_DCN_BF16_ARGV
                          + ["--prefix", "/nowhere"])
    cfg = cli.config_from_args(args)
    assert cfg.network.deformable
    assert cfg.network.ROI_MODE == "deform_roi_pool"
    assert cfg.dataset.NUM_CLASSES == 21 and cfg.TRAIN.BATCH_IMAGES == 8
    assert cfg.network.COMPUTE_DTYPE == "bfloat16"
    assert args.max_steps == 6 and args.lr == 1e-05
    with open(os.path.join(REPO_ROOT, "benchmark", "configs",
                           "frcnn_r101_dcn_voc.json")) as f:
        cell_argv = json.load(f)["train_argv"]
    assert chip_smoke.TRAIN_DCN_BF16_ARGV[:4] == cell_argv


def test_dcn_phase_holds_the_deform_conv_pair():
    """The DCN phase fails where its compiled step stops calling the
    deformable convolution's pair (or the NMS)."""
    assert chip_smoke.DCN_STEP_KERNELS == (
        "pallas_nms_mask", "pallas_deform_conv_fwd", "pallas_deform_conv_bwd")


def test_streaming_train_shapes_are_the_pyramid_s_p2_and_p3():
    from mx_rcnn_tpu.ops.pallas.roi_align import fits_vmem

    cases = chip_smoke.STREAM_TRAIN_MAPS
    assert [c[0] for c in cases] == ["stream_p2", "stream_p3"]
    for _tag, (b, h, w, c), stride in cases:
        assert (b, c) == (8, 256)
        assert (h * stride, w * stride) == (608, 1024)
        for esize in (2, 4):  # over the resident budget: these stream
            assert not fits_vmem(h, w, c, (14, 14), esize)


def test_the_compiled_step_s_text_is_kept_once():
    """``train_phase(kernels=...)`` stands between ``train_net`` and the
    step it builds: the first call's shapes are compiled once more and
    the text kept; later calls go straight through."""
    import jax
    import jax.numpy as jnp

    def make_train_step(scale):
        def step(state, batch, rng):
            return state * scale + batch["x"].sum(), {"loss": rng.sum()}

        return jax.jit(step)

    cli = SimpleNamespace(make_train_step=make_train_step)
    found: dict = {}
    saved = chip_smoke._compiled_step_text(cli, found)
    assert saved is make_train_step and cli.make_train_step is not saved
    step = cli.make_train_step(2.0)
    args = (jnp.ones((3,)), {"x": jnp.ones((2, 2))}, jnp.zeros((2,)))
    out, _aux = step(*args)
    assert out.tolist() == [6.0, 6.0, 6.0]
    text = found["text"]
    assert "f32[2,2]" in text and "HloModule" in text
    step(*args)
    assert found["text"] is text


def test_tiny_pyramid_train_phase_counts_rois_by_level(tmp_path, monkeypatch):
    """The pyramid through ``train_phase`` at the tiny size, one image a
    virtual device - the per-chip batch 1 that ``per_image`` serves - and
    the per-level counters in ``report``: every sampled roi on one level."""
    from mx_rcnn_tpu.tools import train_end2end as cli

    monkeypatch.setattr(cli, "generate_config", _tiny_generate_config)
    _state, report = chip_smoke.train_phase([
        "--network", "resnet_fpn", "--dataset", "PascalVOC",
        "--synthetic", "16", "--epochs", "1", "--frequent", "1",
        "--batch_images", "1", "--lr", "1e-05", "--max_steps", "2",
        "--prefix", str(tmp_path / "ckpt"),
    ])
    levels = report["roi_levels"]
    assert set(levels) == {f"num_rois_p{lv}" for lv in (2, 3, 4, 5)}
    # aux is averaged over the devices: 16 rois an image, 2 steps
    assert sum(levels.values()) == pytest.approx(2 * 16)


def test_serve_phase_small_config():
    report = chip_smoke.serve_phase([
        "--small", "--max_batch", "2", "--requests", "8",
        "--concurrency", "4", "--seed", "0",
    ])
    assert report["outcomes"]["ok"] == 8
    assert report["engine"]["compile"]["misses"] == 2  # small ladder


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py")],
        env=env, cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_result_line_is_the_contract():
    fake = (SimpleNamespace(platform="tpu", device_kind="TPU v5 lite"),)
    assert chip_smoke.result_line(fake) == (
        '{"ok": true, "device": {"platform": "tpu", '
        '"kind": "TPU v5 lite", "count": 1}}'
    )
    assert json.loads(chip_smoke.result_line(fake * 4))["device"]["count"] == 4
