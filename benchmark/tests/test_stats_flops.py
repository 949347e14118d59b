"""Arithmetic of rates and percentiles, and the FLOP count against a hand
count and against itself."""

import json
import os

import pytest

from harness import flops, spec, stats

CONFIG = json.load(open(os.path.join(
    spec.BENCH_DIR, "configs", "frcnn_r101_c4_voc.json")))


def test_window_rate_is_all_the_work_over_all_the_time():
    assert stats.window_rate(800, 10.0, 20.0) == 80.0
    # the same work with a stall of 2.5 s inside the window reads lower
    assert stats.window_rate(800, 10.0, 22.5) == 64.0
    with pytest.raises(ValueError):
        stats.window_rate(1, 5.0, 5.0)


@pytest.mark.parametrize("q, want", [(50, 5), (95, 10), (100, 10), (10, 1)])
def test_percentile_is_nearest_rank_over_every_value(q, want):
    assert stats.percentile(list(range(10, 0, -1)), q) == want


def test_percentile_sees_one_slow_request_in_twenty():
    lat = [10.0] * 19 + [900.0]
    assert stats.percentile(lat, 95) == 10.0
    assert stats.percentile(lat + [900.0], 95) == 900.0


def test_bottleneck_stage_by_hand():
    """One bottleneck unit, 8x8 map, 16 -> filters 4 (out 16... x4), stride 1:
    conv1 1x1 16->4, conv2 3x3 4->4, conv3 1x1 4->16, shortcut 1x1 16->16."""
    layers, h, w, c = flops.bottleneck_stage("s", 8, 8, 16, 4, 1, 1, True, False)
    want = [2 * 64 * 1 * 16 * 4, 2 * 64 * 9 * 4 * 4, 2 * 64 * 1 * 4 * 16,
            2 * 64 * 1 * 16 * 16]
    assert [l.flops for l in layers] == want
    assert (h, w, c) == (8, 8, 16)
    # the first trained layer needs no input gradient; the shortcut neither
    assert [l.needs_dx for l in layers] == [False, True, True, False]


def test_three_layer_toy_train_count_by_hand():
    """conv1 (frozen), conv2 (trains, first trained: no dx), conv3 (trains):
    forward 3 terms, backward dW for 2 and dx for 1."""
    toy = [flops.Layer("a", 10.0, False, False),
           flops.Layer("b", 20.0, True, False),
           flops.Layer("c", 30.0, True, True)]
    train = sum(l.flops * (1 + l.trains + l.needs_dx) for l in toy)
    assert train == 10 + 20 * 2 + 30 * 3


def test_stride_two_halves_by_ceiling():
    assert [flops.half(n) for n in (608, 1024, 75, 38)] == [304, 512, 38, 19]


def test_c4_forward_is_linear_in_rois_and_area():
    f1 = flops.forward_flops(CONFIG, 608, 1024, 128)
    f2 = flops.forward_flops(CONFIG, 608, 1024, 256)
    f0 = flops.forward_flops(CONFIG, 608, 1024, 0)
    assert f2 - f1 == pytest.approx(f1 - f0)
    assert flops.forward_flops(CONFIG, 1024, 608, 128) == pytest.approx(f1)
    # ResNet-101 C4 at 608x1024 with 128 rois: some hundreds of GFLOPs
    assert 3e11 < f1 < 9e11


def test_c4_train_counts_no_backward_below_the_frozen_prefix():
    fwd = flops.forward_flops(CONFIG, 608, 1024, 128)
    train = flops.train_flops(CONFIG, 608, 1024, 128)
    layers = flops.layers_of(CONFIG, 608, 1024, 128)
    frozen = sum(l.flops for l in layers if not l.trains)
    assert {l.name.split("/")[0] for l in layers if not l.trains} == {
        "conv0", "stage1"}
    first = [l for l in layers if l.trains and not l.needs_dx]
    assert {l.name for l in first} == {"stage2/unit1/conv1", "stage2/unit1/sc"}
    want = frozen + 3 * (fwd - frozen) - sum(l.flops for l in first)
    assert train == pytest.approx(want)
    assert fwd < train < 3 * fwd


def test_per_image_counts_do_not_depend_on_the_batch():
    """The functions count one image; a batch is that many times it."""
    one = flops.train_flops(CONFIG, 608, 1024, 128)
    assert 8 * one == pytest.approx(sum(
        flops.train_flops(CONFIG, 608, 1024, 128) for _ in range(8)))


def test_roi_align_least_time_is_bound_by_bytes_on_a_v5e():
    least = flops.roi_align_least_s(CONFIG, 608, 1024, 128, 2, True,
                                    197e12, 819e9)
    map_bytes = 38 * 64 * 1024 * 2
    roi_bytes = 128 * 14 * 14 * 1024 * 2
    assert least["bytes"] == 2 * (map_bytes + roi_bytes)
    assert least["bound"] == "bytes"
    assert least["least_s"] == pytest.approx(least["bytes"] / 819e9)


def test_the_graph_reads_its_sizes_from_the_configuration():
    """Channels and stride are the configuration's, not the code's: half
    the channels, half the pool's bytes; a stride the stages do not end
    at is refused."""
    import copy

    thin = copy.deepcopy(CONFIG)
    thin["model"]["c4_channels"] = 512
    thin["model"]["stage_filters"] = [64, 128, 128, 512]
    a = flops.roi_align_least_s(CONFIG, 608, 1024, 128, 2, True, 197e12, 819e9)
    b = flops.roi_align_least_s(thin, 608, 1024, 128, 2, True, 197e12, 819e9)
    assert b["bytes"] == a["bytes"] / 2
    assert flops.forward_flops(thin, 608, 1024, 128) < flops.forward_flops(
        CONFIG, 608, 1024, 128)
    wrong = copy.deepcopy(CONFIG)
    wrong["model"]["feat_stride"] = 32
    with pytest.raises(ValueError):
        flops.forward_flops(wrong, 608, 1024, 128)


def test_unknown_graph_and_unknown_device_are_errors():
    from harness.device import peak

    with pytest.raises(KeyError):
        flops.forward_flops({"model": {"graph": "nope"}}, 8, 8, 1)
    with pytest.raises(KeyError):
        peak("TPU v9 imaginary", "flops_bf16")
    assert peak("TPU v5 lite", "flops_bf16") == 197e12
