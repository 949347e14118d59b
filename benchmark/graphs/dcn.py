"""Faster R-CNN with Deformable ConvNets on ResNet-101 (Dai et al.,
arXiv:1703.06211): what ``harness/flops.py`` counts for ``"graph":
"dcn"``, every size read from the configuration's ``model``."""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple

from harness.flops import Layer, Pool, bottleneck_stage, conv_flops, half


class DeformConv(NamedTuple):
    """One deformable 3×3 of one image: the map it reads and writes at
    stride 1, and the offsets its own convolution writes."""
    map_h: int
    map_w: int
    channels: int
    filters: int
    offset_channels: int


def _trunk(model: Dict[str, Any], h: int, w: int):
    """conv0 and stages 1-3 as in the C4 detector → (layers, (h, w,
    channels) of conv4), checked against ``feat_stride`` and
    ``c4_channels``."""
    units = [int(u) for u in model["units"]]
    filters = [int(f) for f in model["stage_filters"]]
    frozen = set(model["fixed_params"])
    h1, w1 = half(h), half(w)
    out = [Layer("conv0", conv_flops(h1, w1, 7, 3, filters[0]),
                 "conv0" not in frozen, False)]
    hh, ww, c = half(h1), half(w1), filters[0]   # 3x3 max pool, stride 2
    below_trained = False  # does a trained layer sit below (needs dx)?
    for i, stride in enumerate((1, 2, 2)):
        name = f"stage{i + 1}"
        trains = name not in frozen
        ls, hh, ww, c = bottleneck_stage(
            name, hh, ww, c, filters[i], units[i], stride, trains,
            below_trained)
        out += ls
        below_trained = below_trained or trains
    fh, fw, s = h, w, int(model["feat_stride"])
    while s > 1:
        fh, fw, s = half(fh), half(fw), s // 2
    if (hh, ww, c) != (fh, fw, int(model["c4_channels"])):
        raise ValueError(
            f"the stages end at {(hh, ww, c)}, the configuration states "
            f"stride {model['feat_stride']} and {model['c4_channels']} channels")
    return out, (hh, ww, c)


def deform_convs(model: Dict[str, Any], h: int, w: int) -> List[DeformConv]:
    """conv5's deformable layers, one a unit, on the conv4 map (stride
    ``conv5_stride`` 1)."""
    if int(model["conv5_stride"]) != 1:
        raise ValueError("the deformable conv5 runs at stride 1 on conv4's map")
    _layers, (fh, fw, _c) = _trunk(model, h, w)
    f = int(model["stage_filters"][3])
    return [DeformConv(fh, fw, f, f, int(model["offset_channels"]))
            for _ in range(int(model["units"][3]))]


def layers(model: Dict[str, Any], h: int, w: int, rois: int) -> List[Layer]:
    """One h×w image with ``rois`` rois through the second stage: the
    trunk, conv5 on the map (each deformable 3×3 counted as the 3×3
    product it is, its offset convolution beside it), ``conv_new_1``, the
    RPN head on conv4, then per roi the offset fc, ``fc_new_1``,
    ``fc_new_2`` and the two output layers.  The sampling, the pooling,
    ReLU: nothing."""
    out, (fh, fw, c4) = _trunk(model, h, w)
    f, units = int(model["stage_filters"][3]), int(model["units"][3])
    conv5, _h, _w, c5 = bottleneck_stage("stage4", fh, fw, c4, f, units, 1,
                                         True, True)
    if c5 != int(model["c5_channels"]):
        raise ValueError(f"conv5 ends at {c5} channels, the configuration "
                         f"states {model['c5_channels']}")
    out += conv5
    for d in range(units):
        out.append(Layer(f"stage4/unit{d + 1}/conv2_offset",
                         conv_flops(fh, fw, 3, f, int(model["offset_channels"])),
                         True, True))
    cn = int(model["conv_new_channels"])
    out.append(Layer("conv_new_1", conv_flops(fh, fw, 1, c5, cn), True, True))
    a, r = int(model["num_anchors"]), int(model["rpn_channels"])
    out.append(Layer("rpn_conv", conv_flops(fh, fw, 3, c4, r), True, True))
    out.append(Layer("rpn_cls_score", conv_flops(fh, fw, 1, r, 2 * a),
                     True, True))
    out.append(Layer("rpn_bbox_pred", conv_flops(fh, fw, 1, r, 4 * a),
                     True, True))
    ph, pw = (int(v) for v in model["pooled_size"])
    width, k = int(model["head_channels"]), int(model["num_classes"])
    for name, cin, cout in (("roi_offset", ph * pw * cn, 2 * ph * pw),
                            ("fc_new_1", ph * pw * cn, width),
                            ("fc_new_2", width, width),
                            ("cls_score", width, k),
                            ("bbox_pred", width, 4 * k)):
        out.append(Layer(name, 2.0 * cin * cout * rois, True, True))
    return out


def roi_align_pools(model: Dict[str, Any], h: int, w: int,
                    rois: int) -> List[Pool]:
    """Two pools, the passes of the deformable ROI pooling: every roi read
    from ``conv_new_1``'s map twice, ``sample_per_part``² bilinear samples
    a bin each time.  (The name is the one ``harness/flops.py`` calls.)"""
    _layers, (fh, fw, _c) = _trunk(model, h, w)
    ph, pw = (int(v) for v in model["pooled_size"])
    pool = Pool(fh, fw, int(model["conv_new_channels"]), rois, ph, pw,
                int(model["sample_per_part"]))
    return [pool, pool]
