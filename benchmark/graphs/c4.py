"""Faster R-CNN on a ResNet C4 backbone: what ``harness/flops.py`` counts
for ``"graph": "c4"``, every size read from the configuration's ``model``."""

from __future__ import annotations

from typing import Any, Dict, List

from harness.flops import Layer, Pool, bottleneck_stage, conv_flops, half


def _stride_map(model: Dict[str, Any], h: int, w: int):
    """Extent of the map the RPN and the pool read (``feat_stride``)."""
    stride = int(model["feat_stride"])
    while stride > 1:
        h, w, stride = half(h), half(w), stride // 2
    return h, w


def layers(model: Dict[str, Any], h: int, w: int, rois: int) -> List[Layer]:
    """One h×w image with ``rois`` rois through the second stage: backbone
    conv0..stage3, RPN head, stage4 per roi on the pooled map, the two
    output layers."""
    units = [int(u) for u in model["units"]]
    filters = [int(f) for f in model["stage_filters"]]
    frozen = set(model["fixed_params"])
    out: List[Layer] = []
    h1, w1 = half(h), half(w)
    out.append(Layer("conv0", conv_flops(h1, w1, 7, 3, filters[0]),
                     "conv0" not in frozen, False))
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
    if (hh, ww, c) != (*_stride_map(model, h, w), int(model["c4_channels"])):
        raise ValueError(
            f"the stages end at {(hh, ww, c)}, the configuration states "
            f"stride {model['feat_stride']} and {model['c4_channels']} channels")
    a, r = int(model["num_anchors"]), int(model["rpn_channels"])
    out.append(Layer("rpn_conv", conv_flops(hh, ww, 3, c, r), True, True))
    out.append(Layer("rpn_cls_score", conv_flops(hh, ww, 1, r, 2 * a),
                     True, True))
    out.append(Layer("rpn_bbox_pred", conv_flops(hh, ww, 1, r, 4 * a),
                     True, True))
    ph, pw = model["pooled_size"]
    ls, _h, _w, c5 = bottleneck_stage("stage4", ph, pw, c, filters[3],
                                      units[3], 2, True, True)
    out += [l._replace(flops=l.flops * rois) for l in ls]
    k = int(model["num_classes"])
    out.append(Layer("cls_score", 2.0 * c5 * k * rois, True, True))
    out.append(Layer("bbox_pred", 2.0 * c5 * 4 * k * rois, True, True))
    return out


def roi_align_pools(model: Dict[str, Any], h: int, w: int,
                    rois: int) -> List[Pool]:
    """One pool: every roi reads the stride-``feat_stride`` map."""
    fh, fw = _stride_map(model, h, w)
    ph, pw = model["pooled_size"]
    return [Pool(fh, fw, int(model["c4_channels"]), rois, int(ph), int(pw),
                 int(model["roi_sample_ratio"]))]
