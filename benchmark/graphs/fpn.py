"""Faster R-CNN on a ResNet feature pyramid (Lin et al., arXiv:1612.03144):
what ``harness/flops.py`` counts for ``"graph": "fpn"``, every size read
from the configuration's ``model``."""

from __future__ import annotations

from typing import Any, Dict, List

from harness.flops import Layer, Pool, bottleneck_stage, conv_flops, half

#: the levels whose maps the second stage pools (P6 feeds the RPN only)
POOLED_LEVELS = 4


def _trunk(model: Dict[str, Any], h: int, w: int):
    """conv0 and the four stages, all convolutional (C5 is part of the
    pyramid) → (layers, [(h, w, channels) of C2..C5])."""
    units = [int(u) for u in model["units"]]
    filters = [int(f) for f in model["stage_filters"]]
    frozen = set(model["fixed_params"])
    h1, w1 = half(h), half(w)
    out = [Layer("conv0", conv_flops(h1, w1, 7, 3, filters[0]),
                 "conv0" not in frozen, False)]
    hh, ww, c = half(h1), half(w1), filters[0]   # 3x3 max pool, stride 2
    below_trained = False  # does a trained layer sit below (needs dx)?
    maps = []
    for i, stride in enumerate((1, 2, 2, 2)):
        name = f"stage{i + 1}"
        trains = name not in frozen
        ls, hh, ww, c = bottleneck_stage(
            name, hh, ww, c, filters[i], units[i], stride, trains,
            below_trained)
        out += ls
        below_trained = below_trained or trains
        maps.append((hh, ww, c, trains))
    return out, maps


def _pyramid(model: Dict[str, Any], h: int, w: int):
    """→ (trunk layers, C2..C5 as (h, w, channels, trains), extents of
    P2..P6), checked against the strides the configuration states."""
    layers_, maps = _trunk(model, h, w)
    extents = [(mh, mw) for mh, mw, _c, _t in maps]
    extents.append((half(extents[-1][0]), half(extents[-1][1])))   # P6
    want, hh, ww, s = [], h, w, 1
    for stride in (int(v) for v in model["strides"]):
        while s < stride:
            hh, ww, s = half(hh), half(ww), s * 2
        want.append((hh, ww))
    if extents != want:
        raise ValueError(
            f"the pyramid's maps are {extents}, the configuration's strides "
            f"{model['strides']} say {want}")
    return layers_, maps, extents


def layers(model: Dict[str, Any], h: int, w: int, rois: int) -> List[Layer]:
    """One h×w image with ``rois`` rois through the second stage: the
    trunk conv0..stage4, the neck (a 1×1 lateral and a 3×3 output conv a
    level), the shared RPN head on P2..P6, then ``fc1``, ``fc2`` and the
    two output layers per roi."""
    out, maps, extents = _pyramid(model, h, w)
    f = int(model["fpn_channels"])
    for lv, (mh, mw, c, stage_trains) in enumerate(maps, 2):
        # a lateral's input gradient is needed where the stage under it
        # trains: C2 is stage1's output, below the frozen prefix
        out.append(Layer(f"neck/lateral{lv}", conv_flops(mh, mw, 1, c, f),
                         True, stage_trains))
        out.append(Layer(f"neck/post{lv}", conv_flops(mh, mw, 3, f, f),
                         True, True))
    a, r = int(model["num_anchors"]), int(model["rpn_channels"])
    for lv, (mh, mw) in enumerate(extents, 2):
        out.append(Layer(f"rpn/p{lv}/rpn_conv", conv_flops(mh, mw, 3, f, r),
                         True, True))
        out.append(Layer(f"rpn/p{lv}/rpn_cls_score",
                         conv_flops(mh, mw, 1, r, 2 * a), True, True))
        out.append(Layer(f"rpn/p{lv}/rpn_bbox_pred",
                         conv_flops(mh, mw, 1, r, 4 * a), True, True))
    ph, pw = model["pooled_size"]
    width, k = int(model["head_channels"]), int(model["num_classes"])
    for name, cin, cout in (("fc1", int(ph) * int(pw) * f, width),
                            ("fc2", width, width),
                            ("cls_score", width, k),
                            ("bbox_pred", width, 4 * k)):
        out.append(Layer(name, 2.0 * cin * cout * rois, True, True))
    return out


def roi_align_pools(model: Dict[str, Any], h: int, w: int,
                    rois: int) -> List[Pool]:
    """The least any implementation needs: the map of each of P2..P5 read
    once, and the ``rois`` pooled outputs written ONCE - every roi belongs
    to one level (eq. 1).  Which level is the data's to say and all four
    maps have the same channels, so the outputs are booked on the first
    pool and the other three write nothing: the sum is the same however
    the rois fall, and stays the same when the program stops pooling
    every roi on every level."""
    _layers, _maps, extents = _pyramid(model, h, w)
    ph, pw = (int(v) for v in model["pooled_size"])
    f, s = int(model["fpn_channels"]), int(model["roi_sample_ratio"])
    return [
        Pool(mh, mw, f, rois if i == 0 else 0, ph, pw, s)
        for i, (mh, mw) in enumerate(extents[:POOLED_LEVELS])
    ]
