"""Faster R-CNN on VGG-16 (configuration D, arXiv:1409.1556): what
``harness/flops.py`` counts for ``"graph": "vgg"``, every size read from
the configuration's ``model``."""

from __future__ import annotations

from typing import Any, Dict, List

from harness.flops import Layer, Pool, conv_flops


def _trunk(model: Dict[str, Any], h: int, w: int):
    """The thirteen 3×3 convolutions, a 2×2 max pool (floor) after every
    block but the last → (layers, (h, w, channels) of the last map).  A
    block named in ``fixed_params`` trains nothing, and no gradient is
    taken into anything at or below the last such block."""
    frozen = set(model["fixed_params"])
    blocks = model["blocks"]
    out: List[Layer] = []
    cin, below_trained = 3, False
    for b, (n, ch) in enumerate(blocks, start=1):
        trains = f"conv{b}" not in frozen
        for i in range(1, n + 1):
            out.append(Layer(f"conv{b}_{i}", conv_flops(h, w, 3, cin, int(ch)),
                             trains, trains and below_trained))
            cin, below_trained = int(ch), below_trained or trains
        if b < len(blocks):
            h, w = h // 2, w // 2
    stride = 2 ** (len(blocks) - 1)
    if stride != int(model["feat_stride"]):
        raise ValueError(
            f"{len(blocks)} blocks end at stride {stride}, the configuration "
            f"states {model['feat_stride']}")
    return out, (h, w, cin)


def layers(model: Dict[str, Any], h: int, w: int, rois: int) -> List[Layer]:
    """One h×w image with ``rois`` rois through the second stage: the
    trunk, the RPN head on its map, then ``fc6``, ``fc7`` and the two
    output layers per roi.  Max pools, ROI pooling, ReLU and dropout count
    nothing."""
    out, (fh, fw, c) = _trunk(model, h, w)
    a, r = int(model["num_anchors"]), int(model["rpn_channels"])
    out.append(Layer("rpn_conv", conv_flops(fh, fw, 3, c, r), True, True))
    out.append(Layer("rpn_cls_score", conv_flops(fh, fw, 1, r, 2 * a),
                     True, True))
    out.append(Layer("rpn_bbox_pred", conv_flops(fh, fw, 1, r, 4 * a),
                     True, True))
    ph, pw = (int(v) for v in model["pooled_size"])
    width, k = int(model["head_channels"]), int(model["num_classes"])
    for name, cin, cout in (("fc6", ph * pw * c, width),
                            ("fc7", width, width),
                            ("cls_score", width, k),
                            ("bbox_pred", width, 4 * k)):
        out.append(Layer(name, 2.0 * cin * cout * rois, True, True))
    return out


def roi_align_pools(model: Dict[str, Any], h: int, w: int,
                    rois: int) -> List[Pool]:
    """One pool: every roi is max-pooled from the stride-``feat_stride``
    map.  (The name is the one ``harness/flops.py`` calls.)  A maximum
    takes no samples and no multiply-adds: ``sample_ratio`` 0, so the
    least time is the bytes' alone."""
    _layers, (fh, fw, c) = _trunk(model, h, w)
    ph, pw = (int(v) for v in model["pooled_size"])
    return [Pool(fh, fw, c, rois, ph, pw, 0)]
