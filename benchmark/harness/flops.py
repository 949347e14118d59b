"""Operations and bytes the algorithm needs, from the configuration's
shapes alone - never from the program's compiled cost, so the same work
reads the same whatever implements it.

Rules: 2 FLOPs a multiply-add over every convolution and matrix product
of the forward pass.  The backward pass counts the input gradient wherever
one is needed (nothing below the frozen prefix, nothing into the first
trained layer's input) and the weight gradient only for layers that train
(``FIXED_PARAMS``: ``conv0``, ``stage1`` and every BN train nothing).
ROIAlign, NMS, losses and element-wise work count nothing.

What belongs to one graph - its list of layers, the maps its ROIAlign
pools read - sits in ``graphs/<graph>.py``, found by the name the
configuration gives under ``model.graph``; the rules and the arithmetic
shared by every graph are here.  A later PR that brings another graph
adds a file there and edits none.
"""

from __future__ import annotations

import importlib.util
import os
from typing import Any, Dict, List, NamedTuple

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Layer(NamedTuple):
    name: str
    flops: float       # forward, one image (or one roi where per_roi)
    trains: bool       # weight gradient counted
    needs_dx: bool     # input gradient counted


def half(n: int) -> int:
    """Extent after a stride-2 layer with symmetric (k-1)//2 padding."""
    return -(-n // 2)


def conv_flops(h_out: int, w_out: int, k: int, cin: int, cout: int) -> float:
    return 2.0 * h_out * w_out * k * k * cin * cout


def bottleneck_stage(name: str, h: int, w: int, cin: int, filters: int,
                     units: int, stride: int, trains: bool,
                     first_needs_dx: bool):
    """→ (layers, h_out, w_out, c_out).  The stride sits on ``conv1`` and
    the shortcut projection of the first unit."""
    layers: List[Layer] = []
    cout = filters * 4
    for u in range(units):
        s = stride if u == 0 else 1
        ho, wo = (half(h), half(w)) if s == 2 else (h, w)
        dx_in = trains and (first_needs_dx or u > 0)
        pre = f"{name}/unit{u + 1}/"
        layers.append(Layer(pre + "conv1", conv_flops(ho, wo, 1, cin, filters),
                            trains, dx_in))
        layers.append(Layer(pre + "conv2",
                            conv_flops(ho, wo, 3, filters, filters),
                            trains, trains))
        layers.append(Layer(pre + "conv3", conv_flops(ho, wo, 1, filters, cout),
                            trains, trains))
        if u == 0:  # projection shortcut where the shape changes
            layers.append(Layer(pre + "sc", conv_flops(ho, wo, 1, cin, cout),
                                trains, dx_in))
        h, w, cin = ho, wo, cout
    return layers, h, w, cin


class Pool(NamedTuple):
    """One ROIAlign pool of one image: the map it reads and what it writes."""
    map_h: int
    map_w: int
    channels: int
    rois: int
    pooled_h: int
    pooled_w: int
    sample_ratio: int


def load_graph(name: str, bench_dir: str = BENCH_DIR):
    """``graphs/<name>.py`` → module with ``layers(model, h, w, rois)`` and
    ``roi_align_pools(model, h, w, rois)``."""
    path = os.path.join(bench_dir, "graphs", name + ".py")
    if not os.path.exists(path):
        have = sorted(f[:-3] for f in os.listdir(os.path.dirname(path))
                      if f.endswith(".py"))
        raise KeyError(f"no FLOP count for graph {name!r}; have {have}")
    spec = importlib.util.spec_from_file_location(f"bench_graph_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layers_of(config: Dict[str, Any], h: int, w: int, rois: int,
              bench_dir: str = BENCH_DIR) -> List[Layer]:
    model = config["model"]
    return load_graph(model["graph"], bench_dir).layers(model, h, w, rois)


def forward_flops(config: Dict[str, Any], h: int, w: int, rois: int,
                  bench_dir: str = BENCH_DIR) -> float:
    return sum(l.flops for l in layers_of(config, h, w, rois, bench_dir))


def train_flops(config: Dict[str, Any], h: int, w: int, rois: int,
                bench_dir: str = BENCH_DIR) -> float:
    """Forward + backward of one image."""
    return sum(
        l.flops * (1 + int(l.trains) + int(l.needs_dx))
        for l in layers_of(config, h, w, rois, bench_dir)
    )


def roi_align_least_s(config: Dict[str, Any], h: int, w: int, rois: int,
                      itemsize: int, backward: bool, peak_flops: float,
                      peak_bytes_per_s: float,
                      bench_dir: str = BENCH_DIR) -> Dict[str, float]:
    """The least time one image's ROIAlign pools can take: the larger of
    operations over peak FLOP/s and bytes over peak bytes/s.

    Bytes: each pool's map read once and its pooled rois written once;
    the backward pass reads the pooled gradient and writes the map's.
    Operations: every pooled value is the mean of ``s×s`` bilinear samples
    of 4 taps (a multiply-add each).  Which maps are pooled, with their
    channels and stride, is the graph's to say, from the configuration."""
    model = config["model"]
    pools = load_graph(model["graph"], bench_dir).roi_align_pools(
        model, h, w, rois)
    passes = 2 if backward else 1
    nbytes = ops = 0.0
    for p in pools:
        nbytes += passes * itemsize * p.channels * (
            p.map_h * p.map_w + p.rois * p.pooled_h * p.pooled_w)
        ops += passes * 2.0 * 4 * p.sample_ratio ** 2 * (
            p.rois * p.pooled_h * p.pooled_w * p.channels)
    return {
        "bytes": nbytes, "flops": ops,
        "least_s": max(ops / peak_flops, nbytes / peak_bytes_per_s),
        "bound": "bytes" if nbytes / peak_bytes_per_s >= ops / peak_flops
        else "flops",
    }
