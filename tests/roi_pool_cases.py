"""ROI max pooling's oracle and cases, shared by ``test_roi_align.py`` (the
jnp sweep and the benchmark's reference) and ``test_pallas_roi_pool.py``
(the Pallas pair): an independent loop written from MXNet's ``ROIPooling``
(``src/operator/roi_pooling.cc``) in exact integer arithmetic on slices of
the map, and the roi sets every formulation is held to."""

import math

import numpy as np


def c_round(v: float) -> int:
    return int(math.copysign(math.floor(abs(v) + 0.5), v))


def mxnet_roi_pool(feat, rois, pooled, scale, valid_hw=None, cot=None,
                   more=(0, 0)):
    """→ (out (R, ph, pw, C) float64, d feat of ``sum(out * cot)``): round
    the roi to cells (C's ``round``), bin ``p`` spans ``floor(p·bin) ..
    ceil((p+1)·bin)`` from the roi's start, clipped to the map; the
    maximum over the bin, 0 for an empty one; the gradient goes to the
    arg-max cell, the first in row-major order where cells tie.  ``more``:
    rows / columns added at every bin's far edge (the fault of the frozen
    ``benchmark/reference/ops/roi_align.py::roi_pool``)."""
    feat = np.asarray(feat, np.float64)
    hf, wf, c = feat.shape
    if valid_hw is not None:   # cells that carry image content
        hf = min(int(np.ceil(valid_hw[0] * scale)), hf)
        wf = min(int(np.ceil(valid_hw[1] * scale)), wf)
    ph, pw = pooled
    out = np.zeros((len(rois), ph, pw, c))
    grad = np.zeros_like(feat)
    for r, roi in enumerate(np.asarray(rois, np.float64)):
        x1, y1, x2, y2 = (c_round(v * scale) for v in roi)
        rh, rw = max(y2 - y1 + 1, 1), max(x2 - x1 + 1, 1)
        for p in range(ph):
            h0 = min(max(y1 + (p * rh) // ph, 0), hf)
            h1 = min(max(y1 - (-(p + 1) * rh) // ph + more[0], 0), hf)
            for q in range(pw):
                w0 = min(max(x1 + (q * rw) // pw, 0), wf)
                w1 = min(max(x1 - (-(q + 1) * rw) // pw + more[1], 0), wf)
                if h1 <= h0 or w1 <= w0:
                    continue
                cells = feat[h0:h1, w0:w1].reshape(-1, c)
                out[r, p, q] = cells.max(axis=0)
                if cot is not None:
                    best = cells.argmax(axis=0)
                    for ch in range(c):
                        hh, ww = divmod(int(best[ch]), w1 - w0)
                        grad[h0 + hh, w0 + ww, ch] += cot[r, p, q, ch]
    return out, grad


MAP_H, MAP_W = 12, 20        # at 1/16: a 192x320 canvas


def small_map():
    return np.random.RandomState(11).randn(MAP_H, MAP_W, 5).astype(
        np.float32)


def clipped_rois():
    """Rois that the map's border clips or that lie wholly outside: one
    cell in the map's last corner, a thin row, the whole map and more,
    boxes past every border, random boxes that run off the map."""
    rng = np.random.RandomState(11)
    rois = [
        [300.0, 172.0, 310.0, 182.0],    # the last cell: every bin reads it
        [3.0, 180.0, 400.0, 186.0],      # the last row, 20 cells wide
        [-3.0, -5.0, 330.0, 200.0],      # the whole map and a margin
        [-80.0, -50.0, 400.0, 260.0],    # past every border
        [250.0, 150.0, 400.0, 260.0],    # past the bottom-right corner
        [400.0, 300.0, 460.0, 380.0],    # wholly outside: all bins empty
        [100.0, 20.0, 330.0, 250.0],
    ]
    for _ in range(9):
        rois.append([rng.uniform(-40, 280), rng.uniform(-40, 160),
                     rng.uniform(321, 420), rng.uniform(193, 260)])
    return np.asarray(rois, np.float32)


def inner_rois():
    """Rois that end inside the map: one cell, thin, wide, extents that 7
    divides (where every far edge is a whole number), random."""
    rng = np.random.RandomState(12)
    rois = [
        [33.0, 49.0, 35.0, 51.0],        # one cell
        [3.0, 70.0, 300.0, 75.0],        # one row high, 20 cells wide
        [0.0, 0.0, 221.0, 110.0],        # 15 x 8 cells from the corner
        [-80.0, -50.0, 90.0, 60.0],      # past the top-left corner
        [100.0, 20.0, 133.0, 181.0],     # 2 cells wide, 11 high
        [16.0, 32.0, 112.0, 128.0],      # 7 x 7 cells: a cell a bin
        [48.0, 16.0, 256.0, 112.0],      # 14 x 7 cells
    ]
    for _ in range(13):
        x1, y1 = rng.uniform(-20, 200), rng.uniform(-20, 100)
        rois.append([x1, y1, x1 + rng.uniform(1, 100), y1 + rng.uniform(1, 70)])
    return np.asarray(rois, np.float32)


def half_cell_rois():
    """Corners on exact half cells (x = 16k + 8, which scaled gt boxes do
    meet), on either side of zero: C's ``round`` takes 0.5 → 1, 1.5 → 2,
    2.5 → 3, -0.5 → -1, where rounding to even gives 0, 2, 2, 0."""
    return np.asarray([
        [8.0, 8.0, 120.0, 104.0],        # 0.5 .. 7.5, 0.5 .. 6.5
        [24.0, 40.0, 200.0, 136.0],      # 1.5 .. 12.5, 2.5 .. 8.5
        [-8.0, -24.0, 72.0, 88.0],       # -0.5 .. 4.5, -1.5 .. 5.5
        [40.0, 8.0, 40.0, 8.0],          # one cell, at (3, 1) and not (2, 0)
        [104.0, 72.0, 312.0, 184.0],     # 6.5 .. 19.5, 4.5 .. 11.5
    ], np.float32)


ROIS = {"clipped": clipped_rois, "inner": inner_rois,
         "half_cells": half_cell_rois}


def every_edge_rois(map_h: int, map_w: int):
    """Rois that between them meet every (first cell, last cell) pair of a
    ``map_h`` x ``map_w`` map on either axis, and some that start a cell
    or two outside it."""
    xs = [(a, b) for a in range(-2, map_w) for b in range(max(a, 0), map_w + 2)]
    ys = [(a, b) for a in range(-2, map_h) for b in range(max(a, 0), map_h + 2)]
    n = max(len(xs), len(ys))
    return np.asarray(
        [[16.0 * xs[i % len(xs)][0], 16.0 * ys[i % len(ys)][0],
          16.0 * xs[i % len(xs)][1], 16.0 * ys[i % len(ys)][1]]
         for i in range(n)], np.float32)


def position_map(map_h: int, map_w: int):
    """Channel 0 rises with a cell's (row, column), channel 1 falls, row
    and column apart in channels 2-5: a bin's maxima name its four edges."""
    r, c = np.mgrid[0:map_h, 0:map_w].astype(np.float32)
    return np.stack([r * map_w + c, -(r * map_w + c), r, -r, c, -c], axis=-1)
