"""Deformable convolution and deformable ROI pooling (Deformable ConvNets)
against independent float64 loops written from MXNet's definitions
(``deformable_im2col`` and ``DeformablePSROIPooling`` at ``group_size``
1): the program's ops (``ops/deform_conv.py``, ``ops/deform_roi_pool.py``)
and the plain reference's own copies (``benchmark/reference/models/
dcn.py``), which are written apart from the program's.

Where every offset is zero or a whole number a sample lands on a cell,
so the sampled values are the map's own and the only rounding is the
float32 product's: those cases are held to 1e-5 of the output's scale.
Fractional offsets add the bilinear weights' float32 rounding: 1e-5 too
(a handful of multiply-adds a value).  Gradients are held against
float64 central differences of the loop at points whose samples lie
away from whole-number crossings (the interpolation's slope jumps there),
at 2e-3: the differences' own error at steps of 1e-3 (the convolution)
and 1e-4 (the pooling) is under 1e-6, the rest is the float32
gradient's rounding over a few hundred terms."""

import importlib.util
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.ops.deform_conv import deform_conv, inside_count
from mx_rcnn_tpu.ops.pallas import deform_conv as pallas_deform_conv
from mx_rcnn_tpu.ops.deform_roi_pool import (deform_roi_pool,
                                             deform_roi_pool_batched,
                                             empty_bins, sample_grid)

_BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)

D, G = 2, 4                     # the configuration's dilation and groups
POOLED, SPP, GAMMA, SCALE = (7, 7), 4, 0.1, 1.0 / 16
VALUE_TOL = 1e-5
GRAD_TOL = 2e-3


def _reference_dcn():
    """``benchmark/reference/models/dcn.py``, executed as the reference's
    ``build_model`` executes it (by file, outside ``sys.modules``)."""
    path = os.path.join(_BENCH, "reference", "models", "dcn.py")
    spec = importlib.util.spec_from_file_location("reference.models.dcn", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference_dcn()


# ----------------------------------------------------------- float64 loops
def loop_deform_conv(x, off, k, d=D, groups=G):
    """MXNet's ``deformable_im2col`` then the product, point by point."""
    b, h, w, c = x.shape
    cg = c // groups
    cols = np.zeros((b, h, w, 9, c))
    for n in range(b):
        for r in range(h):
            for s in range(w):
                for i in range(3):
                    for j in range(3):
                        for g in range(groups):
                            ch = 2 * (9 * g + 3 * i + j)
                            y = r - d + i * d + off[n, r, s, ch]
                            xx = s - d + j * d + off[n, r, s, ch + 1]
                            if not (0 <= y < h and 0 <= xx < w):
                                continue
                            y0, x0 = math.floor(y), math.floor(xx)
                            if y0 >= h - 1:
                                y0 = y1 = h - 1
                                y = float(y0)
                            else:
                                y1 = y0 + 1
                            if x0 >= w - 1:
                                x0 = x1 = w - 1
                                xx = float(x0)
                            else:
                                x1 = x0 + 1
                            ly, lx = y - y0, xx - x0
                            sl = slice(g * cg, (g + 1) * cg)
                            cols[n, r, s, 3 * i + j, sl] = (
                                (1 - ly) * (1 - lx) * x[n, y0, x0, sl]
                                + (1 - ly) * lx * x[n, y0, x1, sl]
                                + ly * (1 - lx) * x[n, y1, x0, sl]
                                + ly * lx * x[n, y1, x1, sl])
    return cols.reshape(b, h, w, 9 * c) @ k.reshape(9 * c, -1)


def loop_inside(off, h, w, d=D, groups=G):
    n = 0
    for idx in np.ndindex(off.shape[:3]):
        _b, r, s = idx
        for i in range(3):
            for j in range(3):
                for g in range(groups):
                    ch = 2 * (9 * g + 3 * i + j)
                    y = r - d + i * d + off[idx][ch]
                    xx = s - d + j * d + off[idx][ch + 1]
                    n += int(0 <= y < h and 0 <= xx < w)
    return n


def _c_round(v):
    return math.copysign(math.floor(abs(v) + 0.5), v)


def loop_bins(h, w, rois, trans=None, pooled=POOLED, spp=SPP, gamma=GAMMA,
              scale=SCALE):
    """MXNet's ``DeformablePSROIPoolForwardKernel`` at ``group_size`` 1 on
    an h × w map, bin by bin and sample by sample: yields ``(n, p, q,
    corners)`` a bin, ``corners`` the (row, column, weight) of every kept
    sample's four corners, the weights divided by the bin's kept count
    (an empty bin has none)."""
    ph, pw = pooled
    for n, roi in enumerate(rois):
        sw = _c_round(roi[0]) * scale - 0.5
        sh = _c_round(roi[1]) * scale - 0.5
        ew = (_c_round(roi[2]) + 1.0) * scale - 0.5
        eh = (_c_round(roi[3]) + 1.0) * scale - 0.5
        rw, rh = max(ew - sw, 0.1), max(eh - sh, 0.1)
        bw, bh = rw / pw, rh / ph
        for p in range(ph):
            for q in range(pw):
                tx = 0.0 if trans is None else trans[n, 0, p, q] * gamma
                ty = 0.0 if trans is None else trans[n, 1, p, q] * gamma
                ws = q * bw + sw + tx * rw
                hs = p * bh + sh + ty * rh
                corners = []
                for ih in range(spp):
                    for iw in range(spp):
                        xx = ws + iw * bw / spp
                        y = hs + ih * bh / spp
                        if xx < -0.5 or xx > w - 0.5 or y < -0.5 or y > h - 0.5:
                            continue
                        xx = min(max(xx, 0.0), w - 1.0)
                        y = min(max(y, 0.0), h - 1.0)
                        x1, x2 = math.floor(xx), math.ceil(xx)
                        y1, y2 = math.floor(y), math.ceil(y)
                        dx, dy = xx - x1, y - y1
                        corners.append([(y1, x1, (1 - dx) * (1 - dy)),
                                        (y2, x1, (1 - dx) * dy),
                                        (y1, x2, dx * (1 - dy)),
                                        (y2, x2, dx * dy)])
                count = len(corners)
                yield n, p, q, [(yy, xx, wgt / count)
                                for sample in corners
                                for yy, xx, wgt in sample]


def loop_deform_roi_pool(fmap, rois, trans=None):
    """The bins' values from :func:`loop_bins` → (values, bins with no
    sample)."""
    h, w, c = fmap.shape
    out = np.zeros((len(rois),) + POOLED + (c,))
    empty = 0
    for n, p, q, corners in loop_bins(h, w, rois, trans):
        for yy, xx, wgt in corners:
            out[n, p, q] += wgt * fmap[yy, xx]
        empty += not corners
    return out, empty


def loop_map_gradient(fmap_shape, rois, trans, cot):
    """The gradient of ``Σ cot · values`` with respect to the map: each
    bin's cotangent sent back to its samples' corners by their weights."""
    h, w, _c = fmap_shape
    grad = np.zeros(fmap_shape)
    for n, p, q, corners in loop_bins(h, w, rois, trans):
        for yy, xx, wgt in corners:
            grad[yy, xx] += wgt * cot[n, p, q]
    return grad


# ------------------------------------------------------------------ inputs
B, H, W, C, COUT = 2, 5, 6, 8, 3


def _conv_inputs(kind, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(B, H, W, C)
    k = rng.randn(3, 3, C, COUT) / 6.0
    shape = (B, H, W, 2 * 9 * G)
    if kind == "zero":
        off = np.zeros(shape)
    elif kind == "whole":
        off = rng.randint(-3, 4, size=shape).astype(np.float64)
    elif kind == "fraction":
        # every sample 0.2-0.8 of a cell from a whole number: the slopes
        # are the same on both sides of a finite difference
        off = rng.randint(-2, 3, size=shape) + rng.uniform(0.2, 0.8, shape)
    elif kind == "off_map":
        off = rng.randint(-9, 10, size=shape) + rng.uniform(0.2, 0.8, shape)
    else:
        raise ValueError(kind)
    return x, off, k


def _program_conv(x, off, k):
    with jax.default_matmul_precision("highest"):
        return np.asarray(deform_conv(
            jnp.asarray(x, jnp.float32), jnp.asarray(off, jnp.float32),
            jnp.asarray(k, jnp.float32), D, G))


def _reference_conv(x, off, k):
    with jax.default_matmul_precision("highest"):
        return np.asarray(REF.deform_conv(
            jnp.asarray(x, jnp.float32), jnp.asarray(off, jnp.float32),
            jnp.asarray(k, jnp.float32)))


CONVS = {"program": _program_conv, "reference": _reference_conv}


def _close(got, want, tol=VALUE_TOL):
    scale = max(float(np.abs(want).max()), 1.0)
    assert float(np.abs(got - want).max()) <= tol * scale, (
        float(np.abs(got - want).max()), scale)


# ------------------------------------------------------ deformable conv
def test_reference_constants_are_the_configuration_s():
    assert (REF.DILATION, REF.GROUPS) == (D, G)
    assert (REF.POOLED, REF.SAMPLE_PER_PART, REF.TRANS_STD) == (
        POOLED, SPP, GAMMA)


@pytest.mark.parametrize("side", sorted(CONVS))
def test_zero_offsets_are_the_dilated_convolution(side):
    x, off, k = _conv_inputs("zero")
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.lax.conv_general_dilated(
            jnp.asarray(x, jnp.float32), jnp.asarray(k, jnp.float32), (1, 1),
            [(D, D), (D, D)], rhs_dilation=(D, D),
            dimension_numbers=("NHWC", "HWIO", "NHWC")))
    _close(CONVS[side](x, off, k), want)
    _close(want, loop_deform_conv(x, off, k))


@pytest.mark.parametrize("side", sorted(CONVS))
def test_one_whole_offset_everywhere_is_a_shifted_convolution(side):
    """Every tap moved by (+1, −2): output (r, s) is the dilated
    convolution's output at (r + 1, s − 2) over the zero-padded map."""
    x, off, k = _conv_inputs("zero")
    off[..., 0::2], off[..., 1::2] = 1.0, -2.0
    p = 5
    padded = np.zeros((B, H + 2 * p, W + 2 * p, C))
    padded[:, p:p + H, p:p + W] = x
    with jax.default_matmul_precision("highest"):
        valid = np.asarray(jax.lax.conv_general_dilated(
            jnp.asarray(padded, jnp.float32), jnp.asarray(k, jnp.float32),
            (1, 1), "VALID", rhs_dilation=(D, D),
            dimension_numbers=("NHWC", "HWIO", "NHWC")))
    # the valid output o reads padded[o + D·i]: output (r, s) tap (i, j)
    # reads padded[p + r + 1 + D·(i − 1), p + s − 2 + D·(j − 1)]
    want = valid[:, p + 1 - D:p + 1 - D + H, p - 2 - D:p - 2 - D + W]
    _close(CONVS[side](x, off, k), want)


@pytest.mark.parametrize("kind", ["whole", "fraction", "off_map"])
@pytest.mark.parametrize("side", sorted(CONVS))
def test_deform_conv_equals_the_loop(side, kind):
    x, off, k = _conv_inputs(kind, seed=3)
    _close(CONVS[side](x, off, k), loop_deform_conv(x, off, k))


def test_the_last_row_and_column_read_the_edge():
    """A point in [H − 1, H) reads row H − 1 itself (MXNet's clamp of
    the corner past the edge), one at H or beyond reads 0."""
    x, off, k = _conv_inputs("zero")
    off[:, :, :, 0::2] = 0.0
    off[:, 2, 3, 0::2] = H - 1 - 2 + 0.5 + 2   # tap row 0 lands at H − 0.5
    off[:, 2, 4, 0::2] = H - 2 + 2.0           # tap row 0 lands at H
    for side in CONVS:
        _close(CONVS[side](x, off, k), loop_deform_conv(x, off, k))


@pytest.mark.parametrize("side", sorted(CONVS))
def test_deform_conv_gradients_against_differences(side):
    x, off, k = _conv_inputs("fraction", seed=5)
    cot = np.random.RandomState(6).randn(B, H, W, COUT)
    fn = {"program": lambda a, o, kk: deform_conv(a, o, kk, D, G),
          "reference": REF.deform_conv}[side]

    def loss(a, o, kk):
        return jnp.sum(fn(a, o, kk) * jnp.asarray(cot, jnp.float32))

    with jax.default_matmul_precision("highest"):
        grads = jax.grad(loss, argnums=(0, 1, 2))(
            *(jnp.asarray(v, jnp.float32) for v in (x, off, k)))
    rng = np.random.RandomState(7)
    eps = 1e-3
    for which, arr in enumerate((x, off, k)):
        g = np.asarray(grads[which])
        for _ in range(6):
            idx = tuple(rng.randint(s) for s in arr.shape)
            args = [x, off, k]
            plus, minus = arr.copy(), arr.copy()
            plus[idx] += eps
            minus[idx] -= eps
            args[which] = plus
            hi = (loop_deform_conv(*args) * cot).sum()
            args[which] = minus
            lo = (loop_deform_conv(*args) * cot).sum()
            want = (hi - lo) / (2 * eps)
            assert abs(g[idx] - want) <= GRAD_TOL * max(1.0, abs(want)), (
                which, idx, g[idx], want)


def test_the_in_map_counter():
    """Zero offsets: a tap row of −2 or +2 leaves two rows out, so a
    group sees (3H − 4)(3W − 4) of its 9·H·W points; pushed a whole map
    down, none; and a random field, as the loop counts."""
    zero = np.zeros((B, H, W, 2 * 9 * G))
    assert int(inside_count(jnp.asarray(zero), D, G)) == (
        B * G * (3 * H - 4) * (3 * W - 4)) == loop_inside(zero, H, W)
    down = zero.copy()
    down[..., 0::2] = H + 2
    assert int(inside_count(jnp.asarray(down), D, G)) == 0
    _x, off, _k = _conv_inputs("off_map", seed=9)
    assert int(inside_count(jnp.asarray(off, jnp.float32), D, G)) == (
        loop_inside(off, H, W))


def loop_window_chunks(off, h, w, d=D, groups=G):
    """The chunks the Pallas pair's bands read, point by point: for every
    image, group, block of the pair's positions (row-major, the last one
    short) and tap, the kept points' corner rows from the first to the
    last, in chunks of the pair's rows."""
    block, rows = pallas_deform_conv._P, pallas_deform_conv._CR
    total = 0
    for n in range(off.shape[0]):
        for g in range(groups):
            for first in range(0, h * w, block):
                for i in range(3):
                    for j in range(3):
                        ch = 2 * (9 * g + 3 * i + j)
                        corners = []
                        for pos in range(first, min(first + block, h * w)):
                            r, s = divmod(pos, w)
                            y = r - d + i * d + off[n, r, s, ch]
                            xx = s - d + j * d + off[n, r, s, ch + 1]
                            if 0 <= y < h and 0 <= xx < w:
                                y0 = min(math.floor(y), h - 1)
                                corners += [y0, min(y0 + 1, h - 1)]
                        if corners:
                            total += math.ceil(
                                (max(corners) - min(corners) + 1) / rows)
    return total


def _window_chunks(off):
    return int(pallas_deform_conv.window_chunks(
        jnp.asarray(off, jnp.float32), D, G))


def test_the_window_chunk_counter():
    """At zero offsets the count is ``deform_window_chunks_min``, as the
    loop counts it.  Every offset of a block's first output row moved up
    by ``n`` whole rows and of its last row down by ``n`` widens the
    block's bands by ``2n`` rows, and the count rises by what the loop
    counts, ``2n`` rows' worth of chunks on every inner band at least;
    every offset moved by ``n`` rows, and a random field, as the loop
    counts them."""
    h, w = 24, 16                       # three blocks of eight map rows
    zero = np.zeros((1, h, w, 2 * 9 * G))
    at_zero = _window_chunks(zero)
    assert at_zero == loop_window_chunks(zero, h, w)
    rows, per = pallas_deform_conv._CR, pallas_deform_conv._P // w
    edge = np.arange(h) % per
    sign = ((edge == per - 1).astype(float) - (edge == 0))[None, :, None,
                                                             None]
    for n in (1, 2, 4):
        moved = zero.copy()
        moved[..., 0::2] = n * sign
        count = _window_chunks(moved)
        assert count == loop_window_chunks(moved, h, w)
        assert count > at_zero
        # the middle block, middle tap row: its rows + 1 at zero offsets,
        # 2n more when moved, for each of the row's 3 taps and G groups
        inner = 3 * G * (math.ceil((per + 1 + 2 * n) / rows)
                         - math.ceil((per + 1) / rows))
        assert count - at_zero >= inner
        # every offset moved the same n rows: the bands move, and change
        # only where they meet the map's border, as the loop counts
        down = zero.copy()
        down[..., 0::2] = n
        assert _window_chunks(down) == loop_window_chunks(down, h, w)
    _x, off, _k = _conv_inputs("off_map", seed=9)
    assert _window_chunks(off) == loop_window_chunks(off, H, W)


# ------------------------------------------------- deformable ROI pooling
MH, MW, MC, R = 9, 11, 4, 6
#: a whole-map roi, rois at the border, one-cell rois, a roi past the map
ROIS = np.array([
    [0, 0, MW * 16 - 1, MH * 16 - 1],
    [0, 0, 40, 30],
    [120, 100, MW * 16 - 1, MH * 16 - 1],
    [50, 60, 50, 60],
    [33.5, 17.4, 33.6, 17.5],
    [100, 10, 230, 200],
], np.float64)


def _pool_inputs(kind, seed=0):
    rng = np.random.RandomState(seed)
    fmap = rng.randn(MH, MW, MC)
    if kind == "none":
        return fmap, None
    trans = rng.uniform(-1.5, 1.5, size=(R, 2) + POOLED)
    if kind == "far":
        trans = trans * 8.0
    return fmap, trans


def _program_pool(fmap, rois, trans, valid_hw=None):
    return np.asarray(deform_roi_pool(
        jnp.asarray(fmap, jnp.float32), jnp.asarray(rois, jnp.float32),
        None if trans is None else jnp.asarray(trans, jnp.float32),
        POOLED, SCALE, SPP, GAMMA,
        valid_hw=None if valid_hw is None else jnp.asarray(valid_hw)))


def _reference_pool(fmap, rois, trans):
    return np.asarray(REF.deform_roi_pool(
        jnp.asarray(fmap, jnp.float32), jnp.asarray(rois, jnp.float32),
        None if trans is None else jnp.asarray(trans, jnp.float32), SCALE))


POOLS = {"program": _program_pool, "reference": _reference_pool}


@pytest.mark.parametrize("kind", ["none", "moved", "far"])
@pytest.mark.parametrize("side", sorted(POOLS))
def test_deform_roi_pool_equals_the_loop(side, kind):
    fmap, trans = _pool_inputs(kind, seed=1)
    want, _empty = loop_deform_roi_pool(fmap, ROIS, trans)
    _close(POOLS[side](fmap, ROIS, trans), want)


def test_the_empty_bins_counter():
    fmap, trans = _pool_inputs("far", seed=2)
    _want, empty = loop_deform_roi_pool(fmap, ROIS, trans)
    assert empty > 0
    got = empty_bins((MH, MW), jnp.asarray(ROIS, jnp.float32),
                     jnp.asarray(trans, jnp.float32), POOLED, SCALE, SPP, GAMMA)
    assert int(got) == empty
    assert int(empty_bins((MH, MW), jnp.asarray(ROIS, jnp.float32))) == (
        loop_deform_roi_pool(fmap, ROIS)[1])


@pytest.mark.parametrize("kind", ["border", "last_cell"])
def test_valid_hw_is_the_cropped_map(kind):
    """An image 100×130 pixels on the 9×11 map: its valid extent is 7×9
    cells (ceil at 1/16), and pooling with ``valid_hw`` is pooling the map
    cropped to it, values and map gradient (none past the crop).
    ``last_cell``: one roi's bins moved 1.1 cells down and right, so that
    samples in (6, 6.5] × (8, 8.5] are kept and clamped onto the last valid
    row and column, where both corners of a sample fall on one cell."""
    fmap, trans = _pool_inputs("moved", seed=4)
    rois = np.minimum(ROIS, [[129, 99, 129, 99]])
    if kind == "last_cell":
        rois[2] = [120, 90, 129, 99]          # 0.625 cells a side at 1/16
        trans[2] = 1.1 / (GAMMA * 0.625)
        y, x, keep = sample_grid(
            jnp.asarray(rois[2:3], jnp.float32),
            jnp.asarray(trans[2:3], jnp.float32), POOLED, SCALE, SPP, GAMMA,
            [(7.0, 7), (9.0, 9)])
        assert bool((keep & (y > 6) & (x > 8)).any()) and not bool(keep.all())
    valid_hw = jnp.asarray([100.0, 130.0])
    want, _ = loop_deform_roi_pool(fmap[:7, :9], rois, trans)
    _close(_program_pool(fmap, rois, trans, valid_hw=valid_hw), want)
    cot = np.random.RandomState(5).randn(len(rois), *POOLED, MC)
    got = jax.grad(lambda f: jnp.sum(deform_roi_pool(
        f, jnp.asarray(rois, jnp.float32), jnp.asarray(trans, jnp.float32),
        POOLED, SCALE, SPP, GAMMA, valid_hw=valid_hw) * cot))(
            jnp.asarray(fmap, jnp.float32))
    want = np.zeros_like(fmap)
    want[:7, :9] = loop_map_gradient((7, 9, MC), rois, trans, cot)
    _close(np.asarray(got), want)


#: a bfloat16 rounding is at most 2^-9 of a value; the weights, the
#: product and, for the offsets, the difference of two rows of the weights'
#: gradient round, against the output's largest value rather than the sum
#: of the magnitudes behind it: eight roundings' room
BF16_TOL = 2.0 ** -6


def test_a_bf16_map_is_within_its_rounding_of_the_loop():
    """The training graph's dtype at a mid size (a 19×32×128 map, 32
    rois, offsets moved): the weights meet the map in bfloat16 with
    float32 accumulation.  Values, map gradient (the loop's weights
    sending the cotangent back) and offsets' gradient (central
    differences of the loop, one roi at a time) against the loop on the
    same bfloat16 map and cotangent."""
    rng = np.random.RandomState(12)
    h, w, c, r = 19, 32, 128, 32
    x1, y1 = rng.uniform(0, w * 16 - 40, r), rng.uniform(0, h * 16 - 40, r)
    rois = np.stack([x1, y1, np.minimum(x1 + rng.uniform(8, 300, r),
                                        w * 16 - 1),
                     np.minimum(y1 + rng.uniform(8, 200, r), h * 16 - 1)], 1)
    trans = rng.uniform(-1.5, 1.5, size=(r, 2) + POOLED)

    def bf16(a):
        return np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32),
                          np.float64)

    fmap, cot = bf16(rng.randn(h, w, c)), bf16(rng.randn(r, *POOLED, c))

    def loss(f, t):
        out = deform_roi_pool(f, jnp.asarray(rois, jnp.float32), t, POOLED,
                              SCALE, SPP, GAMMA)
        return jnp.sum(out.astype(jnp.float32) * cot), out

    (_l, out), (gf, gt) = jax.value_and_grad(loss, (0, 1), has_aux=True)(
        jnp.asarray(fmap, jnp.bfloat16), jnp.asarray(trans, jnp.float32))
    assert out.dtype == gf.dtype == jnp.bfloat16
    _close(np.asarray(out.astype(jnp.float32)),
           loop_deform_roi_pool(fmap, rois, trans)[0], BF16_TOL)
    _close(np.asarray(gf.astype(jnp.float32)),
           loop_map_gradient(fmap.shape, rois, trans, cot), BF16_TOL)
    pick, eps = np.random.RandomState(5), 1e-4
    for _ in range(30):
        n, idx = pick.randint(r), tuple(pick.randint(s) for s in (2,) + POOLED)
        vals = []
        for sign in (1, -1):
            moved = trans[n:n + 1].copy()
            moved[(0,) + idx] += sign * eps
            vals.append((loop_deform_roi_pool(fmap, rois[n:n + 1], moved)[0][0]
                         * cot[n]).sum())
        want = (vals[0] - vals[1]) / (2 * eps)
        got = float(gt[(n,) + idx])
        assert abs(got - want) <= BF16_TOL * max(1.0, abs(want)), (
            n, idx, got, want)


def test_both_passes_lower_to_products_alone():
    """The two passes with an fc between them, as ``FasterRCNN.
    _deform_pool`` runs them on a bfloat16 batch, differentiated with
    respect to the map and the fc: the lowered program holds no gather,
    no scatter and no loop; every read of the map and every gradient sent
    back to it is a matrix product."""
    b, r = 2, 5
    rng = np.random.RandomState(0)
    feat = jnp.asarray(rng.randn(b, MH, MW, MC), jnp.bfloat16)
    rois = jnp.asarray(np.stack([ROIS[:r]] * b), jnp.float32)
    valid_hw = jnp.asarray([[MH * 16.0, MW * 16.0], [100.0, 130.0]])
    fc = jnp.asarray(rng.randn(49 * MC, 2 * 49) * 0.01, jnp.float32)

    def loss(f, k):
        first = deform_roi_pool_batched(f, rois, valid_hw=valid_hw)
        t = (first.reshape(b * r, -1).astype(jnp.float32) @ k).reshape(
            (b, r, 2) + POOLED)
        return jnp.sum(deform_roi_pool_batched(
            f, rois, t, valid_hw=valid_hw).astype(jnp.float32))

    hlo = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
        feat, fc).as_text(dialect="hlo")
    assert " dot(" in hlo
    for op in ("gather", "scatter", "while"):
        assert hlo.count(f" {op}(") == 0, op


@pytest.mark.parametrize("side", sorted(POOLS))
def test_deform_roi_pool_gradients_against_differences(side):
    fmap, trans = _pool_inputs("moved", seed=8)
    rois = ROIS[[0, 1, 5]]
    trans = trans[[0, 1, 5]]
    cot = np.random.RandomState(3).randn(len(rois), *POOLED, MC)
    pool = {"program": lambda f, t: deform_roi_pool(
        f, jnp.asarray(rois, jnp.float32), t, POOLED, SCALE, SPP, GAMMA),
        "reference": lambda f, t: REF.deform_roi_pool(
            f, jnp.asarray(rois, jnp.float32), t, SCALE)}[side]

    def loss(f, t):
        return jnp.sum(pool(f, t) * jnp.asarray(cot, jnp.float32))

    gf, gt = jax.grad(loss, argnums=(0, 1))(
        jnp.asarray(fmap, jnp.float32), jnp.asarray(trans, jnp.float32))
    rng = np.random.RandomState(11)
    eps = 1e-4
    checked = 0
    for which, arr, g in ((0, fmap, gf), (1, trans, gt)):
        for _ in range(40):
            idx = tuple(rng.randint(s) for s in arr.shape)
            vals = []
            for sign in (1, -1):
                moved = arr.copy()
                moved[idx] += sign * eps
                args = (moved, trans) if which == 0 else (fmap, moved)
                vals.append((loop_deform_roi_pool(args[0], rois, args[1])[0]
                             * cot).sum())
            want = (vals[0] - vals[1]) / (2 * eps)
            if which == 1 and want == 0.0:
                continue      # a bin whose samples all sit clamped or off
            got = float(np.asarray(g)[idx])
            assert abs(got - want) <= GRAD_TOL * max(1.0, abs(want)), (
                which, idx, got, want)
            checked += 1
    assert checked >= 60
