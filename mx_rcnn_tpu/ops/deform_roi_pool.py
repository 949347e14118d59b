"""Deformable ROI pooling (Dai et al., "Deformable Convolutional
Networks", ICCV 2017, arXiv:1703.06211 §2.2), in plain jnp.

The operator of MXNet's ``contrib.DeformablePSROIPooling`` at
``group_size`` 1 (``output_dim`` = the map's channels, ``part_size`` = the
pooled size), as the public ``msracver/Deformable-ConvNets`` Faster
R-CNN runs it.  For one roi ``(x1, y1, x2, y2)`` in image coordinates at
``spatial_scale`` ``s``:

- **Extent.** ``start = round(x1)·s − 0.5``, ``end = (round(x2) + 1)·s −
  0.5`` (C's ``round``: a half goes away from zero), width ``max(end −
  start, 0.1)``; a bin is a seventh of it, a sub-bin a quarter of a bin
  (``sample_per_part`` 4); the same for the height.
- **The bin's start**, moved by the roi's offsets ``t`` (``(2, ph, pw)``:
  ``t[0]`` along x, ``t[1]`` along y, MXNet's ``trans`` layout) scaled by
  ``trans_std`` γ and by the roi's extent: ``wstart = pw·bin + start +
  γ·t[0, ph, pw]·width``.
- **Samples.** ``sample_per_part`` × ``sample_per_part`` points at
  ``wstart + iw·sub``.  A point with ``w < −0.5`` or ``w > W − 0.5`` (or
  the same in h) is skipped; a kept one is clamped to ``[0, W − 1]`` and
  interpolated bilinearly.
- **The bin's value** is the mean over its kept samples, 0 where none is
  kept.

Without offsets (``t = 0``) it is the first pass of DCN's two: its pooled
rois feed the fully connected layer that computes ``t`` for the second.
Gradients come from ``jax.grad``: with respect to the map (scatter-adds of
the samples' bilinear weights) and to the offsets (the weights' slopes;
zero for a clamped coordinate, whose position no longer moves).

``valid_hw`` (the image's true ``(h, w)``) makes the border rule the
image's own: the map's extent is the valid one, ``ceil(h·s)`` cells, the
limit ``ops/roi_align.py::_feat_limits`` gives ROIAlign.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from mx_rcnn_tpu.ops.roi_align import _feat_limits, _round_half_away

#: γ, the scale of the offsets (``trans_std`` of the public Faster R-CNN)
TRANS_STD = 0.1


def sample_grid(rois, offsets, pooled, spatial_scale: float,
                sample_per_part: int, trans_std: float, limits):
    """(R, 4) rois, (R, 2, ph, pw) offsets or None → (y, x, keep), each
    (R, ph, pw, n, n) with ``n = sample_per_part``: every sample's
    coordinates on the map (before the clamp) and whether it is kept.
    ``limits``: the map's (height, width) as the border rule takes them."""
    ph, pw = pooled
    n = sample_per_part
    v = _round_half_away(rois.astype(jnp.float32))
    x_start = v[:, 0] * spatial_scale - 0.5
    y_start = v[:, 1] * spatial_scale - 0.5
    width = jnp.maximum((v[:, 2] + 1.0) * spatial_scale - 0.5 - x_start, 0.1)
    height = jnp.maximum((v[:, 3] + 1.0) * spatial_scale - 0.5 - y_start, 0.1)
    bin_w, bin_h = width / pw, height / ph                          # (R,)
    bins_y = jnp.arange(ph, dtype=jnp.float32)[None, :, None]
    bins_x = jnp.arange(pw, dtype=jnp.float32)[None, None, :]
    hstart = y_start[:, None, None] + bins_y * bin_h[:, None, None]
    wstart = x_start[:, None, None] + bins_x * bin_w[:, None, None]
    if offsets is not None:
        t = offsets.astype(jnp.float32)
        wstart = wstart + trans_std * t[:, 0] * width[:, None, None]
        hstart = hstart + trans_std * t[:, 1] * height[:, None, None]
    sub = jnp.arange(n, dtype=jnp.float32)
    y = (hstart[..., None, None]
         + sub[:, None] * (bin_h / n)[:, None, None, None, None])
    x = (wstart[..., None, None]
         + sub[None, :] * (bin_w / n)[:, None, None, None, None])
    y, x = jnp.broadcast_arrays(y, x)
    (lh, _), (lw, _) = limits
    keep = (x >= -0.5) & (x <= lw - 0.5) & (y >= -0.5) & (y <= lh - 0.5)
    return y, x, keep


def deform_roi_pool(feat: jnp.ndarray, rois: jnp.ndarray, offsets=None,
                    pooled=(7, 7), spatial_scale: float = 1.0 / 16.0,
                    sample_per_part: int = 4, trans_std: float = TRANS_STD,
                    valid_hw=None) -> jnp.ndarray:
    """(H, W, C) map × (R, 4) rois [× (R, 2, ph, pw) offsets] → (R, ph,
    pw, C) in the map's dtype (the module docstring has the semantics).

    One sub-bin sample at a time over every roi: its four corners are
    gathered as rows of the map, weighted in float32 and summed; each
    sample's gather is recomputed in the backward pass
    (``jax.checkpoint``), so no (R, ph, pw, n, n, C) tensor is kept."""
    hf, wf, c = feat.shape
    limits = _feat_limits((hf, wf), valid_hw, spatial_scale)
    y, x, keep = sample_grid(rois, offsets, pooled, spatial_scale,
                             sample_per_part, trans_std, limits)
    (lh, lh_i), (lw, lw_i) = limits
    y = jnp.clip(y, 0.0, lh - 1.0)
    x = jnp.clip(x, 0.0, lw - 1.0)
    rows = feat.reshape(hf * wf, c)

    @jax.checkpoint
    def sample(y, x, keep):
        y0, x0 = jnp.floor(y), jnp.floor(x)
        ly, lx = y - y0, x - x0
        y0, x0 = y0.astype(jnp.int32), x0.astype(jnp.int32)
        y1, x1 = jnp.minimum(y0 + 1, lh_i - 1), jnp.minimum(x0 + 1, lw_i - 1)
        keep = keep.astype(jnp.float32)
        out = 0.0
        for yy, xx, wgt in ((y0, x0, (1 - ly) * (1 - lx)),
                            (y0, x1, (1 - ly) * lx),
                            (y1, x0, ly * (1 - lx)), (y1, x1, ly * lx)):
            out = out + (wgt * keep)[..., None] * rows[yy * wf + xx].astype(
                jnp.float32)
        return out

    n = sample_per_part
    total = sum(sample(y[..., i, j], x[..., i, j], keep[..., i, j])
                for i in range(n) for j in range(n))
    count = keep.sum(axis=(-2, -1)).astype(jnp.float32)          # (R, ph, pw)
    return (total / jnp.maximum(count, 1.0)[..., None]).astype(feat.dtype)


def deform_roi_pool_batched(feat: jnp.ndarray, rois: jnp.ndarray,
                            offsets=None, pooled=(7, 7),
                            spatial_scale: float = 1.0 / 16.0,
                            sample_per_part: int = 4, valid_hw=None):
    """(B, H, W, C) × (B, R, 4) [× (B, R, 2, ph, pw) offsets, (B, 2)
    ``valid_hw``] → (B, R, ph, pw, C): :func:`deform_roi_pool` one image
    after the other (``lax.map``): batched, its sixteen samples' gathers
    took 0.9 GB of temporaries a pass at the cell's shape."""
    return jax.lax.map(lambda a: deform_roi_pool(
        a[0], a[1], a[2], pooled, spatial_scale, sample_per_part,
        valid_hw=a[3]), (feat, rois, offsets, valid_hw))


def empty_bins(feat_hw, rois, offsets=None, pooled=(7, 7),
               spatial_scale: float = 1.0 / 16.0, sample_per_part: int = 4,
               trans_std: float = TRANS_STD, valid_hw=None) -> jnp.ndarray:
    """How many of the rois' bins keep no sample (int32): such a bin
    pools 0 whatever the map holds."""
    limits = _feat_limits(feat_hw, valid_hw, spatial_scale)
    _y, _x, keep = sample_grid(rois, offsets, pooled, spatial_scale,
                               sample_per_part, trans_std, limits)
    return (~keep.any(axis=(-2, -1))).sum()
