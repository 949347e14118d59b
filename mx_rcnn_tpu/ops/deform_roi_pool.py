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
A pass is a matrix product of every bin's weight row over the map with
the map (:func:`deform_roi_pool`); gradients come from ``jax.grad``: with
respect to the map (the transposed product) and to the offsets (the
weights' slopes; zero for a clamped coordinate, whose position no longer
moves).

``valid_hw`` (the image's true ``(h, w)``) makes the border rule the
image's own: the map's extent is the valid one, ``ceil(h·s)`` cells, the
limit ``ops/roi_align.py::_feat_limits`` gives ROIAlign.
"""

from __future__ import annotations

import functools

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


def _weight_rows(coord, keep, lim_f, lim_i, size: int):
    """One axis of a bin's samples: (..., n) coordinates on the map
    (before the clamp) and whether each is kept → (..., size), the kept
    samples' two-corner bilinear weights summed over the samples.  A
    sample clamped onto the last cell puts both weights on it."""
    c = jnp.clip(coord, 0.0, lim_f - 1.0)
    lo = jnp.floor(c)
    frac = c - lo
    lo = lo.astype(jnp.int32)
    hi = jnp.minimum(lo + 1, lim_i - 1)
    cells = jnp.arange(size, dtype=jnp.int32)
    rows = ((1.0 - frac)[..., None] * (cells == lo[..., None])
            + frac[..., None] * (cells == hi[..., None]))
    return (keep[..., None] * rows).sum(axis=-2)


def deform_roi_pool(feat: jnp.ndarray, rois: jnp.ndarray, offsets=None,
                    pooled=(7, 7), spatial_scale: float = 1.0 / 16.0,
                    sample_per_part: int = 4, trans_std: float = TRANS_STD,
                    valid_hw=None) -> jnp.ndarray:
    """(H, W, C) map × (R, 4) rois [× (R, 2, ph, pw) offsets] → (R, ph,
    pw, C) in the map's dtype (the module docstring has the semantics).

    A bin's samples form a product grid: a sample's y hangs on its row
    of the grid alone, its x on its column, and the skip rule is a test
    of each.  So the bin's value is ``Σ_h Σ_w u[h]·v[w]·F[h, w] / (k_y ·
    k_x)``, with ``u`` (H,) and ``v`` (W,) the kept samples' weights along
    each axis (:func:`_weight_rows`) and ``k_y · k_x`` the kept samples:
    one weight row ``u ⊗ v`` over the whole map a bin (an empty bin's is
    zero), and one (R·ph·pw, H·W) × (H·W, C) product for the pass.
    ``jax.grad`` gives the map's gradient as the transposed product and
    the offsets' through the weights' slopes.  Precision follows the
    map's dtype: a bfloat16 map meets bfloat16 weights in one MXU pass
    with float32 accumulation (the training graph's contract), a float32
    one ``HIGHEST``.

    The row is formed flat, ``u`` repeated times ``v`` tiled: formed as
    (…, H, W) and reshaped, a 38×64 map's rows are laid out 64 lanes of
    128 and copied.  At ``dcn_train_b8``'s shape (8 images, 128 rois) the
    rows are 8 × 6272 × 2432, 244 MB in bfloat16 a pass, and the weights'
    gradient 488 MB in float32; :func:`deform_roi_pool_batched` forms
    them again in the backward pass rather than keep them."""
    hf, wf, c = feat.shape
    limits = _feat_limits((hf, wf), valid_hw, spatial_scale)
    y, x, keep = sample_grid(rois, offsets, pooled, spatial_scale,
                             sample_per_part, trans_std, limits)
    (lh, lh_i), (lw, lw_i) = limits
    count = keep.sum(axis=(-2, -1)).astype(jnp.float32)          # (R, ph, pw)
    # keep = (row kept) & (column kept): where a bin keeps any sample the
    # two tests are its any() along the other axis, elsewhere both zero
    u = _weight_rows(y[..., :, 0], keep.any(axis=-1), lh, lh_i, hf)
    v = _weight_rows(x[..., 0, :], keep.any(axis=-2), lw, lw_i, wf)
    u = (u / jnp.maximum(count, 1.0)[..., None]).reshape(-1, hf)
    a = jnp.repeat(u, wf, axis=-1) * jnp.tile(v.reshape(-1, wf), (1, hf))
    if feat.dtype == jnp.bfloat16:
        a, prec = a.astype(jnp.bfloat16), jax.lax.Precision.DEFAULT
    else:
        prec = jax.lax.Precision.HIGHEST
    out = jnp.dot(a, feat.reshape(hf * wf, c), precision=prec,
                  preferred_element_type=feat.dtype)
    return out.reshape(y.shape[:3] + (c,))


@functools.partial(jax.jit, static_argnames=(
    "pooled", "spatial_scale", "sample_per_part"))
def deform_roi_pool_batched(feat: jnp.ndarray, rois: jnp.ndarray,
                            offsets=None, pooled=(7, 7),
                            spatial_scale: float = 1.0 / 16.0,
                            sample_per_part: int = 4, valid_hw=None):
    """(B, H, W, C) × (B, R, 4) [× (B, R, 2, ph, pw) offsets, (B, 2)
    ``valid_hw``] → (B, R, ph, pw, C): :func:`deform_roi_pool` over the
    batch, one product with the images as its batch dimension, its
    weight rows formed again for the backward pass (``jax.checkpoint``:
    kept, they took the cell's step from 3.97 to 5.00 GB of temporaries
    by the compiler's count).  Jitted, so that ``train_net``'s op-by-op
    ``model.init`` compiles a pass as one program and not each of its
    some sixty operations as a program of its own."""
    return jax.vmap(jax.checkpoint(lambda f, r, t, v: deform_roi_pool(
        f, r, t, pooled, spatial_scale, sample_per_part, valid_hw=v)))(
            feat, rois, offsets, valid_hw)


def empty_bins(feat_hw, rois, offsets=None, pooled=(7, 7),
               spatial_scale: float = 1.0 / 16.0, sample_per_part: int = 4,
               trans_std: float = TRANS_STD, valid_hw=None) -> jnp.ndarray:
    """How many of the rois' bins keep no sample (int32): such a bin
    pools 0 whatever the map holds."""
    limits = _feat_limits(feat_hw, valid_hw, spatial_scale)
    _y, _x, keep = sample_grid(rois, offsets, pooled, spatial_scale,
                               sample_per_part, trans_std, limits)
    return (~keep.any(axis=(-2, -1))).sum()
