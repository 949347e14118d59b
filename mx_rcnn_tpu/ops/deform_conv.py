"""Deformable convolution, v1 (Dai et al., "Deformable Convolutional
Networks", ICCV 2017, arXiv:1703.06211 §2.1), in plain jnp.

A 3×3 convolution whose taps read the map at positions the network
computes:

    y(p0) = Σ_k w_k · x(p0 + d·p_k + Δp_k)

with ``p_k`` the 9 taps of the dilated grid (``d`` the dilation, padding
``d``, stride 1), ``Δp_k`` an offset per output position, per tap and per
deformable group (the input channels in ``G`` equal groups, each group
sharing one offset field), and ``x(·)`` bilinear interpolation.

Semantics are MXNet's ``deformable_im2col`` (``contrib.DeformableConvolution``,
the operator of the public ``msracver/Deformable-ConvNets``):

- **Offset layout.** The offsets' channels are ordered (group, tap,
  (dy, dx)): channel ``(g·9 + k)·2`` moves tap ``k`` of group ``g``
  down, the next one right.  Taps are in row-major order of the 3×3
  window, as the kernel's ``(3, 3, C, Cout)`` HWIO layout has them.
- **Border rule.** A point outside the map (``y < 0``, ``y >= H``,
  ``x < 0`` or ``x >= W``) reads 0.  A point inside is interpolated
  bilinearly; where it lies in the last row or column (``y >= H - 1``)
  the corner past the edge is clamped to the edge, so the point reads the
  edge row (or column) itself.  With every offset 0 this is
  ``lax.conv_general_dilated`` with ``rhs_dilation`` ``d`` and padding
  ``d``.
- **Gradients** come from ``jax.grad`` through the bilinear weights: with
  respect to the map (a scatter-add of the weighted columns), the kernel,
  and the offsets (the weights' slopes; zero where a point is outside the
  map or on the clamped edge, as in MXNet's backward).

The form: the four corners' clamped indices and bilinear weights for every
(position, tap, group), the corners gathered as rows of the map, the
weighted ``(H·W, 9·C)`` columns formed in float32 and cast to the
compute dtype, then one product with the ``(9·C, Cout)`` kernel; one
image after the other, each image's columns recomputed in the backward
pass.

Training reads the whole canvas.  A forward-only caller that serves
images padded into a shape bucket would need the valid extent
(``valid_hw``, as ``ops/roi_align.py::_feat_limits`` gives the pooling):
a point past the image's last row interpolates against the bucket's zero
padding where an exact-fit canvas reads the edge row, so the answer would
hang on the bucket.  Nothing here takes it yet.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: the 3×3 window's taps in row-major order: (row, column) in {-1, 0, 1}
TAPS = tuple((i - 1, j - 1) for i in range(3) for j in range(3))


def sample_points(offsets: jnp.ndarray, dilation: int, groups: int):
    """(B, H, W, 2·9·G) offsets → (y, x), each (B, H, W, 9, G) float32:
    where tap ``k`` of group ``g`` at output (h, w) reads the map."""
    b, h, w, _ = offsets.shape
    off = offsets.astype(jnp.float32).reshape(b, h, w, groups, len(TAPS), 2)
    off = off.transpose(0, 1, 2, 4, 3, 5)                # (B, H, W, 9, G, 2)
    ti = jnp.asarray([t[0] for t in TAPS], jnp.float32)[:, None]
    tj = jnp.asarray([t[1] for t in TAPS], jnp.float32)[:, None]
    rows = jnp.arange(h, dtype=jnp.float32)[None, :, None, None, None]
    cols = jnp.arange(w, dtype=jnp.float32)[None, None, :, None, None]
    return (rows + dilation * ti + off[..., 0],
            cols + dilation * tj + off[..., 1])


def inside_map(y: jnp.ndarray, x: jnp.ndarray, hw) -> jnp.ndarray:
    """MXNet v1's test of a sampling point: ``0 <= y < H`` and
    ``0 <= x < W``."""
    return (y >= 0) & (y < hw[0]) & (x >= 0) & (x < hw[1])


def inside_count(offsets: jnp.ndarray, dilation: int, groups: int):
    """How many of the layer's ``B·H·W·9·G`` sampling points fall inside
    the map (int32): a share near 0 would leave the layer reading zeros."""
    y, x = sample_points(offsets, dilation, groups)
    return inside_map(y, x, offsets.shape[1:3]).sum()


def deform_conv(x: jnp.ndarray, offsets: jnp.ndarray, kernel: jnp.ndarray,
                dilation: int, groups: int) -> jnp.ndarray:
    """(B, H, W, C) map, (B, H, W, 2·9·G) offsets, (3, 3, C, Cout) kernel
    → (B, H, W, Cout) in the kernel's dtype (the module docstring has the
    semantics).  ``C`` must divide by ``groups``."""
    _b, h, w, c = x.shape
    k = kernel.reshape(len(TAPS) * c, kernel.shape[-1])

    @jax.checkpoint
    def one_image(xo):
        cols = _columns(xo[0][None], xo[1][None], dilation, groups)
        return (cols.astype(k.dtype).reshape(h * w, len(TAPS) * c) @ k
                ).reshape(h, w, k.shape[-1])

    # image by image, each image's columns recomputed in the backward
    # pass: the batch's four gathered corners in float32 would hold 1.4 GB
    # a layer at 8 × 38 × 64 × 512 from its forward to its backward
    return jax.lax.map(one_image, (x, offsets))


def _columns(x, offsets, dilation: int, groups: int):
    """→ (B, H, W, 9, G, C / G) float32: every tap's bilinear sample."""
    b, h, w, c = x.shape
    cg = c // groups
    y, xx = sample_points(offsets, dilation, groups)
    keep = inside_map(y, xx, (h, w)).astype(jnp.float32)
    # inside the map the clip is MXNet's edge rule (a point in the last row
    # reads that row); outside it only keeps the indices in range, the
    # point weighing 0
    y = jnp.clip(y, 0.0, h - 1.0)
    xx = jnp.clip(xx, 0.0, w - 1.0)
    y0, x0 = jnp.floor(y), jnp.floor(xx)
    ly, lx = y - y0, xx - x0
    y0, x0 = y0.astype(jnp.int32), x0.astype(jnp.int32)
    y1, x1 = jnp.minimum(y0 + 1, h - 1), jnp.minimum(x0 + 1, w - 1)
    # the map as rows of one group's channels: row ((b·H + y)·W + x)·G + g
    rows = x.reshape(b * h * w * groups, cg)
    base = (jnp.arange(b, dtype=jnp.int32) * (h * w))[:, None, None, None, None]
    grp = jnp.arange(groups, dtype=jnp.int32)
    cols = 0.0
    for yy, xc, wgt in ((y0, x0, (1 - ly) * (1 - lx)), (y0, x1, (1 - ly) * lx),
                        (y1, x0, ly * (1 - lx)), (y1, x1, ly * lx)):
        idx = (base + yy * w + xc) * groups + grp                 # (B,H,W,9,G)
        cols = cols + (wgt * keep)[..., None] * rows[idx].astype(jnp.float32)
    return cols
