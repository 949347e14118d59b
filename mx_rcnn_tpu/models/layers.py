"""Shared NN building blocks.

TPU-first conventions: NHWC layout (XLA's native conv layout on TPU),
optional bfloat16 compute with float32 parameters (MXU-friendly), and
*frozen* batch-norm as an affine transform using stored moments —
the reference runs every BN with ``use_global_stats=True`` during detection
training (``rcnn/symbol/symbol_resnet.py :: residual_unit``, eps 2e-5), so
BN never updates and is exactly a per-channel scale/shift.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

# the reference's BN epsilon (use_global_stats=True, eps 2e-5); shared by
# the unfused FrozenBatchNorm and the folded fused_conv_bn so the two
# graphs can never silently diverge
BN_EPS = 2e-5


class FrozenBatchNorm(nn.Module):
    """BatchNorm with frozen moments: y = (x - mean) / sqrt(var + eps) * γ + β.

    All four tensors live in ``params`` so checkpoints carry them, but
    ``mean``/``var`` get zero gradient by construction (they only appear
    inside ``lax.stop_gradient``) and γ/β are excluded from the optimizer
    via the FIXED_PARAMS mask (reference: ``FIXED_PARAMS`` incl. BN
    gammas/betas).
    """

    eps: float = BN_EPS
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        c = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (c,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (c,), jnp.float32)
        mean = self.param("mean", nn.initializers.zeros, (c,), jnp.float32)
        var = self.param("var", nn.initializers.ones, (c,), jnp.float32)
        mean = jax.lax.stop_gradient(mean)
        var = jax.lax.stop_gradient(var)
        # fold into a single multiply-add; XLA fuses it into the conv
        mul = scale * jax.lax.rsqrt(var + self.eps)
        add = bias - mean * mul
        return (x * mul.astype(self.dtype) + add.astype(self.dtype)).astype(self.dtype)


def normalize_images(images: jnp.ndarray, im_info, cfg) -> jnp.ndarray:
    """On-device image normalization for uint8-transferred batches
    (TEST.UINT8_TRANSFER: raw RGB crosses host→device at 1/4 the bytes).
    float batches arrive already normalized by the loader and pass
    through untouched, so every model entry point can call this
    unconditionally.

    The bucket padding is re-zeroed from ``im_info`` (true pre-padding
    h/w): the host path pads AFTER normalization, so padding must be 0
    in normalized space — normalizing raw zero pixels would instead
    paint the padding "blacker than black" ((0−mean)/std) and shift
    boundary conv features vs the float path."""
    if images.dtype != jnp.uint8:
        return images
    means = jnp.asarray(cfg.network.PIXEL_MEANS, jnp.float32)
    inv_stds = 1.0 / jnp.asarray(cfg.network.PIXEL_STDS, jnp.float32)
    out = (images.astype(jnp.float32) - means) * inv_stds
    bh, bw = images.shape[1], images.shape[2]
    rows = jnp.arange(bh, dtype=jnp.float32)[None, :, None, None]
    cols = jnp.arange(bw, dtype=jnp.float32)[None, None, :, None]
    mask = (rows < im_info[:, 0, None, None, None]) & (
        cols < im_info[:, 1, None, None, None]
    )
    return out * mask


def make_pad_mask(im_info, canvas_hw):
    """→ ``fn(x)`` that zeroes feature cells sitting on bucket padding.

    The serving/inference invariance tool: ``normalize_images`` zeroes
    the padding at the input, but the first frozen BN maps those zeros to
    its bias, so every subsequent k>1 conv at the valid-region edge would
    read different neighbours on an exact-fit canvas (explicit zero
    padding) than on a larger bucket (BN-propagated values) — detections
    would depend on which bucket the image landed in.  Re-zeroing the pad
    region *before each spatial op* restores the induction: edge convs
    read zeros on every canvas, so the valid region is bitwise canvas-
    independent (at fixed batch size; XLA's conv algorithm choice varies
    with batch).

    A cell (y, x) at feature stride s is valid iff ``s·y < h`` — the same
    criterion as ``ops.proposal.anchor_grid_mask``.  The stride is
    recovered from the canvas/feature ratio snapped to a power of two
    (feature extents are ceil-of-halving chains, so the ratio is exact
    for bucket-divisible levels and within [s/2, s] otherwise)."""
    ch, cw = float(canvas_hw[0]), float(canvas_hw[1])

    def snap(ratio: float) -> float:
        import math

        return float(2 ** round(math.log2(ratio))) if ratio > 1.0 else 1.0

    def apply(x: jnp.ndarray) -> jnp.ndarray:
        fh, fw = x.shape[1], x.shape[2]
        sy, sx = snap(ch / fh), snap(cw / fw)
        rows = jnp.arange(fh, dtype=jnp.float32) * sy
        cols = jnp.arange(fw, dtype=jnp.float32) * sx
        ok = (rows[None, :] < im_info[:, 0, None])[:, :, None] & (
            cols[None, :] < im_info[:, 1, None]
        )[:, None, :]
        return x * ok[..., None].astype(x.dtype)

    return apply


def pad_feat_to_ladder(feat: jnp.ndarray, stride: int, shape_buckets):
    """Zero-pad a (B, H, W, C) feature map to the bucket ladder's max
    extent at this stride.

    Companion to :func:`make_pad_mask` for EXACT cross-bucket serving
    invariance: the masked feature values are canvas-independent, but the
    roi-align → heads subgraph still compiles per canvas shape, and XLA's
    shape-dependent scheduling can reassociate its reductions differently
    (observed at ~1e-6 on box deltas under multi-device CPU).  Padding
    the (masked) map to one ladder-wide shape gives that subgraph a
    single HLO signature — identical inputs, identical program, identical
    bits.  No-op when the canvas already reaches the ladder max (callers
    outside the ladder keep their shapes)."""
    if not shape_buckets:
        return feat
    th = max(feat.shape[1], max(-(-bh // stride) for bh, _ in shape_buckets))
    tw = max(feat.shape[2], max(-(-bw // stride) for _, bw in shape_buckets))
    if (th, tw) == (feat.shape[1], feat.shape[2]):
        return feat
    return jnp.pad(
        feat,
        ((0, 0), (0, th - feat.shape[1]), (0, tw - feat.shape[2]), (0, 0)),
    )


class _ConvKernel(nn.Module):
    """Parameter bank declaring an nn.Conv-compatible HWIO kernel.

    Same param name ("kernel"), shape, dtype, and initializer as the
    nn.Conv the unfused path builds, so a module that swaps between
    fused and unfused conv+BN keeps a byte-identical param tree."""

    features: int
    kernel: int

    @nn.compact
    def __call__(self, cin: int) -> jnp.ndarray:
        return self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (self.kernel, self.kernel, cin, self.features),
            jnp.float32,
        )


class _BNParams(nn.Module):
    """Parameter bank declaring FrozenBatchNorm's four tensors."""

    @nn.compact
    def __call__(self, c: int):
        scale = self.param("scale", nn.initializers.ones, (c,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (c,), jnp.float32)
        mean = self.param("mean", nn.initializers.zeros, (c,), jnp.float32)
        var = self.param("var", nn.initializers.ones, (c,), jnp.float32)
        return scale, bias, mean, var


def fused_conv_bn(
    x: jnp.ndarray,
    features: int,
    kernel: int,
    stride: int,
    dtype: Any,
    conv_name: str,
    bn_name: str,
    eps: float = BN_EPS,
) -> jnp.ndarray:
    """conv → FrozenBatchNorm with the BN affine folded into the kernel.

    Algebraically identical to the unfused pair — y = conv(x, W)·mul + add
    = conv(x, W·mul) + add since mul is per-output-channel — but the
    fold happens on the (tiny) weight tensor in f32 instead of the (huge)
    activation tensor, removing the activation-side multiply and its
    backward twin entirely.  Gradients flow to W and the BN affine
    through the fold arithmetic unchanged; mean/var stay stop_gradient'd
    exactly as in FrozenBatchNorm.  Param paths ({conv_name}/kernel,
    {bn_name}/{scale,bias,mean,var}) match the unfused modules, so
    checkpoints and the pretrained importer work with either path.

    Call only inside an @nn.compact parent (instantiates param banks)."""
    w = _ConvKernel(features, kernel, name=conv_name)(x.shape[-1])
    scale, bias, mean, var = _BNParams(name=bn_name)(features)
    mean = jax.lax.stop_gradient(mean)
    var = jax.lax.stop_gradient(var)
    mul = scale * jax.lax.rsqrt(var + eps)            # (cout,) f32
    w = (w * mul[None, None, None, :]).astype(dtype)
    add = (bias - mean * mul).astype(dtype)
    pad = (kernel - 1) // 2
    y = jax.lax.conv_general_dilated(
        x.astype(dtype),
        w,
        (stride, stride),
        [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return y + add


def make_conv_bn(fold: bool, dtype: Any):
    """→ ``cbn(x, features, kernel, stride, conv_name, bn_name)`` — ONE
    conv→frozen-BN wiring shared by the folded and unfused graphs, so a
    structural edit (stride placement, shortcut condition) can never be
    made on one side only.  Param paths are identical either way."""
    if fold:
        def cbn(x, features, kernel, stride, conv_name, bn_name):
            return fused_conv_bn(
                x, features, kernel, stride, dtype, conv_name, bn_name
            )
    else:
        def cbn(x, features, kernel, stride, conv_name, bn_name):
            y = conv(features, kernel, stride, dtype, name=conv_name)(x)
            return FrozenBatchNorm(dtype=dtype, name=bn_name)(y)
    return cbn


def conv(
    features: int,
    kernel: int,
    stride: int = 1,
    dtype: Any = jnp.float32,
    name: str | None = None,
    use_bias: bool = False,
    dilation: int = 1,
    kernel_init=nn.initializers.lecun_normal(),
) -> nn.Conv:
    """3x3/1x1/7x7 conv helper, NHWC, f32 params.

    Padding is explicit symmetric ``(k-1)//2`` — identical to SAME at
    stride 1, but at stride 2 SAME pads (0, 1) while every public
    ResNet/VGG checkpoint family (caffe/torch) pads symmetrically; the
    explicit form keeps imported pretrained weights spatially aligned.
    """
    pad = dilation * (kernel - 1) // 2
    return nn.Conv(
        features,
        (kernel, kernel),
        strides=(stride, stride),
        padding=((pad, pad), (pad, pad)),
        use_bias=use_bias,
        kernel_dilation=(dilation, dilation),
        kernel_init=kernel_init,
        dtype=dtype,
        param_dtype=jnp.float32,
        name=name,
    )


def per_image(fn, *args):
    """``jax.vmap(fn)(*args)`` over the leading (image) axis — except at
    batch 1, where ``fn`` runs on the one image without the batch axis.
    Same values either way; the shapes differ, and that is the point:
    the TPU compiler aborts in its TopK emitter on a ``[1, N]`` operand
    at the pyramid's finest level's N = 152·256·3 = 116736 (ROADMAP R1),
    while the rank-1 ``[N]`` and every batch ≥ 2 compile.  Every per-image
    ``top_k`` over anchors goes through here: the pyramid's proposals and
    both families' anchor targets (``ops/targets.py::_random_keep_k``)."""
    if args[0].shape[0] != 1:
        return jax.vmap(fn)(*args)
    out = fn(*(a[0] for a in args))
    return jax.tree_util.tree_map(lambda x: x[None], out)
