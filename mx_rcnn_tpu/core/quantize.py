"""int8 weight-only quantization for the serve-graph compression ladder.

The second rung below bf16 (ISSUE 18): every weight matrix/kernel is
stored as int8 with a per-output-channel symmetric scale, and the serve
graph dequantizes on use — ``q.astype(f32) * scale`` runs INSIDE the
jit, on device, as the first op touching each weight.  Activations and
accumulation stay f32 (weight-only quantization), so the numerics are
the f32 graph's with ~2^-7 relative weight error — small enough to pass
the same warmup detection/mask parity gate that guards bf16, which is
exactly the contract: a rung that drifts refuses to serve.

Quantization layout
-------------------

Flax puts the output-channel axis LAST on every kernel this repo builds
(conv ``(kh, kw, in, out)``, dense ``(in, out)``), so the scale is the
per-last-axis absmax over 127 with ``keepdims=True`` — dequantization is
a plain broadcast multiply for any rank.  Only floating leaves with
``ndim >= 2`` quantize (the weights); biases, BN affine/stats, and other
vectors stay f32 untouched — they are a rounding error of the tree's
bytes and per-channel scaling of a 1-D leaf would be a no-op identity
anyway.

A quantized leaf is a plain dict ``{"int8_q": int8[...], "int8_scale":
f32[..., 1-per-channel]}`` — a pytree CONTAINER, not a custom node, so
the quantized tree flattens/maps/device_puts with stock jax utilities
and ``jax.jit`` traces both arrays as ordinary arguments.  The tree's
structure is therefore a pure function of the f32 tree's structure:
the registry's swap-time structure gate (f32 vs f32) remains the single
source of truth, and every runner quantizing the same version gets the
same treedef (compile-cache keys stay stable across hot-swaps).

Scales are computed and folded once at registry load/restore
(:meth:`~mx_rcnn_tpu.serve.registry.ModelRegistry.quantized_tree`
caches per ``(model, version)``), never per replica and never on the
predict path.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

#: the two keys that make a dict a quantized-leaf container — checked
#: exactly (a params sub-dict that happened to carry these names would
#: be a collision; no flax module in this repo names params this way)
QKEYS = frozenset({"int8_q", "int8_scale"})


def is_quantized_leaf(x: Any) -> bool:
    """True for the ``{"int8_q", "int8_scale"}`` container produced by
    :func:`quantize_leaf` (usable as a ``tree_map`` ``is_leaf``)."""
    return isinstance(x, dict) and set(x.keys()) == QKEYS


def quantize_leaf(w: np.ndarray) -> Dict[str, np.ndarray]:
    """One weight array → per-output-channel symmetric int8.

    ``scale[c] = absmax(w[..., c]) / 127`` (keepdims, so dequantization
    broadcasts for any rank); zero channels get scale 1.0 so the
    round-trip is exact zeros instead of 0/0."""
    w = np.asarray(w, np.float32)
    reduce_axes = tuple(range(w.ndim - 1))
    amax = np.max(np.abs(w), axis=reduce_axes, keepdims=True)
    scale = (amax / 127.0).astype(np.float32)
    scale = np.where(scale > 0.0, scale, np.float32(1.0)).astype(np.float32)
    q = np.clip(np.rint(w / scale), -127, 127).astype(np.int8)
    return {"int8_q": q, "int8_scale": scale}


def _should_quantize(leaf: Any) -> bool:
    arr = np.asarray(leaf)
    return arr.ndim >= 2 and np.issubdtype(arr.dtype, np.floating)


def quantize_tree(params: Any) -> Any:
    """f32 params tree → mixed tree: every ``ndim >= 2`` float leaf
    becomes a quantized-leaf container, everything else passes through
    as float32 numpy (host-side — device placement is the caller's job,
    same as the f32 restore path)."""
    import jax

    def q(leaf):
        if _should_quantize(leaf):
            return quantize_leaf(np.asarray(leaf))
        return np.asarray(leaf)

    return jax.tree_util.tree_map(q, params)


def dequantize_tree(params: Any) -> Any:
    """Mixed quantized tree → f32 tree, jit-traceable: inside a jit the
    multiply lowers to one broadcast op per weight, fused by XLA into
    the consuming conv/matmul — this is the serve graph's
    dequantize-on-use."""
    import jax

    def dq(x):
        if is_quantized_leaf(x):
            return x["int8_q"].astype(np.float32) * x["int8_scale"]
        return x

    return jax.tree_util.tree_map(dq, params, is_leaf=is_quantized_leaf)

