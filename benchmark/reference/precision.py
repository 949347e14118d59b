"""The lower-precision control: the reference with every convolution's
and matrix product's operands rounded to a narrower type (and computed in
float32 from there).  ``rounded_operands(None)`` changes nothing."""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

_DTYPES = {
    "bfloat16": jnp.bfloat16,
    "float8_e4m3fn": jnp.float8_e4m3fn,
}


def _round(x, dtype):
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return x
    if dtype == jnp.float8_e4m3fn:
        # fp8 has no headroom for unnormalised activations: scale each
        # tensor into range first, as an fp8 path would
        amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        top = float(jnp.finfo(dtype).max)
        scale = jax.lax.stop_gradient(top / amax).astype(x.dtype)
        return ((x * scale).astype(dtype).astype(x.dtype)) / scale
    return x.astype(dtype).astype(x.dtype)


@contextlib.contextmanager
def rounded_operands(dtype_name):
    """Inside: ``jax.lax.conv_general_dilated`` and ``jax.lax.dot_general``
    (what flax's Conv, ConvTranspose and Dense and the reference's own
    folded conv call) round both operands to ``dtype_name`` first.  Must
    enclose the tracing of whatever is to be rounded."""
    if dtype_name is None:
        yield
        return
    dtype = _DTYPES[dtype_name]
    conv, dot = jax.lax.conv_general_dilated, jax.lax.dot_general
    conv_t = jax.lax.conv_transpose

    def conv_r(lhs, rhs, *a, **kw):
        return conv(_round(lhs, dtype), _round(rhs, dtype), *a, **kw)

    def dot_r(lhs, rhs, *a, **kw):
        return dot(_round(lhs, dtype), _round(rhs, dtype), *a, **kw)

    def conv_t_r(lhs, rhs, *a, **kw):
        return conv_t(_round(lhs, dtype), _round(rhs, dtype), *a, **kw)

    jax.lax.conv_general_dilated, jax.lax.dot_general = conv_r, dot_r
    jax.lax.conv_transpose = conv_t_r
    try:
        yield
    finally:
        jax.lax.conv_general_dilated, jax.lax.dot_general = conv, dot
        jax.lax.conv_transpose = conv_t
