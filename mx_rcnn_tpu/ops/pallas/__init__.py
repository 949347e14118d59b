"""Pallas TPU kernels (ROIAlign resident + streaming, ROI max pooling, NMS)."""

import jax


def out_struct(shape, dtype, *inputs) -> jax.ShapeDtypeStruct:
    """``out_shape`` of a ``pallas_call`` whose result varies over the
    mesh axes its ``inputs`` vary over.  Under ``jax.shard_map`` (the DP
    train step) the replication checker needs that stated; outside one
    the set is empty."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in inputs))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
