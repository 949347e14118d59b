"""Data parallelism over a device mesh: the KVStore('device') replacement.

Reference: MXNet ``kvstore='device'`` single-node gradient allreduce +
``AnchorLoader``'s per-GPU batch slicing (SURVEY §3.3, §5.8).  Here the
whole trainer is one ``shard_map`` over a ``Mesh(('data',))``: each chip
runs the identical train step on its batch shard, gradients/metrics are
``pmean``-ed — XLA lowers that to an ICI all-reduce within a slice and
DCN collectives across slices, so the same ten lines scale from 1 chip to
a multi-host pod (where the reference was hardcoded single-node).

Axis layout (scaling-book recipe): batch sharded on ``'data'``; params
and optimizer state replicated.  The mesh carries reserved axes for
tensor/pipeline extensions (`model`) so configs can evolve without
re-plumbing.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mx_rcnn_tpu.core.train import TrainState, make_train_step


def make_mesh(
    n_data: Optional[int] = None, n_model: int = 1, devices=None
) -> Mesh:
    """('data', 'model') mesh over all (or the given) devices."""
    devices = np.asarray(devices if devices is not None else jax.devices())
    if n_data is None:
        n_data = devices.size // n_model
    assert n_data * n_model == devices.size, (
        f"{devices.size} devices cannot form ({n_data}, {n_model}) mesh"
    )
    return Mesh(devices.reshape(n_data, n_model), ("data", "model"))


def replica_slices(n_replicas: Optional[int] = None, devices=None) -> list:
    """Device assignment for a serving replica pool: replica i runs on
    ``slices[i % len(slices)]``.

    Serving replication is the transpose of the training mesh: training
    shards ONE batch across all devices, a replica pool pins ONE
    independent predictor per device (params committed via
    ``jax.device_put(params, device)``, so every jit it traces executes
    there).  With ``n_replicas`` ≤ device count each replica owns a
    device exclusively; beyond that they round-robin share (the CPU test
    topology: 8 virtual devices, pools of any size).
    """
    devs = list(devices if devices is not None else jax.devices())
    if n_replicas is None or n_replicas >= len(devs):
        return devs
    return devs[:n_replicas]


def replicate(tree, mesh: Mesh):
    """Replicate a pytree (params/opt state) across the mesh."""
    sharding = NamedSharding(mesh, P())
    return jax.device_put(tree, sharding)


def shard_batch(batch: Dict[str, np.ndarray], mesh: Mesh):
    """Shard the leading (batch) axis of every array across 'data'."""
    sharding = NamedSharding(mesh, P("data"))
    return jax.device_put(batch, sharding)


def take_replica_rows(batch: Dict, n_active: int, n_base: int) -> Dict:
    """Truncate a base-mesh global batch to ``n_active`` of ``n_base``
    replicas' worth of leading-axis rows.

    The elastic shrink path (``parallel/elastic.py``) keeps the data
    loader's plan at the BASE global batch size — re-planning mid-epoch
    would invalidate the deterministic shuffle/bucketing stream — and
    instead drops the tail rows of each global batch.  Always the tail,
    never the dead replica's slice: the kept prefix is then a pure
    function of the survivor COUNT, so a fresh small-mesh run fed the
    same stream consumes bit-identical batches regardless of which
    ordinal died.
    """
    if n_active == n_base:
        return batch
    out = {}
    for k, v in batch.items():
        rows = np.shape(v)[0]
        if rows % n_base:
            raise ValueError(
                f"batch key {k!r}: {rows} rows not divisible by the "
                f"{n_base}-replica base mesh"
            )
        out[k] = v[: rows * n_active // n_base]
    return out


def make_parallel_train_step(
    model, tx, mesh: Mesh, accum_steps: int = 1, donate: bool = True,
):
    """The DP train step: per-chip compute + pmean on grads/metrics.

    Batch arrays arrive sharded on 'data'; state replicated.  Since the
    grads are pmean-ed inside, the updated state stays replicated — the
    invariant KVStore maintained with explicit broadcasts.
    ``accum_steps`` applies per chip (each shard is scanned into that
    many microbatches before its gradient joins the all-reduce).
    ``donate`` mirrors ``make_train_step``'s knob (same default: the
    input state is donated; rollback paths re-place from host
    snapshots, never reuse a donated buffer).
    """
    inner = make_train_step(model, tx, pmean_axis="data", accum_steps=accum_steps)

    state_spec = P()   # replicated
    batch_spec = P("data")

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(state_spec, batch_spec, state_spec, P()),
        out_specs=(state_spec, state_spec),
        # check_vma stays ON (the default): it is what makes autodiff
        # psum the replicated params' cotangents (core/train.py divides
        # by the axis size and relies on it).  With it off this step
        # trains on unsynchronised gradients — test_dp_grads_match_
        # single_device fails by 16% of a kernel's elements
    )
    def sharded_step(state: TrainState, batch, rng, lr_scale):
        # sampling decorrelation across chips: batches carrying per-image
        # sample_seeds decorrelate by construction (and identically to a
        # single-chip run — the DP-equivalence invariant); seedless batches
        # fall back to folding in the chip index
        if "sample_seeds" not in batch:
            rng = jax.random.fold_in(rng, jax.lax.axis_index("data"))
        return inner(state, batch, rng, lr_scale)

    jitted = jax.jit(sharded_step, donate_argnums=(0,) if donate else ())

    def step(state: TrainState, batch, rng, lr_scale=1.0):
        # lr_scale: one-step effective-LR override (replicated scalar) —
        # the guarded loop's divergence-retry backoff.  ×1.0 is exact in
        # f32, so the default path is bit-identical to the unscaled step.
        return jitted(state, batch, rng, jnp.float32(lr_scale))

    return step
