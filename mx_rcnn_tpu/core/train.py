"""Trainer: optimizer, parameter freezing, jitted train step.

Reference: the ``MutableModule.fit`` + SGD + KVStore('device') stack of
``train_end2end.py :: train_net`` and ``rcnn/core/module.py`` (SURVEY
§4.1).  TPU-native shape: one pure ``train_step`` (value_and_grad →
element-wise clip → wd → momentum → piecewise lr), jitted per shape
bucket; data parallelism is the same function under ``shard_map`` with a
``psum`` on grads (``mx_rcnn_tpu/parallel``) — the comm backend is the
compiler.

Optimizer semantics follow MXNet SGD:
- gradient clipped element-wise to ±CLIP_GRADIENT (MXNet ``clip_gradient``),
- weight decay added to the gradient *before* momentum (MXNet SGD),
- momentum 0.9, piecewise-constant lr (MultiFactorScheduler),
- frozen params (FIXED_PARAMS) get zero updates via an optax mask.
One knowing deviation: lr is applied *after* the momentum accumulator
(optax.trace then scale), while MXNet folds lr into the momentum update —
at an LR_FACTOR boundary the existing momentum buffer is rescaled by the
new lr here, so the two diverge transiently for ~1/(1-momentum) steps.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Sequence, Tuple

import flax
import jax
import jax.numpy as jnp
import optax

from mx_rcnn_tpu.config import Config


class TrainState(NamedTuple):
    step: jnp.ndarray
    params: Any
    opt_state: Any


def is_frozen_path(path: Tuple[str, ...], fixed_params: Sequence[str]) -> bool:
    """Reference FIXED_PARAMS semantics: freeze whole subtrees by name
    prefix (conv0/stage1/conv1...) plus every BN affine/stat network-wide
    (the reference lists gamma/beta; our FrozenBatchNorm names them
    scale/bias/mean/var under modules containing 'bn')."""
    for comp in path:
        for pat in fixed_params:
            if pat == "bn":
                if "bn" in comp:
                    return True
            elif comp == pat or comp.startswith(pat):
                return True
    # running stats are never trainable regardless of config
    return path[-1] in ("mean", "var")


def make_optimizer(
    cfg: Config,
    lr_schedule: Callable[[jnp.ndarray], jnp.ndarray],
    fixed_params: tuple | None = None,
) -> optax.GradientTransformation:
    """``fixed_params`` overrides the freeze set (stage-2 alternate
    training freezes FIXED_PARAMS_SHARED instead of FIXED_PARAMS)."""
    t = cfg.TRAIN
    fixed = cfg.network.FIXED_PARAMS if fixed_params is None else fixed_params
    sgd = optax.chain(
        optax.clip(t.CLIP_GRADIENT),
        optax.add_decayed_weights(t.WD),
        optax.trace(decay=t.MOMENTUM, nesterov=False),
        optax.scale_by_schedule(lambda step: -lr_schedule(step)),
    )

    def label_fn(params):
        flat = flax.traverse_util.flatten_dict(params)
        labels = {
            k: "frozen" if is_frozen_path(k, fixed) else "train"
            for k in flat
        }
        return flax.traverse_util.unflatten_dict(labels)

    return optax.multi_transform(
        {"train": sgd, "frozen": optax.set_to_zero()}, label_fn
    )


def make_lr_schedule(cfg: Config, steps_per_epoch: int) -> Callable:
    """MultiFactorScheduler twin: lr × LR_FACTOR at each LR_STEP epoch."""
    t = cfg.TRAIN
    boundaries = {
        int(e * steps_per_epoch): t.LR_FACTOR for e in t.LR_STEP_EPOCHS
    }
    return optax.piecewise_constant_schedule(t.LEARNING_RATE, boundaries)


def create_train_state(params, tx: optax.GradientTransformation) -> TrainState:
    return TrainState(jnp.zeros((), jnp.int32), params, tx.init(params))


def make_train_step(
    model,
    tx: optax.GradientTransformation,
    donate: bool = True,
    pmean_axis: str | None = None,
    accum_steps: int = 1,
    fold_step_rng: bool = True,
    steps_per_call: int = 1,
):
    """Build the jitted train step.

    ``pmean_axis``: when running under shard_map/pmap, the named mesh axis
    to average grads/metrics over (the KVStore('device') replacement);
    None for single-chip.

    ``accum_steps`` > 1 splits the batch's leading axis into that many
    microbatches and averages their gradients under ``lax.scan`` before
    the single optimizer update — the big-effective-batch path when
    activations don't fit (the reference had no analog).  With per-image
    ``sample_seeds`` in the batch the update equals the unaccumulated
    step exactly (same linearity argument as DP equivalence).

    ``steps_per_call`` > 1 runs that many FULL optimizer steps under one
    ``lax.scan`` per jit dispatch, over a batch pytree with an extra
    leading ``steps_per_call`` axis (stack per-step batches with
    :func:`stack_batches`).  Exactly equivalent to the same number of
    single-step calls — each scan iteration folds the advancing
    ``state.step`` into the sampling rng — but the host dispatches once
    per K steps.  This is the device-side training loop: K amortizes
    the host's per-dispatch cost, and the host's only per-K-step job is
    feeding the next stacked batch.  Aux metrics come back
    stacked ``[K, ...]`` so per-step logging survives.

    ``fold_step_rng=False`` keeps the sampling rng CONSTANT across steps
    (no fold_in of state.step): with per-image ``sample_seeds`` every
    image's roi/anchor subsample is then identical every step — the
    zero-label-churn ablation mode (tests/test_fpn.py).

    The returned step additionally accepts an optional ``lr_scale``
    keyword (default None = untouched): a scalar multiplied into the
    final updates, i.e. a one-step effective-LR override.  The guarded
    loop (core/resilience.py) uses it for exponential LR backoff when
    retrying a diverged step; momentum accumulation is deliberately NOT
    rescaled (the retry should damp this step, not rewrite history).
    """
    if steps_per_call > 1 and pmean_axis is not None:
        raise ValueError(
            "steps_per_call > 1 under a pmean_axis is unsupported: "
            "shard_map callers shard the batch's leading axis, which here "
            "would silently be the K-steps axis — keep steps_per_call=1 "
            "under data parallelism until the combo is tested"
        )

    def _grads_and_aux(params, batch, rng):
        def loss_fn(p):
            # batch keys match the model __call__ signature (images,
            # im_info, gt_boxes, gt_valid [, proposals, prop_valid]) so
            # one step builder serves FasterRCNN / RPNOnly / FastRCNN
            loss, aux = model.apply(
                {"params": p}, train=True, rngs={"sampling": rng}, **batch
            )
            return loss, aux

        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        aux = dict(aux)
        aux["loss"] = loss
        return grads, aux

    def step_fn(
        state: TrainState,
        batch: Dict[str, jnp.ndarray],
        rng: jax.Array,
        lr_scale=None,
    ):
        if fold_step_rng:
            rng = jax.random.fold_in(rng, state.step)

        if accum_steps == 1:
            grads, aux = _grads_and_aux(state.params, batch, rng)
        else:
            micro = jax.tree_util.tree_map(
                lambda x: x.reshape((accum_steps, -1) + x.shape[1:]), dict(batch)
            )
            # same DP-equivalence convention as parallel/mesh.py: batches
            # carrying per-image sample_seeds draw identically to the
            # unaccumulated step from ONE shared rng; seedless batches
            # decorrelate microbatches by folding in the index
            if "sample_seeds" in batch:
                rngs = jnp.broadcast_to(
                    jax.random.key_data(rng),
                    (accum_steps,) + jax.random.key_data(rng).shape,
                )
                rngs = jax.vmap(jax.random.wrap_key_data)(rngs)
            else:
                rngs = jax.vmap(lambda i: jax.random.fold_in(rng, i))(
                    jnp.arange(accum_steps)
                )

            def body(_, inp):
                mb, r = inp
                g, aux = _grads_and_aux(state.params, mb, r)
                aux = {k: v.astype(jnp.float32) for k, v in aux.items()}
                return None, (g, aux)

            _, (g_stack, aux_stack) = jax.lax.scan(body, None, (micro, rngs))
            grads = jax.tree_util.tree_map(lambda g: g.mean(0), g_stack)
            aux = jax.tree_util.tree_map(lambda a: a.mean(0), aux_stack)
        if pmean_axis is not None:
            # Under shard_map, params arrive replicated (device-invariant)
            # while the loss is device-varying, so autodiff's transpose
            # rule has ALREADY psum-med the param cotangents across the
            # axis — an explicit pmean here would be a no-op on the sum,
            # silently training with sum-reduced (axis_size×) gradients.
            # Divide by the axis size to get the mean; the exact
            # DP-vs-single-device equality test guards this invariant.
            n = jax.lax.psum(1, pmean_axis)
            grads = jax.tree_util.tree_map(lambda g: g / n, grads)
            aux = jax.lax.pmean(
                {k: v.astype(jnp.float32) for k, v in aux.items()}, pmean_axis
            )
        # clip, weight decay, momentum, apply: one stage of the device
        # trace (utils/tracing.py :: TRAIN_SCOPES); metadata only
        with jax.named_scope("update"):
            updates, opt_state = tx.update(grads, state.opt_state, state.params)
            if lr_scale is not None:
                s = jnp.asarray(lr_scale, jnp.float32)
                updates = jax.tree_util.tree_map(
                    lambda u: u * s.astype(u.dtype), updates
                )
            params = optax.apply_updates(state.params, updates)
        new_state = TrainState(state.step + 1, params, opt_state)
        return new_state, aux

    if steps_per_call > 1:
        def multi_fn(state, batches, rng, lr_scale=None):
            def body(st, mb):
                return step_fn(st, mb, rng, lr_scale)

            return jax.lax.scan(body, state, batches)

        fn = multi_fn
    else:
        fn = step_fn
    if pmean_axis is not None:
        return fn  # caller wraps in shard_map then jit
    return jax.jit(fn, donate_argnums=(0,) if donate else ())


def stack_batches(batches: Sequence[Dict[str, jnp.ndarray]]) -> Dict[str, Any]:
    """Stack K per-step batches along a new leading axis for a
    ``steps_per_call=K`` train step (host-side numpy stack: the result
    crosses host→device once, as one transfer)."""
    import numpy as np

    return {
        k: np.stack([np.asarray(b[k]) for b in batches])
        for k in batches[0]
    }
