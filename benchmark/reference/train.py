"""The reference's optimizer and train step: MXNet-style SGD (element-wise
clip, weight decay before momentum, momentum 0.9, constant lr inside the
few steps a check follows), frozen subtrees by name prefix.  Plain,
undonated, one step per call."""

from __future__ import annotations

from typing import Any, NamedTuple, Sequence, Tuple

import flax
import jax
import jax.numpy as jnp
import optax


#: the step's own counts over the rows of a batch (sums, not means): how
#: many anchors were labelled foreground, how many proposals survived
COUNTS = ("num_fg_anchors", "num_valid_props")


class TrainState(NamedTuple):
    step: jnp.ndarray
    params: Any
    opt_state: Any


def is_frozen_path(path: Tuple[str, ...], fixed_params: Sequence[str]) -> bool:
    """FIXED_PARAMS: whole subtrees by name prefix, every BN tensor
    ("bn"), and running stats always."""
    for comp in path:
        for pat in fixed_params:
            if pat == "bn":
                if "bn" in comp:
                    return True
            elif comp == pat or comp.startswith(pat):
                return True
    return path[-1] in ("mean", "var")


def frozen_labels(params, fixed_params):
    flat = flax.traverse_util.flatten_dict(params)
    return flax.traverse_util.unflatten_dict({
        k: "frozen" if is_frozen_path(k, fixed_params) else "train"
        for k in flat
    })


def make_optimizer(cfg, lr: float) -> optax.GradientTransformation:
    t = cfg.TRAIN
    sgd = optax.chain(
        optax.clip(t.CLIP_GRADIENT),
        optax.add_decayed_weights(t.WD),
        optax.trace(decay=t.MOMENTUM, nesterov=False),
        optax.scale(-lr),
    )
    return optax.multi_transform(
        {"train": sgd, "frozen": optax.set_to_zero()},
        lambda params: frozen_labels(params, cfg.network.FIXED_PARAMS),
    )


def make_train_step(model, tx, block_rows: int):
    """→ ``step(state, batch, rng) -> (state, aux, grads)``: one optimizer
    step over the whole batch, followed in blocks of ``block_rows`` rows so
    that float32 at the full size fits the chip.  The sampling rng is
    folded with the step count and each row draws the key the whole-batch
    step would give it; losses are normalised by rows, so the mean over
    equal blocks is the batch's loss and gradient; the counts of ``COUNTS``
    are sums over the rows."""

    @jax.jit
    def block_grads(params, block, rng, step, full_batch, row_offset):
        rng = jax.random.fold_in(rng, step)

        def loss_fn(p):
            return model.apply(
                {"params": p}, train=True, rngs={"sampling": rng},
                full_batch=full_batch, row_offset=row_offset, **block
            )

        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        return loss, {k: aux[k].astype(jnp.float32) for k in COUNTS}, grads

    block_grads = jax.jit(
        block_grads.__wrapped__, static_argnames=("full_batch", "row_offset"))

    @jax.jit
    def apply(state: TrainState, grads):
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        return TrainState(
            state.step + 1, optax.apply_updates(state.params, updates),
            opt_state)

    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))

    def step(state: TrainState, batch, rng):
        rows = next(iter(batch.values())).shape[0]
        if rows % block_rows:
            raise ValueError(f"{rows} rows in blocks of {block_rows}")
        n = rows // block_rows
        loss_sum, grad_sum, counts = 0.0, None, None
        for i in range(n):
            block = {k: v[i * block_rows:(i + 1) * block_rows]
                     for k, v in batch.items()}
            loss, c, grads = block_grads(
                state.params, block, rng, state.step, rows, i * block_rows)
            loss_sum = loss_sum + loss
            counts = c if counts is None else add(counts, c)
            grad_sum = grads if grad_sum is None else add(grad_sum, grads)
        grads = jax.tree_util.tree_map(lambda g: g / n, grad_sum)
        return apply(state, grads), dict(counts, loss=loss_sum / n), grads

    return step
