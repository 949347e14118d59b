"""What decides ``correct`` for a train cell.

The timed step's own first steps (taken by ``train_driver.StepHook`` from
the object the window then drives) against the plain reference
(``benchmark/reference``: jnp gather ROIAlign, sequential jnp NMS, float32
at ``highest``), which makes its own weights from the seed, rebuilds each
checked batch itself from the record numbers its rows carry, and follows
them with the same sampling key.

Numbers compared, each with a limit of its own (``limits/<cell>.json``):

- ``batch_gap``     the largest difference between a batch as the loader
                    fed it and the same records rebuilt by the reference
- ``fg_anchors_gap``, ``props_gap``  the first step's counts over the rows
                    of its batch (anchors labelled foreground, proposals
                    that survived): sums, so a row left out shows
- ``loss<k>_gap``   |program − reference| / |reference| of step k's loss
- ``grad1_gap``     worst leaf of the first gradient as the optimizer got
                    it (momentum buffer after step 1: clip(g) + wd·p)
- ``dparam_gap``    worst leaf of the parameters' change after the last
                    checked step

The leaf measure is the gap between the two norms, over the reference's
norm of that leaf or of the median leaf, whichever is larger.  Leaves
whose reference gradient is under a thousandth of the median leaf's move
by round-off alone and are left out of ``dparam_gap`` by that rule.
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import Any, Dict, List, Optional


def apply_overrides(cfg, overrides: Optional[Dict[str, Dict[str, Any]]]):
    """``{"": {top-level}, "TRAIN": {...}, "dataset": {...}}`` onto a
    Config of either package (the program's or the reference's)."""
    if not overrides:
        return cfg
    top = dict(overrides.get("", {}))
    for group, fields in overrides.items():
        if group:
            top[group] = dataclasses.replace(getattr(cfg, group), **_tuples(fields))
    return cfg.replace(**_tuples(top))


def _tuples(fields):
    def conv(v):
        return tuple(conv(x) for x in v) if isinstance(v, list) else v

    return {k: conv(v) for k, v in fields.items()}


def reference_config(config: Dict[str, Any], traffic: Dict[str, Any],
                     overrides=None):
    """The reference's Config for this cell: the configuration's network
    and dataset, the traffic's batch and lr; compute stays float32."""
    from reference.config import generate_config

    cfg = generate_config(config["network"], config["dataset"])
    train = {"BATCH_IMAGES": int(traffic["batch_images"])}
    if traffic.get("lr") is not None:
        train["LEARNING_RATE"] = float(traffic["lr"])
    cfg = cfg.replace(TRAIN=dataclasses.replace(cfg.TRAIN, **train))
    return apply_overrides(cfg, overrides)


def reference_readings(cfg, check_input: Dict[str, Any], graph: str,
                       round_to: Optional[str] = None,
                       fault: Optional[str] = None,
                       block_rows: Optional[int] = None) -> Dict[str, Any]:
    """The reference through the same steps → losses, per-leaf norms of
    the momentum after step 1, of the raw first gradient, and of the
    parameters' change after the last step.

    ``round_to`` computes every convolution and matrix product on operands
    rounded to that dtype (the lower-precision control); ``fault`` plants
    one of the faults a train cell can have (``half_batch``: the second
    half of the rows left out, the mean taken over the rest)."""
    import jax
    import jax.numpy as jnp

    from harness.train_driver import leaf_norms, momentum_of
    from reference.models import build_model
    from reference.precision import rounded_operands
    from reference.train import TrainState, make_optimizer, make_train_step

    batches = check_input["batches"]
    seed = int(check_input["seed"])
    if fault == "half_batch":
        batches = [
            {k: v[: max(1, v.shape[0] // 2)] for k, v in b.items()}
            for b in batches
        ]
    elif fault is not None:
        raise ValueError(f"unknown fault {fault!r}")
    model = build_model(cfg, graph)
    h, w = cfg.SHAPE_BUCKETS[0]
    g = cfg.dataset.MAX_GT_BOXES
    tx = make_optimizer(cfg, cfg.TRAIN.LEARNING_RATE)
    rng = jax.random.wrap_key_data(jnp.asarray(check_input["rng_data"]))
    with jax.default_matmul_precision("highest"), rounded_operands(round_to):
        params = jax.jit(lambda: model.init(
            {"params": jax.random.key(seed), "sampling": jax.random.key(1)},
            jnp.zeros((1, h, w, 3), jnp.float32),
            jnp.array([[h, w, 1.0]], jnp.float32),
            jnp.zeros((1, g, 5), jnp.float32),
            jnp.zeros((1, g), bool), train=True,
        )["params"])()
        p0 = params
        state = TrainState(jnp.zeros((), jnp.int32), params, tx.init(params))
        rows = next(iter(batches[0].values())).shape[0]
        block = int(block_rows) if block_rows else rows
        step = make_train_step(model, tx, min(block, rows))
        losses: List[float] = []
        counts: List[Dict[str, float]] = []
        grad1 = raw1 = None
        for i, batch in enumerate(batches):
            state, aux, grads = step(
                state, {k: jnp.asarray(v) for k, v in batch.items()}, rng)
            losses.append(float(aux["loss"]))
            counts.append({k: float(v) for k, v in aux.items() if k != "loss"})
            if i == 0:
                grad1 = leaf_norms(momentum_of(state.opt_state))
                raw1 = leaf_norms(grads)
            del grads
        dparam = leaf_norms(
            jax.tree_util.tree_map(lambda a, b: a - b, state.params, p0))
    return {"losses": losses, "counts": counts, "grad1": grad1,
            "raw_grad1": raw1, "dparam": dparam}


def worst_leaf_gap(got: Dict[str, float], ref: Dict[str, float],
                   leaves: Optional[List[str]] = None):
    """→ (gap, leaf): the largest |got − ref| / max(ref, median ref) over
    ``leaves`` (default: all of the reference's)."""
    names = leaves if leaves is not None else sorted(ref)
    if set(got) != set(ref):
        missing = sorted(set(ref) ^ set(got))[:5]
        raise RuntimeError(f"leaf sets differ, e.g. {missing}")
    med = statistics.median(ref[n] for n in names)
    worst, where = 0.0, ""
    for n in names:
        gap = abs(got[n] - ref[n]) / max(ref[n], med, 1e-30)
        if not gap <= worst:  # NaN counts as the worst
            worst, where = gap, n
    return float(worst), where


def moving_leaves(ref: Dict[str, Any]) -> List[str]:
    """Leaves the optimizer moves and whose reference gradient is at least
    a thousandth of the median leaf's."""
    trained = sorted(ref["grad1"])
    med = statistics.median(ref["raw_grad1"][n] for n in trained)
    return [n for n in trained if ref["raw_grad1"][n] >= 1e-3 * med]


def readings(program: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, Any]:
    """Every number compared, by name, with where the worst leaf was."""
    out: Dict[str, Any] = {}
    for k, (a, b) in enumerate(zip(program["losses"], ref["losses"]), 1):
        out[f"loss{k}_gap"] = abs(a - b) / abs(b) if b == b and b else float("nan")
    # the first step's counts over the rows of the batch
    for name, short in (("num_fg_anchors", "fg_anchors_gap"),
                        ("num_valid_props", "props_gap")):
        a = (program.get("counts") or [{}])[0].get(name)
        b = ref["counts"][0].get(name)
        if a is not None and b is not None:
            out[short] = abs(a - b) / max(b, 1.0)
    leaves = moving_leaves(ref)
    out["grad1_gap"], out["grad1_leaf"] = worst_leaf_gap(
        {n: program["grad1"][n] for n in ref["grad1"]}, ref["grad1"])
    trained = set(ref["grad1"])
    out["dparam_gap"], out["dparam_leaf"] = worst_leaf_gap(
        {n: v for n, v in program["dparam"].items() if n in trained},
        {n: v for n, v in ref["dparam"].items() if n in trained},
        leaves,
    )
    return out


def compare(read: Dict[str, Any], limits: Dict[str, float]):
    """→ [(name, value, limit, ok)] for every limit the cell's file sets; a
    reading that is missing or not a number fails."""
    rows = []
    for name, limit in limits.items():
        value = read.get(name, float("nan"))
        rows.append((name, float(value), float(limit), bool(value <= limit)))
    return rows


def own_batches(cfg, check_input: Dict[str, Any]):
    """The checked batches rebuilt by the reference from the record numbers
    each row carries (``sample_seeds``) → (batches, batch_gap): the
    largest difference from what the loader fed the step."""
    import numpy as np

    from reference import data

    d = check_input["data"]
    roidb = data.synthetic_roidb(
        d["synthetic"], cfg.dataset.NUM_CLASSES, cfg.TRAIN.FLIP and d["flip"])
    own, gap = [], 0.0
    for fed in check_input["batches"]:
        if "sample_seeds" not in fed:
            return check_input["batches"], float("inf")
        own.append(data.make_batch(roidb, np.asarray(fed["sample_seeds"]), cfg,
                                   fed["images"].shape[1:3]))
        gap = max(gap, data.batch_gap(fed, own[-1]))
    return own, gap


def follow(cell, check_input: Dict[str, Any], overrides=None, **how):
    """The reference through the checked steps on batches of its own
    making → (reference's readings, batch_gap).  ``how``: ``round_to`` or
    ``fault`` of :func:`reference_readings`."""
    cfg = reference_config(cell.config, cell.traffic, overrides)
    own, gap = own_batches(cfg, check_input)
    ref = reference_readings(
        cfg, dict(check_input, batches=own), cell.config["model"]["graph"],
        block_rows=cell.traffic.get("reference_block_rows"), **how)
    return ref, gap


def check(cell, check_input: Dict[str, Any], overrides=None):
    """→ (rows, detail) for one run of a train cell."""
    ref, gap = follow(cell, check_input, overrides)
    read = readings(check_input, ref)
    read["batch_gap"] = gap
    detail = {
        "program_losses": check_input["losses"],
        "reference_losses": ref["losses"],
        "program_counts": check_input["counts"],
        "reference_counts": ref["counts"],
        "grad1_leaf": read["grad1_leaf"], "dparam_leaf": read["dparam_leaf"],
    }
    return compare(read, cell.limits["limits"]), detail
