"""Serve cells: ``mx_rcnn_tpu.tools.serve.build_stack`` → registry, runner,
engine as the CLI builds them, warmed by the engine's own ``start()``,
then driven through ``ServingEngine.submit`` by the benchmark's generator.

Set-up: imports, weights from the seed (``random_params`` is handed the
seed - the CLI hard-wires 0 - and the mix's weight recipe, see
``harness/weights.py``), the ladder's compiles or cache loads, the
image pool, and one reply per rung through the whole engine path.  The
window: the traffic mix for ``--seconds``, then every reply awaited.
Nothing compiles inside it (the runner's compile-cache misses and JAX's
own compile events are read at both ends).
"""

from __future__ import annotations

import collections
import os
import tempfile
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from harness import loadgen, weights
from harness.stats import window_rate
from harness.train_driver import SEED_MOD


def serve_argv(cell) -> List[str]:
    return list(cell.config["serve_argv"]) + list(cell.traffic["argv"])


def finite_reply(dets) -> bool:
    """Per-class (n, 5) arrays, background first (None): all finite."""
    return all(d is None or np.isfinite(d).all() for d in dets)


def run(cell, seed: int, seconds: float, trace: bool, clock, t_process: float,
        patch_cli: Optional[Callable[[Any], None]] = None) -> Dict[str, Any]:
    import jax

    from mx_rcnn_tpu.serve.batcher import QueueFull
    from mx_rcnn_tpu.tools import serve as cli
    from mx_rcnn_tpu.utils.platform import cli_bootstrap

    traffic = cell.traffic
    pseed = seed % SEED_MOD
    tmp = tempfile.mkdtemp(prefix="bench_serve_")
    trace_dir = os.path.join(tmp, "trace") if trace else None
    if trace:
        seconds = min(seconds, float(traffic.get("trace_seconds", seconds)))
    cli_bootstrap()
    saved = {n: getattr(cli, n)
             for n in ("random_params", "generate_config", "build_stack")}
    make_params = cli.random_params
    cli.random_params = lambda model, cfg, _seed=0: weights.condition(
        make_params(model, cfg, pseed), traffic.get("weights"))
    if patch_cli is not None:
        patch_cli(cli)
    try:
        p, args = cli.parse_args(serve_argv(cell))
        stack = cli.build_stack(p, args)
    finally:
        for n, fn in saved.items():
            setattr(cli, n, fn)
    runner, engine = stack.runner, stack.engine
    pool = loadgen.make_pool(traffic, pseed)
    with engine:  # start() compiles or loads every rung of the ladder
        warm_misses = runner.compile_cache.misses
        # one reply per distinct size through the whole engine path
        seen = {}
        for im in pool:
            seen.setdefault(im.shape[:2], im)
        for im in seen.values():
            engine.submit(im).result(timeout=600.0)
        before = engine.snapshot()
        marks = [clock.mark(), None]
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
        in_use = [0]

        def submit(im):
            fut = engine.submit(im)
            stats = jax.local_devices()[0].memory_stats() or {}
            in_use[0] = max(in_use[0], stats.get("bytes_in_use", 0))
            return fut

        records, t0, t_end = loadgen.drive(
            submit, pool, traffic, seconds, pseed, refused=(QueueFull,),
            **({"span": jax.profiler.TraceAnnotation} if trace_dir else {}))
        if trace_dir:
            jax.profiler.stop_trace()
        marks[1] = clock.mark()
        after = engine.snapshot()
        window_misses = runner.compile_cache.misses - warm_misses
        ladder = [tuple(b) for b in runner.ladder]
    good = [r for r in records
            if r["outcome"] == "ok" and finite_reply(r["dets"])]
    real = after["batches"]["real_images"] - before["batches"]["real_images"]
    slots = after["batches"]["slots"] - before["batches"]["slots"]
    fault_keys = ("failed", "rejected", "expired", "retried", "shed",
                  "stopped", "invalid", "poisoned", "exhausted", "resubmitted")
    faults = {k: after["requests"][k] - before["requests"][k]
              for k in fault_keys}
    # the canvas each answered request ran on, for the FLOP count
    scale = cell.config["model"]["scale"]
    canvas = collections.Counter(
        _bucket_of(pool[r["image"]].shape[:2], scale, ladder) for r in good)
    # what the check looks at: a sample of answered requests drawn from
    # the seed, the largest image among them
    rng = np.random.RandomState((pseed + 31337) % (2**31 - 1))
    k = min(int(traffic.get("check_requests", 6)), len(good))
    picks = list(rng.choice(len(good), size=k, replace=False)) if k else []
    if good:
        biggest = max(range(len(good)),
                      key=lambda j: pool[good[j]["image"]].size)
        if biggest not in picks:
            picks[0] = biggest
    sample = [{"image": pool[good[j]["image"]], "dets": good[j]["dets"],
               "i": good[j]["i"]} for j in picks]
    window_compiles = (marks[1][1] - marks[0][1]) + window_misses
    return {
        "kind": "serve",
        "attempted": len(records),
        "failed": len(records) - len(good),
        "t0": t0, "t_end": t_end,
        "rate": window_rate(len(good), t0, t_end),
        "latencies_ms": loadgen.latencies_ms(records, t0, t_end),
        "generator_late_ms": loadgen.lateness_ms(records),
        "setup_s": t0 - t_process,
        "compile_s_setup": marks[0][0],
        "window_compiles": window_compiles,
        "window_in_use_bytes": in_use[0],
        "batch_occupancy": (real / slots) if slots else None,
        "engine_faults": faults,
        "canvas_counts": dict(canvas),
        "trace_dir": trace_dir,
        "tmp": tmp,
        "check_input": {"sample": sample, "seed": pseed, "ladder": ladder},
    }


def _bucket_of(hw, scale, ladder):
    """The ladder rung an original (h, w) lands on after the resize to
    ``scale`` (short side to scale[0], long side capped at scale[1])."""
    from harness.check_serve import resize_scale

    h, w = hw
    s = resize_scale(h, w, scale[0], scale[1])
    rh, rw = int(round(h * s)), int(round(w * s))
    fit = [b for b in ladder if b[0] >= rh and b[1] >= rw]
    return min(fit, key=lambda b: b[0] * b[1]) if fit else max(
        ladder, key=lambda b: b[0] * b[1])
