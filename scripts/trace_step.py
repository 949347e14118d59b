"""Capture + parse a device trace of the exact bench-config train step.

Round-5 roofline evidence (VERDICT r4 #5): runs the flagship bench step
(bf16, FOLD_BN, b8) under ``jax.profiler.trace``, then parses the
``.xplane.pb`` directly with TF's bundled xplane proto (the
tensorboard_plugin_profile converter in this image is protobuf-
incompatible) and prints a per-op device-time table: total ms per op
name over the captured window, grouped, sorted.  Divide by the captured
step count for per-step cost.

Usage:
  PYTHONPATH=. python scripts/trace_step.py [--steps 10] [--dir /tmp/trace_r05]
  python scripts/trace_step.py --parse-only --dir /tmp/trace_r05
"""
import argparse
import dataclasses
import glob
import os
import time
from collections import defaultdict


def capture(args):
    import jax
    import numpy as np

    from mx_rcnn_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()

    from __graft_entry__ import _batch, _flagship_cfg
    from mx_rcnn_tpu.core.train import (
        create_train_state,
        make_optimizer,
        make_train_step,
    )
    from mx_rcnn_tpu.models import build_model

    cfg = _flagship_cfg()
    cfg = cfg.replace(
        network=dataclasses.replace(
            cfg.network, COMPUTE_DTYPE="bfloat16", FOLD_BN=True
        ),
        TRAIN=dataclasses.replace(cfg.TRAIN, BATCH_IMAGES=8),
    )
    model = build_model(cfg)
    h, w = cfg.SHAPE_BUCKETS[0]
    batch = _batch(cfg, 8, h, w)
    params = model.init(
        {"params": jax.random.key(0), "sampling": jax.random.key(1)},
        train=True,
        **batch,
    )["params"]
    tx = make_optimizer(cfg, lambda s: cfg.TRAIN.LEARNING_RATE)
    state = create_train_state(params, tx)
    step = make_train_step(model, tx, donate=True)
    rng = jax.random.key(0)

    # warmup/compile outside the trace window
    for _ in range(3):
        state, aux = step(state, batch, rng)
    assert np.isfinite(float(aux["loss"]))

    t0 = time.perf_counter()
    jax.profiler.start_trace(args.dir)
    for _ in range(args.steps):
        state, aux = step(state, batch, rng)
    assert np.isfinite(float(aux["loss"]))
    jax.profiler.stop_trace()
    dt = time.perf_counter() - t0
    print(f"captured {args.steps} steps in {dt:.2f}s "
          f"({8 * args.steps / dt:.1f} img/s incl. profiling overhead)",
          flush=True)


def parse(args):
    os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION", "python")
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    paths = sorted(
        glob.glob(os.path.join(args.dir, "**", "*.xplane.pb"),
                  recursive=True),
        key=os.path.getmtime,
    )
    if not paths:
        raise SystemExit(f"no .xplane.pb under {args.dir}")
    path = paths[-1]
    print(f"parsing {path} ({os.path.getsize(path)/1e6:.1f} MB)")
    xs = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        xs.ParseFromString(f.read())

    for plane in xs.planes:
        if "TPU" not in plane.name and "tpu" not in plane.name:
            continue
        ev_meta = plane.event_metadata
        totals = defaultdict(float)  # name -> total ps
        counts = defaultdict(int)
        span_lo, span_hi = None, 0
        # aggregate the 'XLA Ops' line only: device planes can carry
        # 'XLA Modules'/'Steps' lines whose events NEST the op events —
        # summing every line would double-count busy time (ADVICE r5 #1)
        op_lines = [ln for ln in plane.lines if ln.name == "XLA Ops"]
        if not op_lines:
            print(f"(plane {plane.name}: no 'XLA Ops' line — summing "
                  f"all {len(plane.lines)} lines)")
            op_lines = list(plane.lines)
        for line in op_lines:
            for ev in line.events:
                name = ev_meta[ev.metadata_id].name
                totals[name] += ev.duration_ps
                counts[name] += 1
                lo = ev.offset_ps
                span_lo = lo if span_lo is None else min(span_lo, lo)
                span_hi = max(span_hi, lo + ev.duration_ps)
        if not totals:
            continue
        total_ms = sum(totals.values()) / 1e9
        span_ms = (span_hi - (span_lo or 0)) / 1e9
        print(f"\n== plane: {plane.name} | busy {total_ms:.1f} ms over a "
              f"{span_ms:.1f} ms span ==")
        rows = sorted(totals.items(), key=lambda kv: -kv[1])
        print(f"{'op':<72s} {'total ms':>9s} {'/step ms':>9s} "
              f"{'n':>6s} {'%':>6s}")
        for name, ps in rows[: args.top]:
            ms = ps / 1e9
            print(f"{name[:72]:<72s} {ms:9.2f} {ms/args.steps:9.3f} "
                  f"{counts[name]:6d} {100*ps/sum(totals.values()):6.1f}")
        rest = sum(ps for _, ps in rows[args.top:]) / 1e9
        rest_n = sum(counts[n] for n, _ in rows[args.top:])
        print(f"{'(everything else)':<72s} {rest:9.2f} "
              f"{rest/args.steps:9.3f} {rest_n:6d}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--dir", default="/tmp/trace_r05")
    ap.add_argument("--top", type=int, default=45)
    ap.add_argument("--parse-only", action="store_true")
    args = ap.parse_args()
    if not args.parse_only:
        capture(args)
    parse(args)


if __name__ == "__main__":
    main()
