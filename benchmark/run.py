#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one cell.  Refuses (non-zero exit, no result line) without a
TPU or with fewer chips than the cell asks for.  Set-up (imports, weights
from the seed, compile or cache load, warm-up of the cell's own shapes,
the first steps that the check later compares) is timed as ``setup_s``;
then the window runs for ``--seconds``; then peak memory is read, the
program's state is dropped, and the plain reference decides ``correct``.
Every number compared is printed beside its limit as the last lines of
standard error and under ``compared`` in the result line, which is the
last line of standard output.
"""

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for p in (BENCH_DIR, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def drive(cell, seed, seconds, trace, clock, patch=None):
    """The cell's driver by the traffic's ``kind``."""
    kind = cell.traffic["kind"]
    if kind == "train":
        from harness import train_driver

        return train_driver.run(cell, seed, seconds, trace, clock, T_PROCESS,
                                patch_cli=patch)
    if kind == "serve":
        from harness import serve_driver

        return serve_driver.run(cell, seed, seconds, trace, clock, T_PROCESS,
                                patch_cli=patch)
    raise SystemExit(f"benchmark: traffic kind {kind!r} has no driver")


def decide(cell, run, overrides=None):
    """→ (rows, detail): every number compared with its limit."""
    if run["kind"] == "train":
        from harness import check_train

        return check_train.check(cell, run["check_input"], overrides)
    from harness import check_serve

    return check_serve.check(cell, run["check_input"], overrides)


def finish(cell, run, trace, devices, device, overrides=None):
    """After the window: the check, the metrics, the lines.  → exit code."""
    from harness import spec

    preconditions = []
    if run["window_compiles"]:
        preconditions.append(
            ("window_compiles", float(run["window_compiles"]), 0.0, False))
    t_check = time.monotonic()
    rows, detail = decide(cell, run, overrides)
    rows = preconditions + rows
    check_s = time.monotonic() - t_check

    ctx = {"cell": cell, "run": run, "trace": None, "device": device}
    if trace:
        from harness import trace as tr

        loaded = tr.load(tr.find_xplane(run["trace_dir"]))
        ctx["trace"] = loaded
        busy, window = tr.busy_and_window_s(loaded)
        device["busy_s"], device["window_s"] = busy, window
        names = [m["name"] for m in cell.per_layer]
    else:
        names = [m["name"] for m in cell.end_to_end]
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    values = spec.read_metrics(names, ctx)
    result = {
        "correct": all(ok for *_x, ok in rows),
        "attempted": int(run["attempted"]),
        "failed": int(run["failed"]),
        "metrics": {n: {"value": v, "unit": units[n]} for n, v in values.items()},
        "device": device,
    }
    if trace:
        result["breakdown"] = {
            "device_ops": tr.top_ops(loaded, 10),
            "idle_gaps": tr.idle_gaps(loaded, 10),
        }
    result["window_s"] = run["t_end"] - run["t0"]
    result["check_s"] = check_s
    if len(run.get("dispatch_times", [])) > 1:
        # how evenly the steps were dispatched: a starved feed shows here
        from harness import stats

        t = run["dispatch_times"]
        gaps = [(b - a) * 1e3 for a, b in zip(t, t[1:])]
        detail["step_interval_ms"] = {
            f"p{q}": stats.percentile(gaps, q) for q in (5, 50, 95, 100)}
    result["detail"] = detail
    result["compared"] = {n: {"value": v, "limit": lim} for n, v, lim, _ in rows}
    shutil.rmtree(run["tmp"], ignore_errors=True)
    sys.stdout.flush()
    for n, v, lim, ok in rows:
        print(f"compared {n} = {v:.6g} limit {lim:.6g} "
              f"{'ok' if ok else 'NOT OK'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    args = parse_args(argv)
    from harness import spec

    cell = spec.load_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from harness.device import CompileClock, device_record, require_tpu

    devices = require_tpu(cell.chips)
    clock = CompileClock()
    run = drive(cell, args.seed, args.seconds, bool(args.trace), clock)
    device = device_record(devices, run.get("window_in_use_bytes", 0))
    gc.collect()
    return finish(cell, run, bool(args.trace), devices, device)


if __name__ == "__main__":
    sys.exit(main())
