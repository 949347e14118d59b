#!/usr/bin/env python3
"""Readings from which a cell's limits are set, many seeds in one process
(so the programs compile once): for each seed the cell's own timed path
through a short window, then the plain reference, then - on the first
``--control_seeds`` seeds - the lower-precision control and each planted
fault in the program's place.

    python3 benchmark/tools/calibrate.py --workload c4_train_b8 \
        --seeds 11,12,13 --control_seeds 3 --seconds 2 --out chiprun_out/cal.jsonl

One JSON line per seed: ``program`` / ``control`` / ``fault.<name>``, each
the numbers ``correct`` compares.  Needs the chip like ``run.py`` does.  A
serve cell also keeps what was compared (``<out>.raw<seed>.pkl.gz``);

    python3 benchmark/tools/calibrate.py --workload c4_serve_closed32 \
        --replay 'chiprun_out/c4_serve_closed32/cal2.jsonl.raw*.pkl.gz'

works the readings out again from those files, without the chip: for a
changed comparison or a changed ``match`` in the limits file.
"""

import argparse
import gc
import gzip
import json
import os
import pickle
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [BENCH_DIR, ROOT]


def train_readings(cell, run, control, faults, with_control, with_faults):
    from harness import check_train as ct

    t = time.monotonic()
    ref, gap = ct.follow(cell, run["check_input"])
    out = {"reference_s": time.monotonic() - t,
           "program": dict(ct.readings(run["check_input"], ref), batch_gap=gap),
           "program_losses": run["check_input"]["losses"],
           "reference_losses": ref["losses"],
           "program_counts": run["check_input"]["counts"],
           "reference_counts": ref["counts"]}
    if with_control:
        ctl, _ = ct.follow(cell, run["check_input"], round_to=control)
        out["control"] = ct.readings(ctl, ref)
        out["control_losses"] = ctl["losses"]
    if with_faults:
        for f in faults:
            bad, _ = ct.follow(cell, run["check_input"], fault=f)
            out[f"fault.{f}"] = ct.readings(bad, ref)
    return out


def replay(cell, pattern: str) -> int:
    import glob

    from harness import check_serve as cs

    rules = cs.rules_of(cs.reference_config(cell.config))
    for path in sorted(glob.glob(pattern)):
        with gzip.open(path, "rb") as f:
            raw = pickle.load(f)
        rec = cs.all_readings(raw, rules, cell.limits.get("match"))
        print(json.dumps(dict(rec, seed=raw["seed"])), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--replay", default=None, metavar="GLOB")
    ap.add_argument("--control_seeds", type=int, default=3,
                    help="the first N seeds also run the control")
    ap.add_argument("--fault_seeds", type=int, default=None,
                    help="the first N seeds also plant each fault "
                         "(train cells; default: as --control_seeds)")
    ap.add_argument("--faults", default="half_batch")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import run as bench_run
    from harness import spec
    from harness.device import CompileClock, device_record, require_tpu

    cell = spec.load_cell(args.workload)
    if args.replay:
        return replay(cell, args.replay)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    devices = require_tpu(cell.chips)
    clock = CompileClock()
    faults = [f for f in args.faults.split(",") if f]
    sink = open(args.out, "a") if args.out else None
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        bench_run.T_PROCESS = time.monotonic()
        run = bench_run.drive(cell, seed, args.seconds, False, clock)
        device = device_record(devices, run.get("window_in_use_bytes", 0))
        gc.collect()
        if run["kind"] == "train":
            n_fault = (args.control_seeds if args.fault_seeds is None
                       else args.fault_seeds)
            rec = train_readings(cell, run, cell.limits["control"], faults,
                                 i < args.control_seeds, i < n_fault)
        else:
            from harness import check_serve

            rec = check_serve.calibrate(cell, run, i < args.control_seeds)
            raw = rec.pop("raw")
            if args.out:  # what was compared, beside the readings
                with gzip.open(f"{args.out}.raw{seed}.pkl.gz", "wb") as f:
                    pickle.dump(raw, f, protocol=4)
        rec.update(seed=seed, rate=run["rate"], setup_s=run["setup_s"],
                   attempted=run["attempted"], failed=run["failed"],
                   window_compiles=run["window_compiles"],
                   memory_peak_bytes=device["memory_peak_bytes"],
                   report=run.get("report"))
        line = json.dumps(rec)
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()
        del run
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
