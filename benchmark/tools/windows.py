#!/usr/bin/env python3
"""Several windows of one train cell in ONE process, back to back: how far
the rate of a window spreads where set-up and the check are paid once.

    python3 benchmark/tools/windows.py --workload c4_train_b8 --seed 11 \
        --seconds 20 --windows 6 --out chiprun_out/windows.jsonl

Each window opens and closes as ``run.py``'s does (the clock starts on a
fetched loss, runs until ``--seconds`` have passed at a dispatch, and stops
once the last step sent has been fetched).  One JSON line per window: its
steps, seconds, img/s, the dispatch intervals and the allocator's figures.
Not a run of the benchmark: it compares nothing with the reference and
prints no result line, and what separate processes add to the spread it
cannot see.  Needs the chip like ``run.py`` does.
"""

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [BENCH_DIR, ROOT]


def windows_hook(n_windows: int, sink):
    """A ``StepHook`` that keeps the window's rules and repeats it."""
    from harness import stats
    from harness.train_driver import StepHook

    class WindowsHook(StepHook):
        def wrap(self, step_fn):
            import jax

            sent = []
            count = [0]

            def hooked(state, batch, rng, lr_scale=None):
                self.k += 1
                out = self._call(step_fn, state, batch, rng, lr_scale)
                if self.done or self.k < self.warm:
                    return out
                loss = out[1]["loss"]
                if self.t0 is None:  # the last warm step: the clock starts
                    jax.block_until_ready(loss)
                    self.compile_marks[0] = self.clock.mark()
                    self.t0 = time.monotonic()
                    return out
                now = time.monotonic()
                sent.append(now)
                self.dispatch_times.append(now)
                if now - self.t0 < self.seconds:
                    return out
                jax.block_until_ready(loss)
                t_end = time.monotonic()
                mem = jax.local_devices()[0].memory_stats() or {}
                gaps = [(b - a) * 1e3 for a, b in zip(sent, sent[1:])]
                sink({
                    "window": count[0], "steps": len(sent),
                    "window_s": t_end - self.t0,
                    "drain_s": t_end - now,
                    "img_per_s": stats.window_rate(
                        len(sent) * self.batch_images, self.t0, t_end),
                    "step_interval_ms": {
                        f"p{q}": stats.percentile(gaps, q)
                        for q in (5, 50, 95, 100)},
                    "bytes_in_use": mem.get("bytes_in_use"),
                    "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
                    "peak_bytes_reserved": mem.get("peak_bytes_reserved"),
                    "compiles_so_far": self.clock.mark()[1]
                    - self.compile_marks[0][1],
                })
                count[0] += 1
                del sent[:]
                if count[0] < n_windows:
                    self.t0 = time.monotonic()
                    return out
                self.t_end = t_end
                self.compile_marks[1] = self.clock.mark()
                self.done = True
                self.stop()
                return out

            return hooked

    return WindowsHook


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--windows", type=int, default=6)
    ap.add_argument("--out", required=True)
    ap.add_argument("--cpu_rehearsal", action="store_true",
                    help="tiny configuration on the CPU (tests only)")
    args = ap.parse_args(argv)

    t_process = time.monotonic()
    from harness import spec, train_driver
    from harness.device import CompileClock, require_tpu

    cell = spec.load_cell(args.workload)
    if cell.traffic["kind"] != "train":
        raise SystemExit("windows.py: train cells only")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    patch = None
    if args.cpu_rehearsal:
        from harness import rehearsal
        from harness.check_train import apply_overrides

        cell = rehearsal.tiny_cell(cell)

        def patch(cli):
            make = cli.generate_config
            cli.generate_config = lambda n, d: apply_overrides(
                make(n, d), rehearsal.TINY)
    else:
        require_tpu(cell.chips)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    out = open(args.out, "a")

    def sink(row):
        out.write(json.dumps(row) + "\n")
        out.flush()
        print(json.dumps(row), flush=True)

    train_driver.StepHook = windows_hook(args.windows, sink)
    # no step is checked here: the hook above looks at none
    cell = cell._replace(traffic=dict(cell.traffic, check_steps=0))
    run = train_driver.run(cell, args.seed, args.seconds, False,
                           CompileClock(), t_process, patch_cli=patch)
    sink({"setup_s": run["setup_s"], "report": run["report"],
          "window_compiles": run["window_compiles"]})
    return 0


if __name__ == "__main__":
    sys.exit(main())
