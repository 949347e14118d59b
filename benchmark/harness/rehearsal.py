"""CPU rehearsal of the harness: both drivers, the check and the last
line, end to end, at a tiny configuration with Pallas out of the way.
Reached from the tests only - a ``--workload`` run never comes here - and
nothing it prints is a device number: the result says ``platform: cpu``.
"""

from __future__ import annotations

import copy
import gc
import time
from typing import Any, Dict

#: what tests/test_chip_smoke.py cuts the program to, applied alike to
#: the program's Config and the reference's
TINY = {
    "": {"SHAPE_BUCKETS": [[96, 96]]},
    "TRAIN": {"RPN_PRE_NMS_TOP_N": 256, "RPN_POST_NMS_TOP_N": 32,
              "BATCH_ROIS": 16, "RPN_BATCH_SIZE": 32},
    "TEST": {"RPN_PRE_NMS_TOP_N": 200, "RPN_POST_NMS_TOP_N": 32},
    "dataset": {"SCALES": [[96, 96]], "MAX_GT_BOXES": 8, "NUM_CLASSES": 4},
    "network": {"depth": 50},
}


def tiny_cell(cell, train_batch: int = 2):
    """The cell with its argv cut for the CPU: few images, a short roidb,
    few warm steps."""
    traffic = copy.deepcopy(cell.traffic)
    if traffic["kind"] == "train":
        argv = list(traffic["argv"])
        for flag, value in (("--batch_images", str(train_batch)),
                            ("--synthetic", "64"),
                            ("--compute_dtype", "float32")):
            argv[argv.index(flag) + 1] = value
        traffic.update(argv=argv, batch_images=train_batch, warm_steps=4,
                       bucket=[96, 96], rois_per_image=16,
                       reference_block_rows=1)
    config = copy.deepcopy(cell.config)
    if traffic["kind"] == "serve":
        argv = list(traffic["argv"])
        argv[argv.index("--max_batch") + 1] = "2"
        traffic.update(argv=argv, max_batch=2, clients=4, pool=6,
                       sizes=[[72, 96], [96, 72], [64, 80]], check_requests=3)
        config["model"] = dict(config["model"], scale=[96, 96])
    return cell._replace(traffic=traffic, config=config)


def run_cell(cell, seed: int, seconds: float,
             overrides: Dict[str, Any] = TINY, patch_more=None):
    """Everything ``run.py`` does after its look for a chip.  → the
    result line's object.  ``patch_more(cli)`` lets a test break the timed
    path underneath."""
    import io
    import json
    from contextlib import redirect_stdout

    import jax

    import run as bench_run
    from harness.check_train import apply_overrides
    from harness.device import CompileClock, device_record

    def patch(cli):
        make = cli.generate_config
        cli.generate_config = lambda n, d: apply_overrides(make(n, d), overrides)
        if patch_more is not None:
            patch_more(cli)

    bench_run.T_PROCESS = time.monotonic()
    clock = CompileClock()
    run = bench_run.drive(cell, seed, seconds, False, clock, patch=patch)
    devices = jax.devices()[: cell.chips]
    device = device_record(devices, run.get("window_in_use_bytes", 0))
    gc.collect()
    out = io.StringIO()
    with redirect_stdout(out):
        bench_run.finish(cell, run, False, devices, device, overrides)
    return json.loads(out.getvalue().strip().splitlines()[-1])
