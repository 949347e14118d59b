"""Reader of the guard's flush cycle in the train cells.

``train_net`` runs its steps under ``PipelinedLoop``
(``mx_rcnn_tpu/core/pipeline.py``): at the end of each window of
``--aux_interval`` steps it fetches the window's losses, checks them and
copies the state to the host, and only then dispatches again.  The
program files one record of each completed cycle, from the flush to the
return of the next window's first dispatch, in
``report["pipeline"]["cycles"]``: its parts in ms, ``idle_ms`` among them
(how long the chip had no work), keyed by the window's first step, which
is also the ``rcnn.guard.flush`` span's ``step``.

The reader returns None where the program files no cycles (the parent of
the PR that added them) and in a serve run; it never raises for it.
"""

from __future__ import annotations

import statistics
from typing import List, Optional


def cycles_filed(ctx) -> Optional[List[dict]]:
    """The program's records of its completed flush cycles, over the whole
    process; None in a serve run and where the program keeps none."""
    if ctx["run"]["kind"] != "train":
        return None
    pipe = (ctx["run"].get("report") or {}).get("pipeline") or {}
    return pipe.get("cycles")


def closed_by_the_harness(ctx, cycle) -> bool:
    """Whether the window's closing call is the dispatch that ended
    ``cycle``: inside that call the harness's ``StepHook`` waits for the
    step it sent and, in a traced run, stops the profiler, so the
    cycle's ``resume_ms`` measures the harness.  The hook counts its
    calls from 1 and opens the window after ``max(warm_steps,
    check_steps)`` of them, so the closing call sends stream index
    ``max(warm_steps, check_steps) + attempted - 1``."""
    cell, run = ctx.get("cell"), ctx["run"]
    if cell is None or "attempted" not in run:
        return False
    warm = max(int(cell.traffic["warm_steps"]),
               int(cell.traffic["check_steps"]))
    return cycle["step"] + cycle["n"] == warm + int(run["attempted"]) - 1


def flush_idle_ms(ctx):
    """Median over the process's completed flush cycles of the time the
    chip had no work (the records' ``idle_ms``), the one the harness's
    closing call ended left out."""
    cycles = [c for c in cycles_filed(ctx) or ()
              if not closed_by_the_harness(ctx, c)]
    if not cycles:
        return None
    return statistics.median(c["idle_ms"] for c in cycles)
