"""``BENCHMARK.json`` and the data files it names.

Everything that belongs to one configuration, one traffic mix, one cell's
limits or one per-layer metric sits in a file of its own, found by the
name in ``BENCHMARK.json``:

- ``configs/<config>.json``   the configuration as it is run
- ``traffic/<traffic>.json``  parameters of the mix, read by one general
                              generator per ``kind`` (``train``/``serve``)
- ``limits/<workload>.json``  the limits of the numbers ``correct`` compares
- ``metrics/<metric>.json``   ``{"reader": "<module>:<function>", "args": {}}``
                              with ``<module>.py`` beside it
- ``graphs/<graph>.py``       layers and ROIAlign pools of the graph a
                              configuration names (``harness/flops.py``)
- ``reference/models/<graph>.py``  the plain reference of that graph

so a later PR adds a cell, a configuration or a metric by adding files and
entries, and edits no file that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, NamedTuple

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class Cell(NamedTuple):
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]   # BENCHMARK.json entries of this cell
    per_layer: List[Dict[str, Any]]


def _load(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    return _load(os.path.join(root, "BENCHMARK.json"))


def _in_cell(metric: Dict[str, Any], workload: str, reported: set) -> bool:
    """Does ``metric`` belong to ``workload``?  By its ``workloads`` list,
    or — a per-layer metric without one — by whether the cell reports the
    end-to-end metric it moves."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reported


def load_cell(workload: str, root: str = ROOT,
              bench_dir: str | None = None) -> Cell:
    bench = load_benchmark(root)
    bench_dir = bench_dir or os.path.join(root, bench["paths"][0])
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(
            f"benchmark: unknown workload {workload!r}; BENCHMARK.json has "
            f"{sorted(cells)}"
        )
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _load(os.path.join(root, cfg_entry["file"]))
    traffic = _load(os.path.join(bench_dir, "traffic", w["traffic"] + ".json"))
    limits = _load(os.path.join(bench_dir, "limits", workload + ".json"))
    e2e = [m for m in bench["end_to_end"] if _in_cell(m, workload, set())]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _in_cell(m, workload, reported)]
    return Cell(workload, int(w["chips"]), config, traffic, limits, e2e, layer)


def load_reader(metric: str, bench_dir: str = BENCH_DIR) -> Callable:
    """``metrics/<metric>.json`` → ``read(ctx) -> float | None`` bound to
    the file's ``args``."""
    entry = _load(os.path.join(bench_dir, "metrics", metric + ".json"))
    module, _, func = entry["reader"].partition(":")
    path = os.path.join(bench_dir, "metrics", module + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{module}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    fn = getattr(mod, func)
    args = entry.get("args", {})
    return lambda ctx: fn(ctx, **args)


def read_metrics(names: List[str], ctx: Dict[str, Any],
                 bench_dir: str = BENCH_DIR) -> Dict[str, float]:
    """Each named metric through its own reader; one that finds nothing to
    read returns None and is left out."""
    out = {}
    for name in names:
        value = load_reader(name, bench_dir)(ctx)
        if value is not None:
            out[name] = float(value)
    return out
