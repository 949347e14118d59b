"""From the profiler's ``.xplane.pb`` to numbers, with nothing but
``jax.profiler.ProfileData``.

A device plane (``/device:TPU:<n>``) has a line of operations (``XLA
Ops``) and a line of whole programs (``XLA Modules``).  Busy time is the
union of the operations' intervals; the traced window, on the device's
own clock, runs from the first operation's start to the last one's end on
any device plane, so what ``start_trace`` / ``stop_trace`` themselves cost
the host is not read as idle time.
"""

from __future__ import annotations

import glob
import os
import re
import statistics
from typing import Dict, List, NamedTuple, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the benchmark's own host annotations start with this
SPAN_PREFIX = "bench."
#: label of an idle gap during which none of them was open
OUTSIDE_SPANS = "inside the program (no benchmark span open)"

Event = Tuple[str, int, int]  # name, start_ns, duration_ns


class Trace(NamedTuple):
    ops: Dict[str, List[Event]]       # device plane → operations
    modules: Dict[str, List[Event]]   # device plane → whole programs
    spans: List[Event]                # the benchmark's host annotations
    names: Dict[str, List[str]]       # plane → its line names (for errors)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, spans, names = {}, {}, [], {}
    for plane in data.planes:
        lines = list(plane.lines)
        names[plane.name] = [ln.name for ln in lines]
        if DEVICE_PLANE.match(plane.name):
            for ln in lines:
                if ln.name in (OPS_LINE, MODULES_LINE):
                    evs = [(e.name, int(e.start_ns), int(e.duration_ns))
                           for e in ln.events]
                    (ops if ln.name == OPS_LINE else modules)[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for ln in lines:
                spans.extend(
                    (e.name, int(e.start_ns), int(e.duration_ns))
                    for e in ln.events if e.name.startswith(SPAN_PREFIX))
    return Trace(ops, modules, spans, names)


def union_ns(events: List[Event]) -> int:
    """Total length of the union of the events' intervals."""
    total, cur_s, cur_e = 0, None, None
    for _n, s, d in sorted(events, key=lambda e: e[1]):
        e = s + d
        if cur_s is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            total += cur_e - cur_s
            cur_s, cur_e = s, e
    if cur_s is not None:
        total += cur_e - cur_s
    return total


def device_window_ns(trace: Trace) -> Tuple[int, int]:
    starts = [e[1] for evs in trace.ops.values() for e in evs]
    ends = [e[1] + e[2] for evs in trace.ops.values() for e in evs]
    if not starts:
        raise RuntimeError(
            f"no operation ran on a device plane; planes and lines: "
            f"{trace.names}")
    return min(starts), max(ends)


def busy_and_window_s(trace: Trace) -> Tuple[float, float]:
    """(seconds an operation ran, averaged over the device planes; length
    of the traced window)."""
    w0, w1 = device_window_ns(trace)
    busy = [union_ns(evs) for evs in trace.ops.values()]
    return sum(busy) / len(busy) / 1e9, (w1 - w0) / 1e9


def idle_share_pct(trace: Trace) -> float:
    busy, window = busy_and_window_s(trace)
    return 100.0 * (1.0 - busy / window)


def matching(events: List[Event], pattern: str) -> List[Event]:
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e[0])]


def module_median_ms(trace: Trace, pattern: str) -> Optional[float]:
    """Median device duration of the programs whose name matches, over the
    first device plane; None where nothing matches."""
    for _plane, evs in sorted(trace.modules.items()):
        hit = matching(evs, pattern)
        if hit:
            return statistics.median(e[2] for e in hit) / 1e6
    return None


def ops_time_s(trace: Trace, pattern: str) -> Tuple[float, int]:
    """(summed device seconds, count) of the operations whose name
    matches, averaged over the device planes."""
    per_plane = [matching(evs, pattern) for evs in trace.ops.values()]
    n = sum(len(h) for h in per_plane)
    secs = sum(e[2] for h in per_plane for e in h) / 1e9
    return secs / max(len(per_plane), 1), n


def short_name(name: str) -> str:
    """The trace prints an operation as its whole HLO line; keep the
    result's name, and say where it is a Pallas kernel."""
    head = name.split(" = ")[0].lstrip("%")[:96]
    return head + " [tpu_custom_call]" if "tpu_custom_call" in name else head


def top_ops(trace: Trace, k: int = 10) -> List[List[object]]:
    """[[name, seconds]] of the operations that took most device time
    (first device plane), by their short names."""
    _plane, evs = sorted(trace.ops.items())[0]
    total: Dict[str, int] = {}
    for name, _s, d in evs:
        name = short_name(name)
        total[name] = total.get(name, 0) + d
    best = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in best]


def idle_gaps(trace: Trace, k: int = 10) -> List[List[object]]:
    """[[label, seconds]] of the longest gaps between operations on the
    first device plane, each labelled with the benchmark's own host span
    that was open at the gap's middle (or ``OUTSIDE_SPANS``)."""
    _plane, evs = sorted(trace.ops.items())[0]
    gaps, cur_e = [], None
    for _n, s, d in sorted(evs, key=lambda e: e[1]):
        if cur_e is not None and s > cur_e:
            gaps.append((cur_e, s))
        cur_e = s + d if cur_e is None else max(cur_e, s + d)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for g0, g1 in gaps[:k]:
        mid = (g0 + g1) // 2
        # the innermost (shortest) of the benchmark's open spans
        label = min(
            ((d, n) for n, s, d in trace.spans if s <= mid <= s + d),
            default=(0, OUTSIDE_SPANS),
        )[1]
        out.append([label, (g1 - g0) / 1e9])
    return out
