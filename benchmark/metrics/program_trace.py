"""Readers of what the PROGRAM writes into the profiler's trace: its own
host spans (``rcnn.*`` ``TraceAnnotation``s, ``mx_rcnn_tpu/utils/
tracing.py``) and the stage scopes (``jax.named_scope``) its device
operations carry.  ``harness/trace.py`` keeps only the benchmark's
``bench.`` spans and drops the events' stats, so this module opens the
``.xplane.pb`` itself: once per run, kept on ``ctx`` (the harness
executes this file anew for every metric, and a 4 s trace takes many
seconds to read).

What the profiler gives (TPU v5e, jax 0.9, read off a trace of each cell,
PERF.md PR 25): a ``TraceAnnotation``'s keyword arguments arrive as the
event's stats (numbers as numbers, a list as its ``str``).  An event of a
device plane's ``XLA Ops`` line is named by its whole HLO line WITHOUT the
line's ``metadata``, and carries no stat of its scope; the scope path
(the instruction's ``op_name``) is the ``tf_op`` stat of the event's
*metadata* entry, which ``ProfileData`` does not hand out, so
``op_names`` reads that one map off the file's wire format (planes, lines
and events are skipped by length, never walked).  A path's components
are wrapped by the transforms they went through (``transpose(jvp(M))``,
``vmap(class_nms)``); a fusion of several instructions lists their paths
with ``;`` between (the first is taken); a ``while`` has no ``tf_op`` of
its own, but the operations of its body are events of their own nested in
the loop's, so device time is always the UNION of the matching events'
intervals and the loop is counted through its body.

Every reader returns None where the program has no such span, scope or
counter (the parent of the PR that added them), and never raises for it.
"""

from __future__ import annotations

import bisect
import re
import statistics
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

from harness import trace as tr

SPAN_PREFIX = "rcnn."
BATCH_WAIT = "rcnn.serve.batch_wait"
#: spans under which a thread does no work of its own: it waits for
#: another thread or for the device
WAITS = (
    BATCH_WAIT, "rcnn.serve.slot_wait", "rcnn.serve.fetch",
    "rcnn.feed.wait", "rcnn.loader.wait", "rcnn.guard.fetch",
)
#: gaps on the device shorter than this are the pauses between kernels
MIN_GAP_NS = 1_000_000

#: the PjRt runtime's own annotations of what it does to a buffer on its
#: way to and from the device (host threads ``pjrt-tpu-tasks/*``): the
#: re-tiling of a host array into the device's layout and back.  Found
#: under the serve cell's idle third (PERF.md, PR 25); read by
#: ``tools/idle_by_span.py`` only.
RUNTIME_SPANS = ("XlaLinearize", "XlaDelinearize")

#: span → the role of the host thread it runs on (first match wins)
ROLES = (
    ("train loop", ("rcnn.step.dispatch", "rcnn.feed.wait",
                    "rcnn.guard.snapshot", "rcnn.guard.fetch")),
    ("feed worker", ("rcnn.feed.place",)),
    ("loader worker", ("rcnn.loader.assemble", "rcnn.loader.wait")),
    ("caller", ("rcnn.serve.prepare",)),
    ("assembler", ("rcnn.serve.batch_wait", "rcnn.serve.pickup",
                   "rcnn.serve.assemble", "rcnn.serve.slot_wait")),
    ("completion", ("rcnn.serve.dispatch", "rcnn.serve.fetch",
                    "rcnn.serve.postprocess")),
    ("runtime", RUNTIME_SPANS),
)
#: the stage scopes, as device busy time is tabulated by them
STAGES = (
    "backbone", "rpn", "anchor_targets", "proposal", "roi_sample",
    "roi_head", "losses", "update", "postprocess/decode",
    "postprocess/class_nms", "postprocess/cap", "postprocess/mask_select",
    "postprocess/mask_paste",
)


class Span(NamedTuple):
    name: str
    start: int            # ns, the trace's clock
    dur: int
    thread: int           # index of the host line it was recorded on
    ids: Dict[str, Any]   # the annotation's keyword arguments


class Op(NamedTuple):
    path: Tuple[str, ...]  # scope components, transform wrappers stripped
    start: int
    dur: int


class ProgramTrace(NamedTuple):
    spans: List[Span]             # the program's rcnn.* spans
    ops: List[Op]                 # first device plane
    modules: List[tr.Event]       # first device plane
    runtime: List[Span]           # RUNTIME_SPANS, on the runtime's threads


_WRAPPED = re.compile(r"^(?:[\w.]+\()+(.*?)\)+$")


def scope_path(tf_op: str) -> Tuple[str, ...]:
    """``jit(f)/transpose(jvp(M))/M.fwd/backbone/conv:`` → its components
    with the transforms' wrappers taken off (the first path of several)."""
    out = []
    for comp in tf_op.split(";")[0].rstrip(":").split("/"):
        m = _WRAPPED.match(comp)
        out.append(m.group(1) if m else comp)
    return tuple(out)


# --- the one map ProfileData leaves out, off the protobuf wire format.
# tensorflow/tsl xplane.proto: XSpace.planes=1; XPlane.name=2 lines=3
# event_metadata=4 stat_metadata=5 (maps: key=1 value=2);
# XEventMetadata.name=2 stats=5; XStatMetadata.id=1 name=2;
# XStat.metadata_id=1 str_value=5.
def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a view
    of the bytes for everything else.  A submessage is skipped by its
    length unless the caller walks into it."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            else:
                size = {1: 8, 5: 4}[wire]
            value = buf[i:i + size]
            i += size
        yield key >> 3, value


def _device_plane(path: str) -> bytes:
    """The serialized first device plane of the file.  Planes are found
    from their first bytes (id, then name) and skipped by their length:
    the host plane of a 7 s trace holds hundreds of megabytes."""
    found: Dict[str, Tuple[int, int]] = {}
    with open(path, "rb") as f:
        end = f.seek(0, 2)
        pos = 0
        while pos < end:
            f.seek(pos)
            head = f.read(24)
            key, i = _varint(head, 0)
            if key & 7 != 2:
                raise ValueError(f"{path}: not an XSpace at byte {pos}")
            size, i = _varint(head, i)
            start = pos + i
            if key >> 3 == 1:
                f.seek(start)
                name = b""
                for field, value in _fields(f.read(min(size, 256))):
                    if field >= 2:
                        name = bytes(value) if field == 2 else b""
                        break
                if tr.DEVICE_PLANE.match(name.decode(errors="replace")):
                    found[name.decode()] = (start, size)
            pos = start + size
        if not found:
            return b""
        start, size = found[min(found)]
        f.seek(start)
        return f.read(size)


def op_names(path: str) -> Dict[str, str]:
    """{an operation's HLO line: its ``tf_op``} for the first device
    plane's operations that have one."""
    parts = list(_fields(memoryview(_device_plane(path))))
    tf_op_id = None
    for field, entry in parts:
        if field == 5:
            meta = dict(_fields(dict(_fields(entry))[2]))
            if bytes(meta.get(2, b"")) == b"tf_op":
                tf_op_id = meta.get(1)
    out: Dict[str, str] = {}
    for field, entry in parts:
        if field != 4 or tf_op_id is None:
            continue
        hlo, found = "", None
        for key, value in _fields(dict(_fields(entry))[2]):
            if key == 2:
                hlo = bytes(value).decode()
            elif key == 5:
                stat = dict(_fields(value))
                if stat.get(1) == tf_op_id and 5 in stat:
                    found = bytes(stat[5]).decode()
        if found is not None:
            out[hlo] = found
    return out


def parse(path: str) -> ProgramTrace:
    from jax.profiler import ProfileData

    # one path an instruction, not one an event: a step's operations
    # come back with every step
    paths = {hlo: scope_path(tf_op) for hlo, tf_op in op_names(path).items()}
    data = ProfileData.from_file(path)
    spans: List[Span] = []
    runtime: List[Span] = []
    ops: List[Op] = []
    modules: List[tr.Event] = []
    thread = 0
    device_planes = sorted(
        (p for p in data.planes if tr.DEVICE_PLANE.match(p.name)),
        key=lambda p: p.name)
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            thread += 1
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append(Span(e.name, int(e.start_ns),
                                      int(e.duration_ns), thread,
                                      dict(e.stats)))
                elif e.name in RUNTIME_SPANS:
                    runtime.append(Span(e.name, int(e.start_ns),
                                        int(e.duration_ns), thread, {}))
    for line in (device_planes[0].lines if device_planes else ()):
        if line.name == tr.OPS_LINE:
            ops = [Op(paths.get(e.name, ()), int(e.start_ns),
                      int(e.duration_ns)) for e in line.events]
        elif line.name == tr.MODULES_LINE:
            modules = [(e.name, int(e.start_ns), int(e.duration_ns))
                       for e in line.events]
    return ProgramTrace(spans, ops, modules, runtime)


def of(ctx) -> Optional[ProgramTrace]:
    """The run's trace, parsed once and kept on ``ctx``; None in a run
    that was not traced."""
    if ctx.get("trace") is None:
        return None
    if "program_trace" not in ctx:
        ctx["program_trace"] = parse(tr.find_xplane(ctx["run"]["trace_dir"]))
    return ctx["program_trace"]


# ------------------------------------------------------------- intervals
def union(ivs: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(ivs):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(ivs: Sequence[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in union(ivs))


def clip(ivs, windows) -> List[Tuple[int, int]]:
    """The parts of ``ivs`` that fall inside ``windows`` (both lists of
    (start, end); ``windows`` disjoint)."""
    out = []
    for s, e in ivs:
        for w0, w1 in windows:
            lo, hi = max(s, w0), min(e, w1)
            if hi > lo:
                out.append((lo, hi))
    return out


def intervals(events) -> List[Tuple[int, int]]:
    return [(e.start, e.start + e.dur) for e in events]


def in_scope(ops: List[Op], scope: str) -> List[Op]:
    """Operations whose scope path holds ``scope``'s components next to
    each other (``"postprocess/class_nms"``: two of them)."""
    want = tuple(scope.split("/"))
    n = len(want)
    return [o for o in ops
            if any(o.path[i:i + n] == want
                   for i in range(len(o.path) - n + 1))]


def stage_of(path: Tuple[str, ...]) -> Optional[str]:
    """The outermost of ``STAGES`` a scope path lies under."""
    for i, comp in enumerate(path):
        if comp in STAGES:
            return comp
        if "/".join(path[i:i + 2]) in STAGES:
            return "/".join(path[i:i + 2])
    return None


def idle_gaps(ops: List[Op], min_ns: int = MIN_GAP_NS):
    """(start, end) of the gaps between operations, ``min_ns`` or longer."""
    busy = union(intervals(ops))
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:])
            if b[0] - a[1] >= min_ns]


def role_of(names) -> str:
    for role, own in ROLES:
        if any(n in own for n in names):
            return role
    return "other"


def innermost(spans: List[Span]) -> List[Tuple[str, int, int]]:
    """One thread's spans as disjoint (name, start, end) pieces, each
    piece named by the innermost span open over it."""
    edges = sorted({t for s in spans for t in (s.start, s.start + s.dur)})
    by_start = sorted(spans, key=lambda s: (s.start, -s.dur))
    out = []
    for lo, hi in zip(edges, edges[1:]):
        open_ = [s for s in by_start if s.start <= lo and s.start + s.dur >= hi]
        if open_:
            out.append((open_[-1].name, lo, hi))
    return out


# --------------------------------------------------------------- readers
def _spans(ctx, kind: str) -> Optional[List[Span]]:
    if ctx["run"]["kind"] != kind:
        return None
    pt = of(ctx)
    return pt.spans if pt is not None and pt.spans else None


def _named(spans: List[Span], name: str) -> List[Span]:
    return [s for s in spans if s.name == name]


def feed_wait_share(ctx):
    """Time the train loop's thread spent blocked on the feed, over the
    time from the first to the last step dispatched in the trace."""
    spans = _spans(ctx, "train")
    steps = _named(spans or [], "rcnn.step.dispatch")
    if len(steps) < 2:
        return None
    w0 = min(s.start for s in steps)
    w1 = max(s.start for s in steps)
    loop = {s.thread for s in steps}
    waits = [s for s in _named(spans, "rcnn.feed.wait") if s.thread in loop]
    return 100.0 * length(clip(intervals(waits), [(w0, w1)])) / (w1 - w0)


def loader_batch_ms(ctx):
    """Median time one thread takes to assemble one batch."""
    built = _named(_spans(ctx, "train") or [], "rcnn.loader.assemble")
    return statistics.median(s.dur for s in built) / 1e6 if built else None


def guard_snapshot_ms(ctx):
    """The guard's host copy of the state, from the program's own counter
    over the whole process (a short traced window holds no flush)."""
    if ctx["run"]["kind"] != "train":
        return None
    pipe = (ctx["run"].get("report") or {}).get("pipeline") or {}
    if not pipe.get("snapshots"):
        return None
    return pipe["snapshot_ms"] / pipe["snapshots"]


def scope_device_ms(ctx, scope, module):
    """Device time a step under ``scope``, forward and backward together:
    the union of its operations' intervals over the programs matching
    ``module`` that ran in the trace."""
    if ctx["run"]["kind"] != "train":
        return None
    pt = of(ctx)
    if pt is None:
        return None
    hit = in_scope(pt.ops, scope)
    steps = len(tr.matching(pt.modules, module))
    if not hit or not steps:
        return None
    return length(intervals(hit)) / steps / 1e6


def _as_numbers(value) -> List[float]:
    """A list that came through the profiler as its ``str``, or the one
    number a one-element list was read back as."""
    if isinstance(value, (int, float)):
        return [float(value)]
    return [float(x) for x in re.findall(r"-?\d+(?:\.\d+)?(?:e-?\d+)?",
                                         str(value))]


def queue_wait_p50_ms(ctx):
    """Median wait in the batcher's queue over the requests picked up in
    the trace (``picked_t - enqueue_t``, as ``ServeMetrics.queue_wait``)."""
    picked = _named(_spans(ctx, "serve") or [], "rcnn.serve.pickup")
    waits = [w for s in picked
             for w in _as_numbers(s.ids.get("wait_ms_each", ""))]
    return statistics.median(waits) if waits else None


def batch_host_ms(ctx):
    """Median host time a batch: assembly + dispatch + postprocess, joined
    on the batch number.  The fetch and the waits are left out: they are
    device time seen from the host."""
    spans = _spans(ctx, "serve") or []
    parts = ("rcnn.serve.assemble", "rcnn.serve.dispatch",
             "rcnn.serve.postprocess")
    per_batch: Dict[Any, Dict[str, int]] = {}
    for s in spans:
        if s.name in parts and s.ids.get("batch"):
            got = per_batch.setdefault(s.ids["batch"], {})
            got[s.name] = got.get(s.name, 0) + s.dur
    whole = [sum(got.values()) for got in per_batch.values()
             if len(got) == len(parts)]
    return statistics.median(whole) / 1e6 if whole else None


def dispatch_to_device_ms(ctx, module):
    """Median time from a batch's dispatch returning on the host to its
    program (``module``) starting on the device: what the device still
    waits for once the host has let go of the batch - the input's
    transfer, and the batch before it.  A batch's program is the last one
    that ended before its fetch returned."""
    spans = _spans(ctx, "serve") or []
    sent = {s.ids["batch"]: s.start + s.dur
            for s in _named(spans, "rcnn.serve.dispatch")
            if s.ids.get("batch")}
    progs = sorted(tr.matching(of(ctx).modules, module) if sent else [],
                   key=lambda e: e[1] + e[2])
    ends = [e[1] + e[2] for e in progs]
    waited = []
    for f in _named(spans, "rcnn.serve.fetch"):
        i = bisect.bisect_right(ends, f.start + f.dur) - 1
        if f.ids.get("batch") in sent and i >= 0:
            waited.append(progs[i][1] - sent[f.ids["batch"]])
    waited = [w for w in waited if w >= 0]
    return statistics.median(waited) / 1e6 if waited else None


def scope_device_share(ctx, scope):
    """Device time under ``scope`` over the device's busy time."""
    if ctx["run"]["kind"] != "serve":
        return None
    pt = of(ctx)
    if pt is None:
        return None
    hit = in_scope(pt.ops, scope)
    if not hit:
        return None
    return 100.0 * length(intervals(hit)) / length(intervals(pt.ops))


def idle_unattributed_share(ctx):
    """Of the device's idle time in gaps of a millisecond or more, the
    share during which no span of the program other than the assembler's
    wait for a batch was open on any host thread."""
    spans = _spans(ctx, "serve")
    if spans is None:
        return None
    gaps = idle_gaps(of(ctx).ops)
    if not gaps:
        return None
    working = intervals(s for s in spans if s.name != BATCH_WAIT)
    idle = length(gaps)
    return 100.0 * (idle - length(clip(working, gaps))) / idle
