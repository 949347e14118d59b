#!/usr/bin/env python3
"""What the host was doing while the chip was idle, and what the chip was
doing while it was busy: a profiler trace tabulated by the program's own
``rcnn.*`` spans and stage scopes (``mx_rcnn_tpu/utils/tracing.py``).

    python3 benchmark/tools/idle_by_span.py DIR_OR_XPLANE_PB
    python3 benchmark/tools/idle_by_span.py --workload c4_serve_closed32 \
        --seed 11 --seconds 20 [--keep DIR]

The first form reads a trace that is there: a profiler directory
(``train_net --profile DIR``) or an ``.xplane.pb`` file (one kept by the
second form).  The second is a ``run.py --trace 1`` run
of the cell (same result line, needs the chip like ``run.py`` does) that
prints the tables to standard error before the run's trace is deleted, and
with ``--keep`` copies the ``.xplane.pb`` to DIR first.

Tables (first device plane; seconds):

- device idle in gaps of 1 ms or more, by host-thread role and by the
  innermost span open on a thread of that role (the program's threads by
  their ``rcnn.*`` spans; ``runtime``: the PjRt threads that re-tile a
  buffer into the device's layout, ``XlaLinearize``, and back, by the
  runtime's own annotations): a cell is the part of the
  idle time during which SOME thread of the role had that span innermost,
  ``(no span)`` the part during which none had any.  Threads work side by
  side, so a role's rows may sum to more than the idle total.  Two lines
  sum it up: the idle time no span but the assembler's wait for a batch
  covers (``idle_unattributed_share.serve``), and the idle time during
  which every thread only WAITS - for another thread or for the device -
  so the cause lies under the spans, in the runtime or on the device;
- the longest gaps, each with the spans open at its middle;
- device busy by stage scope (union of the operations' intervals under
  the scope, so a loop's body is not counted twice).
"""

import argparse
import importlib.util
import os
import shutil
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [BENCH_DIR, ROOT]


def _program_trace():
    path = os.path.join(BENCH_DIR, "metrics", "program_trace.py")
    spec = importlib.util.spec_from_file_location("bench_program_trace", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tables(xplane: str, top: int = 5) -> str:
    """The three tables of one ``.xplane.pb``, as text."""
    p = _program_trace()
    t = p.parse(xplane)
    if not t.ops:
        return f"{xplane}: no operation ran on a device plane"
    busy = p.length(p.intervals(t.ops))
    w0 = min(o.start for o in t.ops)
    w1 = max(o.start + o.dur for o in t.ops)
    gaps = p.idle_gaps(t.ops)
    idle = p.length(gaps)
    out = [
        f"trace {xplane}",
        f"device window {(w1 - w0) / 1e9:.3f} s, busy {busy / 1e9:.3f} s, "
        f"idle {(w1 - w0 - busy) / 1e9:.3f} s of which "
        f"{idle / 1e9:.3f} s in {len(gaps)} gaps of 1 ms or more; "
        f"{len(t.spans)} rcnn.* spans "
        f"({len(t.spans) / max((w1 - w0) / 1e9, 1e-9):.0f} a second)",
    ]
    threads = {}
    for s in t.spans + t.runtime:
        threads.setdefault(s.thread, []).append(s)
    roles = {}
    for th, spans in threads.items():
        roles.setdefault(p.role_of({s.name for s in spans}), []).append(
            p.innermost(spans))
    out.append("")
    out.append("device idle (gaps >= 1 ms) by role and innermost span, s:")
    for role, _own in p.ROLES:
        if role not in roles:
            continue
        by_name, covered = {}, []
        for pieces in roles[role]:
            for name, lo, hi in pieces:
                by_name.setdefault(name, []).append((lo, hi))
                covered.append((lo, hi))
        rows = [(n, p.length(p.clip(iv, gaps))) for n, iv in by_name.items()]
        rows.append(("(no span)", idle - p.length(p.clip(covered, gaps))))
        out.append(f"  {role} ({len(roles[role])} thread(s))")
        for name, ns in sorted(rows, key=lambda r: -r[1]):
            out.append(f"    {name:<28} {ns / 1e9:9.4f}  "
                       f"{100.0 * ns / max(idle, 1):5.1f}%")
    for label, skip in (
            (f"no span but {p.BATCH_WAIT} open on any thread",
             (p.BATCH_WAIT,)),
            ("every thread waiting or outside the spans (none open but "
             + ", ".join(w[len(p.SPAN_PREFIX):] for w in p.WAITS) + ")",
             p.WAITS)):
        open_ = p.intervals(s for s in t.spans if s.name not in skip)
        rest = idle - p.length(p.clip(open_, gaps))
        out.append(f"  {label}: {rest / 1e9:.4f} s "
                   f"({100.0 * rest / max(idle, 1):.1f}%)")
    out.append("")
    out.append(f"the {top} longest gaps (start after the first operation, "
               f"length, spans open at the middle):")
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (g0 + g1) // 2
        open_ = [s for s in t.spans + t.runtime
                 if s.start <= mid <= s.start + s.dur]
        names = sorted(
            f"{s.name}{dict(list(s.ids.items())[:2]) or ''}"
            f"[{(mid - s.start) / 1e9:.3f}s in]" for s in open_)
        out.append(f"  +{(g0 - w0) / 1e9:8.4f} s  {(g1 - g0) / 1e9:8.4f} s  "
                   + (", ".join(names[:6]) or "(no rcnn.* span open)")
                   + (f" and {len(names) - 6} more" if len(names) > 6 else ""))
    out.append("")
    out.append("device busy by stage scope, s (share of busy):")
    by_stage = {}
    for o in t.ops:
        stage = p.stage_of(o.path)
        if stage is not None:
            by_stage.setdefault(stage, []).append((o.start, o.start + o.dur))
    rows = [(stage, p.length(ivs)) for stage, ivs in by_stage.items()]
    staged = p.length([iv for ivs in by_stage.values() for iv in ivs])
    rows.append(("(no stage scope)", busy - staged))
    for name, ns in sorted(rows, key=lambda r: -r[1]):
        if ns:
            out.append(f"  {name:<28} {ns / 1e9:9.4f}  "
                       f"{100.0 * ns / busy:5.1f}%")
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir", nargs="?")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--keep", metavar="DIR",
                    help="copy the run's .xplane.pb here before it is deleted")
    args = ap.parse_args(argv)
    from harness import trace as tr

    if args.workload is None:
        if args.trace_dir is None:
            ap.error("a trace directory or --workload")
        given = args.trace_dir
        print(tables(given if os.path.isfile(given) else tr.find_xplane(given)))
        return 0
    import run as bench_run

    finish = bench_run.finish

    def finish_after_tables(cell, run, *rest, **kw):
        xplane = tr.find_xplane(run["trace_dir"])
        if args.keep:
            os.makedirs(args.keep, exist_ok=True)
            shutil.copy(xplane, args.keep)
        print(tables(xplane), file=sys.stderr, flush=True)
        return finish(cell, run, *rest, **kw)

    bench_run.finish = finish_after_tables
    return bench_run.main([
        "--workload", args.workload, "--seed", str(args.seed),
        "--seconds", str(args.seconds), "--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
