"""graftlint CLI — ``python tools/lint.py`` / ``make lint``.

Exit 0 only when the tree is clean: zero unsuppressed findings and zero
stale baseline entries.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Optional, Sequence

from mx_rcnn_tpu.analysis import engine as eng


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="graftlint")
    ap.add_argument(
        "--root", type=Path, default=None,
        help="repo root (default: two levels above this file)",
    )
    ap.add_argument(
        "--baseline", type=Path, default=None,
        help="baseline suppressions (default: <root>/tools/lint_baseline.json)",
    )
    ap.add_argument("--format", choices=("text", "json"), default="text")
    args = ap.parse_args(argv)

    root = args.root or Path(__file__).resolve().parents[2]
    baseline_path = args.baseline or root / "tools" / "lint_baseline.json"
    baseline = (
        eng.load_baseline(baseline_path) if baseline_path.exists() else []
    )

    modules, errors = eng.load_modules(root)
    report = eng.analyze(modules, eng.default_rules(), baseline, errors)

    if args.format == "json":
        print(
            json.dumps(
                {
                    "ok": report.ok,
                    "findings": [f.__dict__ for f in report.findings],
                    "baseline_suppressed": len(report.baseline_suppressed),
                    "inline_suppressed": len(report.inline_suppressed),
                    "stale_baseline": [
                        e.__dict__ for e in report.stale_baseline
                    ],
                    "errors": report.errors,
                },
                indent=1,
            )
        )
        return 0 if report.ok else 1

    for f in report.findings:
        print(f.format())
    for e in report.stale_baseline:
        print(
            f"STALE baseline entry {e.rule} {e.path} [{e.scope}] — matches "
            f"no current finding; remove it"
        )
    for msg in report.errors:
        print(f"ERROR {msg}")
    print(f"graftlint: {report.summary()}")
    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())
