"""graftlint rule engine: module model, suppressions, baseline, report.

Stdlib-``ast`` only — the analyzer must import (and run) without jax so
it can gate CI on boxes where the accelerator stack is absent.

Suppression layers, innermost wins:

1. inline pragma on the finding line (or the line directly above)::

       x = jax.device_get(t)  # graftlint: disable=R1(outputs never donated)

   A reason inside the parentheses is REQUIRED — a bare ``disable=R1``
   is ignored and the finding stands.

2. the checked-in baseline file (``tools/lint_baseline.json``): entries
   match on (rule, path, scope [, contains]).  An entry that matches no
   current finding is STALE and fails the lint run — the baseline can
   only shrink or track real code.
"""

from __future__ import annotations

import ast
import fnmatch
import json
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

PRAGMA_RE = re.compile(
    r"#\s*graftlint:\s*disable=([A-Z][A-Z0-9]*(?:\s*,\s*[A-Z][A-Z0-9]*)*)"
    r"\(([^()]+)\)"
)

#: scan roots, relative to the repo root
DEFAULT_TARGETS: Tuple[str, ...] = ("mx_rcnn_tpu",)
EXCLUDE_PARTS = {"__pycache__"}


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str  # repo-relative posix path
    line: int
    scope: str  # dotted qualname of the enclosing def/class, or <module>
    message: str

    def format(self) -> str:
        return (
            f"{self.rule} {self.path}:{self.line} [{self.scope}] "
            f"{self.message}"
        )


class Module:
    """Parsed source file plus the lookup tables every rule needs:
    parent links and def/class qualnames."""

    def __init__(self, path: str, source: str):
        self.path = path
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        self.parents: Dict[ast.AST, ast.AST] = {}
        for parent in ast.walk(self.tree):
            for child in ast.iter_child_nodes(parent):
                self.parents[child] = parent
        self.qualnames: Dict[ast.AST, str] = {}
        self._index(self.tree, [])

    def _index(self, node: ast.AST, stack: List[str]) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(
                child,
                (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef),
            ):
                qual = stack + [child.name]
                self.qualnames[child] = ".".join(qual)
                self._index(child, qual)
            else:
                self._index(child, stack)

    def parent(self, node: ast.AST) -> Optional[ast.AST]:
        return self.parents.get(node)

    def scope_of(self, node: ast.AST) -> str:
        cur: Optional[ast.AST] = node
        while cur is not None:
            if cur in self.qualnames:
                return self.qualnames[cur]
            cur = self.parents.get(cur)
        return "<module>"

    def enclosing_def(self, node: ast.AST):
        """Nearest enclosing FunctionDef/AsyncFunctionDef (not Lambda)."""
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return cur
            cur = self.parents.get(cur)
        return None

    def pragma_rules(self, line: int) -> Dict[str, str]:
        """rule -> reason for valid pragmas on ``line`` or the line above."""
        out: Dict[str, str] = {}
        for ln in (line, line - 1):
            if 1 <= ln <= len(self.lines):
                m = PRAGMA_RE.search(self.lines[ln - 1])
                if m:
                    reason = m.group(2).strip()
                    for rule in re.split(r"\s*,\s*", m.group(1)):
                        out.setdefault(rule, reason)
        return out


def dotted(node: Optional[ast.AST]) -> Optional[str]:
    """'jax.device_get' for a Name/Attribute chain, else None."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = dotted(node.value)
        return f"{base}.{node.attr}" if base else None
    return None


class Rule:
    id = "R0"
    name = "base"

    def check_module(self, module: Module) -> List[Finding]:
        return []

    def finalize(self, modules: Sequence[Module]) -> List[Finding]:
        """Cross-module pass, runs once after every check_module."""
        return []


@dataclass
class BaselineEntry:
    rule: str
    path: str
    scope: str
    reason: str
    contains: Optional[str] = None
    hits: int = 0

    def matches(self, f: Finding) -> bool:
        return (
            f.rule == self.rule
            and f.path == self.path
            and fnmatch.fnmatchcase(f.scope, self.scope)
            and (self.contains is None or self.contains in f.message)
        )


def load_baseline(path: Path) -> List[BaselineEntry]:
    raw = json.loads(path.read_text())
    out = []
    for e in raw.get("suppressions", []):
        out.append(
            BaselineEntry(
                rule=e["rule"],
                path=e["path"],
                scope=e["scope"],
                reason=e["reason"],
                contains=e.get("contains"),
            )
        )
    return out


@dataclass
class Report:
    findings: List[Finding] = field(default_factory=list)
    inline_suppressed: List[Tuple[Finding, str]] = field(default_factory=list)
    baseline_suppressed: List[Tuple[Finding, BaselineEntry]] = field(
        default_factory=list
    )
    stale_baseline: List[BaselineEntry] = field(default_factory=list)
    errors: List[str] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.findings and not self.stale_baseline and not self.errors

    def summary(self) -> str:
        return (
            f"{len(self.findings)} finding(s), "
            f"{len(self.inline_suppressed)} inline-suppressed, "
            f"{len(self.baseline_suppressed)} baseline-suppressed, "
            f"{len(self.stale_baseline)} stale baseline entr(y/ies), "
            f"{len(self.errors)} error(s)"
        )


def discover(root: Path, targets: Sequence[str] = DEFAULT_TARGETS) -> List[Path]:
    files: List[Path] = []
    for t in targets:
        p = root / t
        if p.is_file():
            files.append(p)
        elif p.is_dir():
            for f in sorted(p.rglob("*.py")):
                if not EXCLUDE_PARTS.intersection(f.parts):
                    files.append(f)
    return files


def load_modules(
    root: Path, targets: Sequence[str] = DEFAULT_TARGETS
) -> Tuple[List[Module], List[str]]:
    modules, errors = [], []
    for f in discover(root, targets):
        rel = f.relative_to(root).as_posix()
        try:
            modules.append(Module(rel, f.read_text()))
        except SyntaxError as e:  # unparseable source is itself a failure
            errors.append(f"parse error in {rel}: {e}")
    return modules, errors


def analyze(
    modules: Sequence[Module],
    rules: Sequence[Rule],
    baseline: Sequence[BaselineEntry] = (),
    errors: Sequence[str] = (),
) -> Report:
    by_path = {m.path: m for m in modules}
    raw: List[Finding] = []
    for rule in rules:
        for m in modules:
            raw.extend(rule.check_module(m))
    for rule in rules:
        raw.extend(rule.finalize(modules))
    raw = sorted(set(raw), key=lambda f: (f.path, f.line, f.rule, f.message))

    report = Report(errors=list(errors))
    entries = list(baseline)
    for f in raw:
        mod = by_path.get(f.path)
        pragmas = mod.pragma_rules(f.line) if mod else {}
        if f.rule in pragmas:
            report.inline_suppressed.append((f, pragmas[f.rule]))
            continue
        hit = next((e for e in entries if e.matches(f)), None)
        if hit is not None:
            hit.hits += 1
            report.baseline_suppressed.append((f, hit))
            continue
        report.findings.append(f)
    report.stale_baseline = [e for e in entries if e.hits == 0]
    return report


def default_rules() -> List[Rule]:
    # imported lazily so engine.py stays importable standalone in tests
    from mx_rcnn_tpu.analysis.rules_hostcopy import HostCopyEscape, UseAfterDonate
    from mx_rcnn_tpu.analysis.rules_jit import JitPurity
    from mx_rcnn_tpu.analysis.rules_locks import LockOrder
    from mx_rcnn_tpu.analysis.rules_futures import ExactlyOnce
    from mx_rcnn_tpu.analysis.rules_faults import FaultCoverage
    from mx_rcnn_tpu.analysis.rules_signals import SignalSafety
    from mx_rcnn_tpu.analysis.rules_requeue import BoundedRequeue

    return [
        HostCopyEscape(),
        UseAfterDonate(),
        JitPurity(),
        LockOrder(),
        ExactlyOnce(),
        FaultCoverage(),
        SignalSafety(),
        BoundedRequeue(),
    ]


def analyze_snippets(
    sources: Dict[str, str],
    rules: Optional[Sequence[Rule]] = None,
    baseline: Sequence[BaselineEntry] = (),
) -> Report:
    """Analyze in-memory {relpath: source} modules — the fixture-matrix
    entry point used by tests/test_analysis.py."""
    modules = [Module(p, s) for p, s in sources.items()]
    return analyze(modules, rules or default_rules(), baseline)
