"""What the build glue and the documents tell a reader to run exists.

Host-only, seconds.  One case for the ``Makefile`` (every file a recipe
runs), one each for ``README.md`` and ``SERVING.md`` (every ``python …
.py`` / ``python -m mx_rcnn_tpu.…`` command line and every ``make
<target>`` inside a fenced block), and one that holds the benchmark's
fast test modules and their ``tests/test_benchmark_*.py`` collectors
equal.  Prose, upstream paths (``rcnn/…``) and the history files
(CHANGES.md, PERF.md, ROADMAP.md, VERDICT.md) are not checked.
"""

import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PY_FILE = re.compile(r"(?<![\w./-])([\w./-]+\.py)\b")
_PY_MODULE = re.compile(r"-m\s+(mx_rcnn_tpu(?:\.\w+)+)")
_PYTHON = re.compile(r"(?:^|[\s;&|(])(?:\$\(PY\)|python3?)\s")
_MAKE = re.compile(r"(?:^|[\s;&|(])make((?:\s+[a-z][\w-]*)+)")


def _read(name):
    with open(os.path.join(ROOT, name)) as f:
        return f.read()


def _joined(lines):
    """Lines with their backslash continuations folded in."""
    out, cur = [], ""
    for line in lines:
        if line.rstrip().endswith("\\"):
            cur += line.rstrip()[:-1] + " "
        else:
            out.append(cur + line)
            cur = ""
    if cur:
        out.append(cur)
    return out


def _makefile_recipes():
    return _joined(
        line[1:] for line in _read("Makefile").split("\n")
        if line.startswith("\t"))


def _makefile_targets():
    return set(re.findall(r"^([a-z][\w-]*):", _read("Makefile"), re.M))


def _fenced_commands(name):
    blocks = re.findall(r"^```[^\n]*\n(.*?)^```", _read(name), re.M | re.S)
    lines = []
    for block in blocks:
        lines += _joined(
            line.split("#", 1)[0] for line in block.split("\n"))
    return lines


def _missing(commands):
    """Files, modules and make targets that ``commands`` run and the tree
    does not hold."""
    missing = []
    targets = _makefile_targets()
    for cmd in commands:
        for names in _MAKE.findall(cmd):
            missing += [f"make {t}" for t in names.split()
                        if t not in targets]
        if not _PYTHON.search(cmd):
            continue
        for path in _PY_FILE.findall(cmd):
            if path.startswith("rcnn/") or os.path.isabs(path):
                continue
            if not os.path.isfile(os.path.join(ROOT, path)):
                missing.append(path)
        for module in _PY_MODULE.findall(cmd):
            rel = os.path.join(ROOT, *module.split("."))
            if not (os.path.isfile(rel + ".py") or os.path.isdir(rel)):
                missing.append(module)
    return missing


def _uncollected_benchmark_modules():
    """Fast modules of ``benchmark/tests`` (those not marked ``rehearsal``
    as a whole) that no ``tests/test_benchmark_*.py`` star-imports."""
    imported = set()
    for path in glob.glob(os.path.join(ROOT, "tests", "test_benchmark_*.py")):
        with open(path) as f:
            imported.update(
                re.findall(r"^from (test_\w+) import \*", f.read(), re.M))
    missing = []
    for path in sorted(
            glob.glob(os.path.join(ROOT, "benchmark", "tests", "test_*.py"))):
        with open(path) as f:
            whole_file_rehearsal = re.search(
                r"^pytestmark = pytest\.mark\.rehearsal", f.read(), re.M)
        module = os.path.basename(path)[:-3]
        if not whole_file_rehearsal and module not in imported:
            missing.append(module)
    return missing


CASES = {
    "Makefile": lambda: _missing(_makefile_recipes()),
    "README.md": lambda: _missing(_fenced_commands("README.md")),
    "SERVING.md": lambda: _missing(_fenced_commands("SERVING.md")),
    "benchmark/tests": _uncollected_benchmark_modules,
}


@pytest.mark.parametrize("where", sorted(CASES))
def test_what_it_tells_a_reader_to_run_exists(where):
    assert CASES[where]() == []


def test_the_check_sees_a_missing_file_module_and_target():
    assert _missing([
        "python gone.py --flag",
        "JAX_PLATFORMS=cpu $(PY) -m mx_rcnn_tpu.tools.gone --x 1",
        "make lint gone-target",
        "python -m mx_rcnn_tpu.tools.serve --small && python chip_smoke.py",
        "python rcnn/tools/train.py",
        "cat notes.py",
    ]) == ["gone.py", "mx_rcnn_tpu.tools.gone", "make gone-target"]
