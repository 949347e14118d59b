"""Import this before a ``from test_<name> import *`` of one of the
benchmark's own test modules: it puts ``benchmark/`` and
``benchmark/tests/`` on ``sys.path``, as ``benchmark/tests/conftest.py``
does for a run from there.

Tier-1 collects ``tests/`` only, so each fast module of
``benchmark/tests`` has one ``tests/test_benchmark_<name>.py`` that
star-imports its cases (one file a module: two modules' cases or
fixtures of one name would shadow each other in a shared file).  The
rehearsals of whole cells (``-m rehearsal``, minutes each) stay with
``python -m pytest benchmark/tests``.
``tests/test_repo_consistency.py`` holds the two sets equal.
"""

import os
import sys

BENCH_TESTS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmark", "tests")
for _p in (BENCH_TESTS, os.path.dirname(BENCH_TESTS)):
    if _p not in sys.path:
        sys.path.insert(0, _p)
