"""The benchmark's program-trace readers (``benchmark/metrics/
program_trace.py``) and ``benchmark/tools/idle_by_span.py`` on their
hand-written trace, collected here so the tier-1 run holds them: the cases
live in ``benchmark/tests/test_program_trace.py`` (fast; the rehearsals of
whole cells stay with ``python -m pytest benchmark/tests``)."""

import os
import sys

_BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
for _p in (os.path.join(_BENCH, "tests"), _BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from test_program_trace import *  # noqa: E402,F401,F403 — the cases themselves
