"""The benchmark's program-trace readers (``benchmark/metrics/
program_trace.py``) and ``benchmark/tools/idle_by_span.py`` on their
hand-written trace, collected here so the tier-1 run holds them: the cases
live in ``benchmark/tests/test_program_trace.py`` (fast, CPU)."""

import benchmark_cases  # noqa: F401 — sys.path for the import below

from test_program_trace import *  # noqa: E402,F401,F403 — the cases themselves
