"""The benchmark's cases for the load generator (``benchmark/harness/loadgen.py``: closed and
open loop, bursts, the due-time clock),
collected here so the tier-1 run holds them: the cases live in
``benchmark/tests/test_loadgen.py`` (fast, CPU)."""

import benchmark_cases  # noqa: F401 — sys.path for the import below

from test_loadgen import *  # noqa: E402,F401,F403 — the cases themselves
