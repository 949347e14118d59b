"""The benchmark's cases for the statistics and the FLOP counts (``benchmark/harness/stats.py``,
``flops.py``, ``graphs/c4.py`` against hand counts),
collected here so the tier-1 run holds them: the cases live in
``benchmark/tests/test_stats_flops.py`` (fast, CPU)."""

import benchmark_cases  # noqa: F401 — sys.path for the import below

from test_stats_flops import *  # noqa: E402,F401,F403 — the cases themselves
