"""The benchmark's cases for the spec loader (``benchmark/harness/spec.py``: ``BENCHMARK.json``,
the configuration, traffic, limits and metric files found by name),
collected here so the tier-1 run holds them: the cases live in
``benchmark/tests/test_spec.py`` (fast, CPU)."""

import benchmark_cases  # noqa: F401 — sys.path for the import below

from test_spec import *  # noqa: E402,F401,F403 — the cases themselves
