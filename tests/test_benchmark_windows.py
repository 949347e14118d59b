"""The benchmark's cases for ``benchmark/tools/windows.py``'s back-to-back windows,
collected here so the tier-1 run holds them: the cases live in
``benchmark/tests/test_windows.py`` (fast, CPU)."""

import benchmark_cases  # noqa: F401 — sys.path for the import below

from test_windows import *  # noqa: E402,F401,F403 — the cases themselves
