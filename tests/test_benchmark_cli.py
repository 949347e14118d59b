"""The benchmark's cases for ``benchmark/run.py``'s command line and exit codes (a subprocess; it
refuses without a TPU),
collected here so the tier-1 run holds them: the cases live in
``benchmark/tests/test_cli.py`` (fast, CPU)."""

import benchmark_cases  # noqa: F401 — sys.path for the import below

from test_cli import *  # noqa: E402,F401,F403 — the cases themselves
