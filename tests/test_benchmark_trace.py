"""The benchmark's cases for the device-trace reducer (``benchmark/harness/trace.py``) on a trace
written by ``xplane_writer.py``, every interval known by hand,
collected here so the tier-1 run holds them: the cases live in
``benchmark/tests/test_trace.py`` (fast, CPU)."""

import benchmark_cases  # noqa: F401 — sys.path for the import below

from test_trace import *  # noqa: E402,F401,F403 — the cases themselves
