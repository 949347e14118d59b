"""Tests of the benchmark's own code; CPU only.  ``python -m pytest
benchmark/tests`` from the root of the repo (the rehearsals compile a tiny
detector on the CPU and take a few minutes each: ``-m 'not rehearsal'``
leaves them out)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
for p in (HERE, BENCH_DIR, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "rehearsal: drives a whole cell at a tiny size on the CPU")
