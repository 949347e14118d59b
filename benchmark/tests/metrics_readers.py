"""``benchmark/metrics/readers.py`` as an importable module for the tests
(the harness itself loads readers by file, see ``harness.spec``)."""

import importlib.util
import os

from harness import spec

_path = os.path.join(spec.BENCH_DIR, "metrics", "readers.py")
_spec = importlib.util.spec_from_file_location("bench_metric_readers", _path)
readers = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(readers)
