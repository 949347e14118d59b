"""The benchmark's cases for the VGG-16 configuration
(``benchmark/graphs/vgg.py`` against hand counts, the cell
``vgg_train_b8`` as ``spec.load_cell`` assembles it), collected here so
the tier-1 run holds them: the cases live in
``benchmark/tests/test_vgg_graph.py`` (fast, no JAX)."""

import benchmark_cases  # noqa: F401 — sys.path for the imports below

from test_vgg_graph import *  # noqa: E402,F401,F403 — the cases themselves
