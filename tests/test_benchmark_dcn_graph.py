"""The benchmark's cases for the Deformable ConvNets configuration
(``benchmark/graphs/dcn.py`` and ``metrics/deform_roofline.py`` against
hand counts, the cell ``dcn_train_b8`` as ``spec.load_cell`` assembles
it, the configuration against the program and the reference), collected
here so the tier-1 run holds them: the cases live in
``benchmark/tests/test_dcn_graph.py``."""

import benchmark_cases  # noqa: F401 — sys.path for the imports below

from test_dcn_graph import *  # noqa: E402,F401,F403 — the cases themselves
