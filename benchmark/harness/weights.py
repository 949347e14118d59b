"""The benchmark's weight recipe for serve cells.

Weights come from the seed (``model.init``).  Under frozen BN, which at
initialisation is the identity, a random ResNet-101 has no normalisation:
pixel-scale inputs (±128) grow ~40x through 33 residual units, the class
head saturates to one-hot scores, and an image yields a handful of
detections whose boxes come from exploding regression deltas - an output
so ill-conditioned that float32 and one-pass bfloat16 disagree on it as
much as fp8 does (PERF.md, PR 24).  A trained network is not like that.
So a mix may state a recipe, applied alike to the program's weights and
to the reference's own: a list of ``{"match": <regex on the leaf's
path>, "scale": x}`` or ``{"match": ..., "set": x}``.  The cells here
scale the stem's kernel to unit-variance output and set the last BN scale
of every residual unit to 0.5 (Goyal et al. 2017 set it to 0), which keeps
activations O(1) and the heads' logits O(1).
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional


def condition(params, recipe: Optional[List[Dict[str, Any]]]):
    """``params`` (a nested dict of arrays) with the recipe applied; every
    rule has to match at least one leaf."""
    if not recipe:
        return params
    import flax

    flat = flax.traverse_util.flatten_dict(params)
    for rule in recipe:
        rx = re.compile(rule["match"])
        hits = [k for k in flat if rx.search("/".join(k))]
        if not hits:
            raise ValueError(f"weight recipe rule matches no leaf: {rule}")
        for k in hits:
            if "scale" in rule:
                flat[k] = flat[k] * rule["scale"]
            else:
                flat[k] = flat[k] * 0 + rule["set"]
    return flax.traverse_util.unflatten_dict(flat)
