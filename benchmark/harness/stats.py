"""Arithmetic of the end-to-end metrics: whole-window rates, percentiles
over every request."""

from __future__ import annotations

import math
from typing import Sequence


def window_rate(units_done: float, t_start: float, t_end: float) -> float:
    """All the window's work over all the window's time."""
    if not t_end > t_start:
        raise ValueError(f"window of {t_end - t_start} s")
    return units_done / (t_end - t_start)


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (q in 0..100) over every value; no binning,
    no interpolation beyond the sample."""
    if not values:
        raise ValueError("percentile of no values")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]
