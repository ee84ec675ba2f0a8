"""Order statistics the benchmark reports, kept apart from the program."""
from __future__ import annotations

import math
from typing import Sequence


def percentile(xs: Sequence[float], q: float) -> float:
    """Nearest-rank percentile, ``q`` in [0, 100]: the value at rank
    ``ceil(q/100 * n)`` (1-indexed). Raises on an empty sample: a metric
    with no sample is missing, not 0."""
    xs = sorted(xs)
    if not xs:
        raise ValueError("percentile of an empty sample")
    r = q / 100.0 * len(xs)
    if abs(r - round(r)) < 1e-9:
        r = round(r)
    return xs[min(max(math.ceil(r) - 1, 0), len(xs) - 1)]


def rate(count: float, t_first: float, t_last: float) -> float:
    """Work per second over the whole window from ``t_first`` to
    ``t_last``."""
    span = t_last - t_first
    if not span > 0:
        raise ValueError(f"empty window [{t_first}, {t_last}]")
    return count / span
