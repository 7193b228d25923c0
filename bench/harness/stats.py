"""Order statistics used by the end-to-end metrics and the spread rule."""
from __future__ import annotations

import math
import statistics


def nearest_rank(values, q: float) -> float:
    """The q-th percentile by nearest rank (q in (0, 100]); ``inf`` values
    (unanswered requests) sort last."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[k - 1]


def spread(values) -> float:
    """(Q3 - Q1) / median by ``statistics.quantiles(n=4)``: the spread the
    bounds in ``BENCHMARK.json`` are set from."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
