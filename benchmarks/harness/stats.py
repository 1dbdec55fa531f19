"""Sample arithmetic of the benchmark: percentiles by nearest rank, and the
spread of repeated runs."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """The q-th percentile by nearest rank: the smallest value with at
    least q % of the sample at or below it.  No interpolation, so a sample
    that holds +inf (a request that never answered) gives +inf exactly
    when the rank falls on it.  Raises on an empty sample."""
    if not 0 < q <= 100:
        raise ValueError(f"percentile {q} is outside (0, 100]")
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of an empty sample")
    return ordered[max(0, math.ceil(q / 100.0 * len(ordered)) - 1)]


def spread(values) -> float:
    """Distance between the quartiles over the median: the driver's measure
    of how far repeated runs of one metric disagree."""
    q1, _, q3 = statistics.quantiles(values, n=4, method="inclusive")
    return (q3 - q1) / abs(statistics.median(values))
