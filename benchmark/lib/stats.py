"""Metric arithmetic: percentiles, time per output token, run spread."""
from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    closest ranks (numpy's default).  Raises on an empty sample: a
    metric with nothing to measure is left out, never reported as 0."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def time_per_output_token(t_first: float, t_done: float,
                          n_output: int) -> float | None:
    """(completion - first token) / (output tokens - 1); None for a
    request of one token, which has no inter-token interval."""
    if n_output < 2:
        return None
    return (t_done - t_first) / (n_output - 1)


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of ``statistics.quantiles(values, n=4)``
    -- the driver's measure of run-to-run spread."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
