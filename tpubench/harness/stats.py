"""Metric arithmetic: percentiles, spreads, serving latencies."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation between order
    statistics; +inf where a value is +inf at that rank (a request that
    never answered misses every limit)."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no values")
    pos = (len(vals) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if pos == lo:
        return vals[lo]
    if math.isinf(vals[hi]):
        return vals[hi]
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def iqr_share(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, by ``statistics.quantiles(n=4)`` as the contract says."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def ttfts_ms(due_s, first_token_s):
    """First-token times counted from when each request was DUE; a request
    with no first token (``None``) misses: +inf."""
    return [math.inf if ft is None else (ft - due) * 1e3
            for due, ft in zip(due_s, first_token_s)]


def lateness_ms(due_s, submit_s):
    return [(s - d) * 1e3 for d, s in zip(due_s, submit_s)]
