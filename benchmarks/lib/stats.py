"""Percentiles, gaps and spreads, on plain lists of numbers.

``percentile`` is the linear-interpolation percentile (numpy's default)
written out, so the arithmetic that decides a metric is in the
benchmark's own files and is tested on hand-made logs.
"""

from __future__ import annotations

import statistics
from typing import Dict, Iterable, List, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0-100), linear between order statistics.
    An empty list is an error: a tail of nothing is not 0."""
    if not values:
        raise ValueError("percentile of an empty list")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def token_gaps(times: Sequence[float]) -> List[float]:
    """Gaps between consecutive tokens of ONE request."""
    return [b - a for a, b in zip(times, times[1:])]


def request_latencies(due: Dict[int, float],
                      tokens: Dict[int, Sequence[float]]) -> Dict:
    """Time to first token and inter-token gaps of the requests in
    ``due`` (request id -> the time it was DUE to be sent, not the time
    the generator got round to it: a stall is charged to the requests
    that waited through it). ``tokens`` maps request id to the arrival
    times of its tokens. Returns ``{"ttft": [...], "gaps": [...],
    "no_token": n}``, seconds."""
    ttft: List[float] = []
    pooled: List[float] = []
    missing = 0
    for rid, t_due in due.items():
        ts = tokens.get(rid) or []
        if not ts:
            missing += 1
            continue
        ttft.append(ts[0] - t_due)
        pooled.extend(token_gaps(ts))
    return {"ttft": ttft, "gaps": pooled, "no_token": missing}


def iqr_share(values: Iterable[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with ``statistics.quantiles(values, n=4)`` — the spread the
    bounds in BENCHMARK.json are set from."""
    xs = list(values)
    q1, _q2, q3 = statistics.quantiles(xs, n=4)
    return (q3 - q1) / statistics.median(xs)
