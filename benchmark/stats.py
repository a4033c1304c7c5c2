"""Percentile, spread and latency arithmetic (pure Python, no JAX)."""

import math
import statistics
from typing import Dict, List, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    closest ranks of the sorted sample (numpy's default rule)."""
    if not values:
        raise ValueError("percentile of an empty sample")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def samples_beyond(n: int, q: float) -> int:
    """How many of n samples lie beyond the q-th percentile."""
    return int(math.floor(n * (100.0 - q) / 100.0))


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of ``statistics.quantiles(values, n=4)``."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def due_latencies(due_s: Sequence[float], done_s: Sequence[float]) -> List[float]:
    """Latency of each request from when it was DUE, not from when the
    generator got round to sending it."""
    if len(due_s) != len(done_s):
        raise ValueError("due and done differ in length")
    return [d - a for a, d in zip(due_s, done_s)]


def lateness(due_s: Sequence[float], sent_s: Sequence[float]) -> Dict[str, float]:
    """How late the generator ran: sent minus due, never negative."""
    late = [max(0.0, s - d) for d, s in zip(due_s, sent_s)]
    if not late:
        return {"n": 0, "median_ms": 0.0, "p95_ms": 0.0, "max_ms": 0.0}
    return {"n": len(late),
            "median_ms": 1e3 * percentile(late, 50),
            "p95_ms": 1e3 * percentile(late, 95),
            "max_ms": 1e3 * max(late)}


def token_gaps(token_times: Sequence[Sequence[float]]) -> List[float]:
    """Every gap between successive tokens of one request, over all
    requests (a request with one token has no gap)."""
    gaps = []
    for ts in token_times:
        gaps.extend(b - a for a, b in zip(ts, ts[1:]))
    return gaps
