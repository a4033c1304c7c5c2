"""Median length of one of the benchmark's own host spans, host clock."""

from ..stats import percentile


def read(run, params):
    xs = run["spans"].durations.get(params["span"])
    if not xs:
        return None
    return 1e3 * percentile(xs, params.get("percentile", 50))
