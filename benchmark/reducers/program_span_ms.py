"""Median length, in ms, of one of the program's own spans in the traced
slice (profiler's clock). With ``sum_inside``, the lengths of the spans
of that name are first summed inside each span of the parent's name, and
the median is over the parents: the scheduler runs several times a step."""

from .. import program_spans as ps
from ..stats import percentile


def read(run, params):
    t = run.get("trace")
    if t is None:
        return None
    spans = ps.named(t, params["span"])
    if spans is None:
        return None
    parent = params.get("sum_inside")
    if parent is None:
        xs = [e.dur for e in spans]
    else:
        xs = [sum(e.dur for e in ps.inside(spans, p))
              for p in ps.named(t, parent)]
    run["notes"].append(
        f"{params['span']}: n={len(xs)} in the slice"
        + (f" (summed inside each {parent})" if parent else "")
        + f", min {1e3 * min(xs):.3f} max {1e3 * max(xs):.3f} ms")
    return 1e3 * percentile(xs, 50)
