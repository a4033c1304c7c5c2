"""Median time, in ms, from one of the program's instants to another of
the same request (``req/submit`` to ``serving/admit``: the wait in the
queue), over the requests that have both inside the traced slice. The
request id is the instants' first string argument."""

from .. import program_spans as ps
from .. import trace as tr
from ..stats import percentile


def read(run, params):
    t = run.get("trace")
    if t is None:
        return None
    starts = ps.named(t, params["start"])
    if starts is None:
        return None
    first = {}
    for e in starts:
        first.setdefault(ps.first_argument(e), e.start)
    xs = []
    for e in ps.named(t, params["end"]):
        rid = ps.first_argument(e)
        if rid in first:
            xs.append(e.start - first.pop(rid))
    if not xs:
        raise tr.TraceError(f"no request has both {params['start']} and "
                            f"{params['end']} in the traced window")
    run["notes"].append(f"{params['start']} -> {params['end']}: n={len(xs)}")
    return 1e3 * percentile(xs, 50)
