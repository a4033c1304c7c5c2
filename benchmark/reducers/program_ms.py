"""Median device time of one compiled program (an event of the device's
``XLA Modules`` line whose name matches), in ms."""

import re

from .. import trace as tr
from ..stats import percentile


def programs(t, pattern, what):
    rx = re.compile(pattern)
    p = t["planes"][0]
    t0, t1 = t["window"]
    evs = [e for e in t["events"] if e.plane == p and e.line == "XLA Modules"
           and e.end > t0 and e.start < t1]
    hit = [e for e in evs if rx.search(e.name)]
    if not hit:
        names = sorted({tr.stable_name(e.name) for e in evs})
        raise tr.TraceError(f"no program matches {pattern!r} ({what}); "
                            f"the trace holds {names}")
    return hit, evs


def read(run, params):
    t = run.get("trace")
    if t is None:
        return None
    hit, _ = programs(t, params["pattern"], "program_ms")
    return 1e3 * percentile([e.dur for e in hit], 50)
