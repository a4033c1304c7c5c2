"""Device-busy time inside each of the benchmark's spans of one name
(say, one optimizer step), median over the spans of the traced window,
averaged over the chips."""

from .. import trace as tr
from ..profiling import spans_in_window
from ..stats import percentile


def read(run, params):
    t = run.get("trace")
    if t is None:
        return None
    spans = spans_in_window(t, params["span"])
    per = [sum(tr.busy_seconds(o, h.start, h.end) for o in t["ops"].values())
           / len(t["ops"]) for h in spans]
    return 1e3 * percentile(per, 50)
