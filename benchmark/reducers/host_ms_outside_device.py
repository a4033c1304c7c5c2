"""Length of each of the benchmark's spans of one name less the time the
device was busy inside it: what the host added. Median, in ms."""

from .. import trace as tr
from ..profiling import spans_in_window
from ..stats import percentile


def read(run, params):
    t = run.get("trace")
    if t is None:
        return None
    ops = t["ops"][t["planes"][0]]
    spans = spans_in_window(t, params["span"])
    return 1e3 * percentile(
        [h.dur - tr.busy_seconds(ops, h.start, h.end) for h in spans], 50)
