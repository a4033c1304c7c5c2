"""Share of the device's busy time that the programs whose names match
take (say, the prefill programs)."""

from .. import trace as tr
from .program_ms import programs


def read(run, params):
    t = run.get("trace")
    if t is None:
        return None
    hit, evs = programs(t, params["pattern"], "program_share_pct")
    t0, t1 = t["window"]
    return 100.0 * tr.union_length(tr.clip(hit, t0, t1)) \
        / tr.union_length(tr.clip(evs, t0, t1))
