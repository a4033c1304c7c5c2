"""Share of the traced window in which a collective ran on the device
and no compute did, averaged over the chips."""

from .. import trace as tr


def read(run, params):
    t = run.get("trace")
    if t is None or len(t["planes"]) < 2:
        return None
    t0, t1 = t["window"]
    exposed = [tr.exposed_collective_seconds(t["events"], p, t0, t1)[1]
               for p in t["planes"]]
    return 100.0 * sum(exposed) / len(exposed) / (t1 - t0)
