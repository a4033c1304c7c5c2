"""A looped stack's decode program's share of its memory roofline over the
traced slice: the least time the chip could take for the decode steps the
device trace holds (bytes and operations by ``peaks_ouro.decode_step``:
the stack's weights once a PASS, the head once, the pages the live slots
list through every cache layer, the rows written) over the time their
programs took. What a step lists comes from that step's own
``serving/decode/dispatch`` span (``full_pages:<n>``, ``passes:<T>``),
summed over the SLICE's steps; its live slots from the ``serving/decode``
span round it (``n_active:<n>``). A program without the counts (the
parent of the PR that brought them) leaves the metric out."""

import re

from .. import peaks as pk
from .. import peaks_ouro as po
from .. import program_spans as ps
from .. import trace as tr
from .experts_roofline_pct import argument_sum


def whole_programs(t, pattern):
    """The compiled programs whose name matches that lie WHOLLY inside the
    traced window (a sum over them is set against the host's counts of the
    same steps: a program the window cuts would count for less than one)."""
    t0, t1 = t["window"]
    rx = re.compile(pattern)
    return [e for e in t["events"] if e.plane == t["planes"][0]
            and e.line == "XLA Modules" and rx.search(e.name)
            and e.start >= t0 and e.end <= t1]


def read(run, params):
    t = run.get("trace")
    if t is None:
        return None
    progs = whole_programs(t, params["pattern"])
    spans = ps.in_window(t, "serving/")
    dispatch = [e for e in spans if e.name == "serving/decode/dispatch"]
    pages, steps = argument_sum(dispatch, "full_pages")
    passes, counted = argument_sum(dispatch, "passes")
    live, _ = argument_sum([e for e in spans if e.name == "serving/decode"],
                           "n_active")
    if not progs or not steps or counted != steps:
        run["notes"].append(
            f"{params['pattern']}: {len(progs)} programs and {steps} decode "
            f"steps with full_pages ({counted} with passes) in the slice: "
            f"no value")
        return None
    if passes != steps * params["shape"]["passes"]:
        raise tr.TraceError(
            f"the program ran {passes / steps:g} passes a step, the metric's "
            f"file counts {params['shape']['passes']}")
    # the slice may cut a step: its programs over the steps its spans stand for
    share = len(progs) / steps
    c = po.decode_step(pages * share / len(progs), live * share / len(progs),
                       **params["shape"])
    took = sum(e.dur for e in progs)
    r = pk.roofline_share(len(progs) * c["flops"], len(progs) * c["bytes"],
                          took, pk.peaks_for(run["device"]["kind"]))
    run["notes"].append(
        f"{params['pattern']}: {len(progs)} programs {1e3 * took:.3f} ms of "
        f"the traced window, a step of {live / steps:.1f} live slots listing "
        f"{pages / steps:.1f} pages and moving {c['bytes'] / 1e9:.2f} GB; "
        f"{r['bound']}-bound, {len(progs) * c['bytes'] / took / 1e9:.1f} GB/s "
        f"achieved")
    return r["share_pct"]
