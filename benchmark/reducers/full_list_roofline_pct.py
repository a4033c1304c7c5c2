"""The page-list read's share of its roofline over the traced slice in a
stack whose ``full_attn`` layers alone keep pages
(``paged_sparse_attn_slots``, a row a slot): the least time the chip
could take for the calls the device trace holds (operations and bytes by
``peaks_mellum.slot_list_call``) over the time they took. What the calls
of a decode step list comes from that step's own
``serving/decode/dispatch`` span (``full_pages:<n>``: the pages its live
slots list, read by each full layer), summed over the SLICE's steps and
shared among its calls. A program without the kernel or the count leaves
the metric out."""

from .. import peaks as pk
from .. import peaks_mellum as pm
from .. import program_spans as ps
from .. import trace as tr
from .experts_roofline_pct import argument_sum


def read(run, params):
    t = run.get("trace")
    if t is None:
        return None
    t0, t1 = t["window"]
    name = params["kernel"]
    evs = tr.outermost([e for e in t["ops"][t["planes"][0]]
                        if tr.stable_name(e.name).startswith(name)
                        and e.start >= t0 and e.end <= t1])
    spans = [e for e in ps.in_window(t, "serving/")
             if e.name == "serving/decode/dispatch"]
    pages, steps = argument_sum(spans, "full_pages")
    if not evs or not steps:
        run["notes"].append(f"{name}: {len(evs)} calls and {steps} decode "
                            f"steps with full_pages in the slice: no value")
        return None
    layers = params["full_layers"]
    # the slice may cut a step: its calls over the calls its spans stand for
    share = len(evs) / (layers * steps)
    c = pm.slot_list_call(layers * pages * share, params["slots"] * len(evs),
                          params["heads"], params["kv_heads"],
                          params["head_dim"], params["block_size"],
                          params["itemsize"])
    took = sum(e.dur for e in evs)
    r = pk.roofline_share(c["flops"], c["bytes"], took,
                          pk.peaks_for(run["device"]["kind"]))
    run["notes"].append(
        f"{name}: {len(evs)} calls {1e3 * took:.3f} ms over {steps} decode "
        f"steps of the traced window, a step listing {pages / steps:.1f} "
        f"pages (x {layers} layers); {r['bound']}-bound, "
        f"{c['bytes'] / took / 1e9:.1f} GB/s and "
        f"{c['flops'] / took / 1e12:.2f} TFLOP/s achieved")
    return r["share_pct"]
