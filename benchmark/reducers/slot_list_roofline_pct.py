"""The page-list read's share of its roofline over the traced slice in a
stack of two cache rules, where one kernel (``paged_sparse_attn_slots``)
serves both kinds of layer with results of one shape: the least time the
chip could take for the calls the device trace holds (operations and
bytes by ``peaks_mellum.slot_list_call``) over the time they took. What
the calls of a decode step list comes from that step's own
``serving/decode/dispatch`` span: ``full_pages:<n>`` (the pages of every
key its live slots list, read by each full layer) and ``window_pages:<n>``
(the whole pages of their rings that go through the kernel, read by each
window layer), summed over the SLICE's steps and shared among its calls.
A program without the kernel or the counts leaves the metric out."""

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
    pages = {key: argument_sum(spans, key)
             for key in ("full_pages", "window_pages")}
    if not evs or not all(n for _, n in pages.values()):
        return None
    steps = pages["full_pages"][1]
    n_full, n_window = params["full_layers"], params["window_layers"]
    listed = n_full * pages["full_pages"][0] \
        + n_window * pages["window_pages"][0]
    # the slice may cut a step: its calls over the calls its spans stand for
    share = len(evs) / ((n_full + n_window) * steps)
    c = pm.slot_list_call(listed * share, params["slots"] * len(evs),
                          params["heads"], params["kv_heads"],
                          params["head_dim"], params["block_size"],
                          params["itemsize"])
    # bytes and operations of all the calls together; the rows' own
    # traffic was given for every call's slots at once
    took = sum(e.dur for e in evs)
    r = pk.roofline_share(c["flops"], c["bytes"], took,
                          pk.peaks_for(run["device"]["kind"]))
    run["notes"].append(
        f"{name}: {len(evs)} calls {1e3 * took:.3f} ms over {steps} decode "
        f"steps of the traced window, a step listing "
        f"{pages['full_pages'][0] / steps:.1f} pages of every key (x "
        f"{n_full} layers) and {pages['window_pages'][0] / steps:.1f} of the "
        f"rings (x {n_window}); {r['bound']}-bound, "
        f"{c['bytes'] / took / 1e9:.1f} GB/s and "
        f"{c['flops'] / took / 1e12:.2f} TFLOP/s achieved")
    return r["share_pct"]
