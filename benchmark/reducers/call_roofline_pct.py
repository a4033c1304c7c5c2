"""A named kernel's share of its roofline over the traced slice, where
every call has the shapes the metric's file gives: the least time the
chip could take for the calls the device trace holds (operations and
bytes by the function ``call`` of the module ``peaks``, given ``shape``)
over the time they took. A kernel is found by the ``name=`` its
``pallas_call`` carries; a program without it (the parent of the PR that
brought it) leaves the metric out."""

import importlib

from .. import peaks as pk
from .. import trace as tr


def read(run, params):
    t = run.get("trace")
    if t is None:
        return None
    t0, t1 = t["window"]
    name = params["kernel"]
    # the call itself: an operation that merely takes the kernel's result
    # names it too, in its operands
    evs = tr.outermost([e for e in t["ops"][t["planes"][0]]
                        if tr.stable_name(e.name).startswith(name)
                        and e.start >= t0 and e.end <= t1])
    if not evs:
        return None
    counts = getattr(importlib.import_module(f"benchmark.{params['peaks']}"),
                     params["call"])(**params["shape"])
    took = sum(e.dur for e in evs)
    flops, nbytes = len(evs) * counts["flops"], len(evs) * counts["bytes"]
    r = pk.roofline_share(flops, nbytes, took,
                          pk.peaks_for(run["device"]["kind"]))
    run["notes"].append(
        f"{name}: {len(evs)} calls {1e3 * took:.3f} ms in the traced window, "
        f"{r['bound']}-bound, {nbytes / took / 1e9:.1f} GB/s and "
        f"{flops / took / 1e12:.2f} TFLOP/s achieved")
    return r["share_pct"]
