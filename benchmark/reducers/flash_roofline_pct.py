"""The flash-attention kernels' share of their roofline: the least time
the chip could take for the calls the trace holds (operations and bytes
from the cell's shapes, by ``peaks.py``) over the time they took.
Forward and backward kernels are told apart by name."""

from .. import peaks as pk
from .. import trace as tr


def read(run, params):
    t = run.get("trace")
    shape = run.get("attention")
    if t is None or shape is None:
        return None
    ops = t["ops"][t["planes"][0]]
    t0, t1 = t["window"]
    inside = [e for e in ops if e.start >= t0 and e.end <= t1]
    peaks = pk.peaks_for(run["device"]["kind"])
    least = took = 0.0
    flops = nbytes = 0.0
    for key, backward in (("forward", False), ("backward", True)):
        evs = tr.outermost(tr.matching(inside, params[key], f"flash {key}"))
        took += sum(e.dur for e in evs)
        flops += len(evs) * pk.flash_call_flops(backward=backward, **shape["flops"])
        nbytes += len(evs) * pk.flash_call_bytes(backward=backward, **shape["bytes"])
    r = pk.roofline_share(flops, nbytes, took, peaks)
    run["notes"].append(f"flash kernels: {took * 1e3:.3f} ms in the traced "
                        f"window, {r['bound']}-bound, "
                        f"{flops / took / 1e12:.2f} TFLOP/s achieved")
    return r["share_pct"]
