"""A named kernel's share of its roofline over the traced slice: the
least time the chip could take for the calls the device trace holds
(operations and bytes by ``peaks_sala.py``) over the time they took. A
kernel is found by the ``name=`` its ``pallas_call`` carries; a program
without it (the parent of the PR that brought it) leaves the metric out.

``paged_sparse_attn``: a call's pages come from what the program counted
(the runner's counters named under ``pages_counter``): calls of
``decode_rows`` rows are a decode step's, every other a prompt chunk's.
``lightning_chunk``: every call has the shapes the file gives."""

import re

from .. import peaks as pk
from .. import peaks_sala as ps
from .. import trace as tr

_ROWS = re.compile(r"\[(\d+),")


def read(run, params):
    t = run.get("trace")
    if t is None:
        return None
    t0, t1 = t["window"]
    name = params["kernel"]
    # the call itself: an operation that merely takes the kernel's result
    # (a fusion, a reshape) names it too, in its operands
    evs = tr.outermost([e for e in t["ops"][t["planes"][0]]
                        if tr.stable_name(e.name).startswith(name)
                        and e.start >= t0 and e.end <= t1])
    if not evs:
        return None
    flops = nbytes = 0.0
    kinds = {}
    for e in evs:
        if name == "lightning_chunk":
            c = ps.lightning_chunk_call(params["chunk"], params["heads"],
                                        params["head_dim"], params["block"],
                                        params["itemsize"])
            kind = "chunk"
        else:
            m = _ROWS.search(tr.stable_name(e.name).partition(" ")[2])
            rows = int(m.group(1)) if m else params["decode_rows"]
            kind = "decode" if rows == params["decode_rows"] else "chunk"
            pages = run["spans"].counters.get(params["pages_counter"][kind])
            if pages is None:
                return None
            c = ps.paged_sparse_call(pages, rows, params["heads_per_row"],
                                     params["head_dim"], params["block_size"],
                                     params["itemsize"])
        flops += c["flops"]
        nbytes += c["bytes"]
        n, s = kinds.get(kind, (0, 0.0))
        kinds[kind] = (n + 1, s + e.dur)
    took = sum(e.dur for e in evs)
    r = pk.roofline_share(flops, nbytes, took, pk.peaks_for(run["device"]["kind"]))
    run["notes"].append(
        f"{name}: " + ", ".join(f"{n} {k} calls {1e3 * s:.3f} ms"
                                for k, (n, s) in sorted(kinds.items()))
        + f" in the traced window, {r['bound']}-bound, "
        f"{nbytes / took / 1e9:.1f} GB/s and {flops / took / 1e12:.2f} TFLOP/s "
        f"achieved")
    return r["share_pct"]
