"""The routed experts' grouped products' share of their roofline over the
traced slice: the least time the chip could take for the calls the device
trace holds (operations and bytes by ``peaks_mellum.experts_product``)
over the time they took. A call is found by the kernel's name (``gmm``)
and filed by the rows of its result: ``decode_rows`` a decode step's, any
other a prompt chunk's. How many experts a call touched and how many rows
were real come from the program's own counts of the SLICE's steps, which
its ``serving/decode/emit`` spans carry (``experts:<n>``,
``assignments:<n>`` of the step read there, summed over its layers;
``chunk_experts:<n>``, ``chunk_assignments:<n>`` of the prompt chunks
read with it): a step's calls share the step's mean, never the window's.
A program without the kernel or without the counts (the parent of the PR
that brought them) leaves the metric out."""

import re

from .. import peaks as pk
from .. import peaks_mellum as pm
from .. import program_spans as ps
from .. import trace as tr

_SHAPE = re.compile(r"\[(\d+),(\d+)\]")


def argument_sum(spans, key):
    """(the sum, the count) of the ``<key>:<n>`` arguments the spans
    carry."""
    rx = re.compile(rf"\b{key}:(\d+)")
    hits = [int(m.group(1)) for e in spans for m in [rx.search(e.text)] if m]
    return sum(hits), len(hits)


def read(run, params):
    t = run.get("trace")
    if t is None:
        return None
    t0, t1 = t["window"]
    name = params["kernel"]
    evs = tr.outermost([e for e in t["ops"][t["planes"][0]]
                        if tr.stable_name(e.name).startswith(name)
                        and e.start >= t0 and e.end <= t1])
    emits = [e for e in ps.in_window(t, "serving/")
             if e.name == "serving/decode/emit"]
    layers, wide, narrow = params["layers"], params["d_model"], params["d_ff"]
    counts = {}
    for kind, pre in (("decode", ""), ("chunk", "chunk_")):
        touched, n = argument_sum(emits, pre + "experts")
        rows, _ = argument_sum(emits, pre + "assignments")
        if n:       # a layer's call, averaged over the slice's programs
            per = n if kind == "decode" else max(
                argument_sum(emits, "chunks")[0], 1)
            counts[kind] = (touched / (layers * per), rows / (layers * per))
    if not evs or "decode" not in counts:
        return None
    flops = nbytes = least = 0.0
    kinds = {}
    peaks = pk.peaks_for(run["device"]["kind"])
    for e in evs:
        m = _SHAPE.search(tr.stable_name(e.name))
        rows, n = int(m.group(1)), int(m.group(2))
        kind = "decode" if rows == params["decode_rows"] else "chunk"
        if kind not in counts:
            continue
        touched, real = counts[kind]
        c = pm.experts_product(real, touched, narrow if n == wide else wide,
                               n, params["itemsize"])
        flops += c["flops"]
        nbytes += c["bytes"]
        least += max(c["flops"] / peaks["flops_per_s"],
                     c["bytes"] / peaks["bytes_per_s"])
        k, s = kinds.get(kind, (0, 0.0))
        kinds[kind] = (k + 1, s + e.dur)
    took = sum(s for _, s in kinds.values())
    if not took:
        return None
    run["notes"].append(
        f"{name}: " + ", ".join(
            f"{k} {kind} calls {1e3 * s:.3f} ms ({counts[kind][0]:.1f} of "
            f"{params['experts']} experts touched and {counts[kind][1]:.0f} "
            f"rows a call)" for kind, (k, s) in sorted(kinds.items()))
        + f" in the traced window, {nbytes / took / 1e9:.1f} GB/s and "
        f"{flops / took / 1e12:.2f} TFLOP/s achieved")
    return 100.0 * least / took
