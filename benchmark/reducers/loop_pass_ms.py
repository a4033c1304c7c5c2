"""Device time of ONE pass of a looped stack inside a decode step, in ms.
The device's trace keeps neither the scope ``ds.loop`` nor the loop over
the passes as an operation of its own (PR 48's first traced run: the only
``while`` events of a decode program were the scatters of the write), so a
pass is found by what every layer of it calls: the page-list kernel runs
once a (pass, layer) pair, ``passes x layers`` times a step, and a pass
lasts from its first call to the first call of the next (the last pass,
which ends in the head, is not timed). The median over the passes of the
decode programs that lie whole inside the traced slice and hold exactly
that many calls; the passes a step ran come from its own
``serving/decode/dispatch`` span (``passes:<T>``). A program that does not
loop, or without the kernel, leaves the metric out."""

from .. import program_spans as ps
from .. import trace as tr
from ..stats import percentile
from .experts_roofline_pct import argument_sum
from .looped_step_roofline_pct import whole_programs


def read(run, params):
    t = run.get("trace")
    if t is None:
        return None
    progs = whole_programs(t, params["pattern"])
    dispatch = [e for e in ps.in_window(t, "serving/")
                if e.name == "serving/decode/dispatch"]
    passes, steps = argument_sum(dispatch, "passes")
    calls = sorted((e for e in tr.outermost(
        [e for e in t["ops"][t["planes"][0]]
         if tr.stable_name(e.name).startswith(params["kernel"])])),
        key=lambda e: e.start)
    if not progs or not steps or passes <= steps or not calls:
        run["notes"].append(
            f"ds.loop: {len(progs)} decode programs, {steps} steps of "
            f"{passes} passes and {len(calls)} calls of {params['kernel']} in "
            f"the slice: no value")
        return None
    T, L = passes // steps, params["layers"]
    took = []
    for p in progs:
        mine = [e.start for e in calls if p.start <= e.start and e.end <= p.end]
        if len(mine) == T * L:
            took += [mine[(k + 1) * L] - mine[k * L] for k in range(T - 1)]
    if not took:
        raise tr.TraceError(
            f"no decode program of the slice holds {T} x {L} calls of "
            f"{params['kernel']}: the metric's file counts {L} layers a pass")
    run["notes"].append(
        f"ds.loop: {len(took)} passes of {L} layers timed in {len(progs)} "
        f"decode programs of the traced window ({T} passes a step, the last "
        f"not timed); a pass {1e3 * percentile(took, 50):.3f} ms, a program "
        f"{1e3 * percentile([e.dur for e in progs], 50):.3f} ms")
    return 1e3 * percentile(took, 50)
