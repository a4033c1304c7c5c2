"""The device's idle time in the traced slice, laid to what the PROGRAM
was doing: the program's spans are cut into their innermost pieces, and
each piece gets the idle time that lies inside it, so a gap that lasts
through several spans is split where the spans change. A metric takes
the idle time of the spans it lists under ``spans`` (a span and its
children), or with ``except`` all the slice's idle time that those take
not, the time outside every span too, so that the metrics of one family
sum to the slice's idle time. In ms per span of the name ``per`` (one
serving step). With ``note`` the whole table by span is printed."""

from .. import program_spans as ps
from .. import trace as tr
from ..stats import percentile

OUTSIDE = "outside every span"


def idle_by_span(t, prefix):
    """{span name: idle seconds inside its own pieces} over the slice,
    with what lies in none under ``OUTSIDE``."""
    t0, t1 = t["window"]
    gaps, end = [], t0
    for s, e in sorted(tr.clip(t["ops"][t["planes"][0]], t0, t1)):
        if s > end:
            gaps.append((end, s))
        end = max(end, e)
    if t1 > end:
        gaps.append((end, t1))
    pieces = ps.innermost_pieces(ps.in_window(t, prefix))
    rows, first = {}, 0
    for gs, ge in gaps:      # both in order, neither overlaps itself
        while first < len(pieces) and pieces[first].end <= gs:
            first += 1
        for p in pieces[first:]:
            if p.start >= ge:
                break
            rows[p.name] = rows.get(p.name, 0.0) \
                + min(p.end, ge) - max(p.start, gs)
    rows[OUTSIDE] = sum(ge - gs for gs, ge in gaps) - sum(rows.values())
    return rows


def read(run, params):
    t = run.get("trace")
    if t is None:
        return None
    steps = ps.named(t, params["per"])
    if steps is None:
        return None
    prefix = ps.family(params["per"])
    rows = idle_by_span(t, prefix)
    if "except" in params:
        mine = [s for n, s in rows.items() if not ps.under(n, params["except"])]
    else:
        mine = [s for n, s in rows.items() if ps.under(n, params["spans"])]
    if params.get("note"):
        lens = {}
        for e in ps.in_window(t, prefix):
            lens.setdefault(e.name, []).append(e.dur)
        run["notes"].append(
            f"idle by program span: {sum(rows.values()):.6f} s idle in a "
            f"slice of {t['window_s']:.6f} s holding {len(steps)} "
            f"{params['per']}, {rows[OUTSIDE]:.6f} {OUTSIDE}; span, n, "
            f"median ms, idle s inside its own time: "
            + ", ".join(f"{n} {len(x)} {1e3 * percentile(x, 50):.3f} "
                        f"{rows.get(n, 0.0):.6f}"
                        for n, x in sorted(lens.items())))
    return 1e3 * sum(mine) / len(steps)
