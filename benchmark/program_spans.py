"""What the program records of ITSELF: its spans in a profiler trace and
its compile account. ``monitor/tracer.py`` enters a ``TraceAnnotation``
for every span and instant it makes, so that in a traced slice they lie
on the host plane, on the clock of the device operations, with their
string arguments in ``Ev.text`` (``load_xplane`` keeps an argument's
value, not its key: a request id is the first word after the name).
``monitor.compile_account()`` came with them (PR 24). A program that has
neither, as before that PR, gives None and the metric is left out. A
program that has them, and lacks the span a metric names, is an error."""

from typing import List, Optional, Sequence

from . import trace as tr

# instants reach the profiler as annotations of a microsecond or two
SHORTEST_SPAN_S = 1e-5


def compile_account() -> Optional[dict]:
    """The program's account ``{program: {phase: {count, seconds}}}``;
    None for a program without one."""
    try:
        from deeperspeed_tpu.monitor import compile_account as account
    except ImportError:
        return None
    return account()


def family(name: str) -> str:
    """A span's subsystem, the prefix its siblings share: ``serving/``."""
    return name.split("/")[0] + "/"


def in_window(t: dict, prefix: str) -> List[tr.Ev]:
    """The host's spans and instants under ``prefix`` that lie inside the
    traced window, by start."""
    t0, t1 = t["window"]
    return sorted((e for e in t["events"]
                   if e.name.startswith(prefix)
                   and not e.plane.startswith("/device:")
                   and e.start >= t0 and e.end <= t1),
                  key=lambda e: (e.start, -e.dur))


def named(t: dict, name: str) -> Optional[List[tr.Ev]]:
    """The spans of one name inside the window; None where the program
    puts no span on the profiler's clock."""
    mine = in_window(t, family(name))
    hit = [e for e in mine if e.name == name]
    if hit:
        return hit
    if compile_account() is None:
        return None
    raise tr.TraceError(
        f"no program span {name!r} in the traced window; of "
        f"{family(name)!r} it holds {sorted({e.name for e in mine})}")


def under(name: str, roots: Sequence[str]) -> bool:
    """Whether a span's name is one of ``roots`` or a child of one
    (``serving/decode/pack`` is under ``serving/decode``)."""
    return any(name == r or name.startswith(r + "/") for r in roots)


def inside(spans: Sequence[tr.Ev], parent: tr.Ev) -> List[tr.Ev]:
    return [e for e in spans
            if e.start >= parent.start - 1e-12 and e.end <= parent.end + 1e-12]


def innermost_pieces(spans: Sequence[tr.Ev]) -> List[tr.Ev]:
    """Nested spans cut into pieces that do not overlap, each under the
    name of the innermost span that covers it: a parent keeps what its
    children leave (its self time). Instants are left to their parent."""
    out: List[tr.Ev] = []
    stack: List[tr.Ev] = []
    cursor = 0.0

    def close(upto: float):
        nonlocal cursor
        top = stack[-1]
        if upto > cursor:
            out.append(tr.Ev(top.name, cursor, upto - cursor, top.plane,
                             top.line, top.text))
        cursor = max(cursor, upto)

    for e in sorted(spans, key=lambda e: (e.start, -e.dur)):
        if e.dur < SHORTEST_SPAN_S:
            continue
        while stack and e.start >= stack[-1].end:
            close(stack[-1].end)
            stack.pop()
        if stack:
            close(e.start)
        cursor = max(cursor, e.start) if stack else e.start
        stack.append(e)
    while stack:
        close(stack[-1].end)
        stack.pop()
    return out


def first_argument(e: tr.Ev) -> str:
    """The first string argument of a span (a request's ``rid``)."""
    words = e.text[len(e.name):].split()
    return words[0] if words else ""
