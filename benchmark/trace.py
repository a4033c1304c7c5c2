"""From a profiler trace to numbers. Works on plain event lists, so that
it can be checked on hand-written events; ``load_xplane`` is the only
function that touches JAX."""

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

COLLECTIVE_RE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|psum|ppermute", re.I)
OPS_LINE = "XLA Ops"


class TraceError(RuntimeError):
    """The trace lacks what a metric needs (never reported as 0)."""


@dataclasses.dataclass(frozen=True)
class Ev:
    name: str
    start: float        # seconds on the profiler's clock
    dur: float          # seconds
    plane: str = ""
    line: str = ""
    text: str = ""      # name plus the string statistics, for patterns

    @property
    def end(self) -> float:
        return self.start + self.dur


def load_xplane(path: str) -> List[Ev]:
    """Every event of every line of every plane of an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for ln in plane.lines:
            for e in ln.events:
                extra = " ".join(str(v) for _, v in e.stats
                                 if isinstance(v, str))
                out.append(Ev(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                              plane.name, ln.name, f"{e.name} {extra}"))
    return out


def device_planes(events: Iterable[Ev]) -> List[str]:
    """Names of the planes that are accelerator devices, in order."""
    names = {e.plane for e in events if e.plane.startswith("/device:")
             and "CPU" not in e.plane}
    return sorted(names, key=lambda s: [int(x) if x.isdigit() else x
                                        for x in re.split(r"(\d+)", s)])


def device_ops(events: Iterable[Ev], plane: str) -> List[Ev]:
    """The operations that ran on one device: its ``XLA Ops`` line."""
    return sorted((e for e in events if e.plane == plane and e.line == OPS_LINE
                   and e.dur > 0), key=lambda e: (e.start, -e.dur))


def union_length(intervals: Iterable[Tuple[float, float]]) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def clip(events: Iterable[Ev], t0: float, t1: float) -> List[Tuple[float, float]]:
    return [(max(e.start, t0), min(e.end, t1)) for e in events
            if e.end > t0 and e.start < t1]


def busy_seconds(ops: Sequence[Ev], t0: float, t1: float) -> float:
    """Seconds of [t0, t1] in which an operation ran on the device."""
    return union_length(clip(ops, t0, t1))


def self_times(ops: Sequence[Ev]) -> List[Tuple[Ev, float]]:
    """Each event with its duration less what its directly nested events
    cover (a ``while`` holds its body's operations)."""
    out, stack = [], []   # stack of [event, child_time]
    for e in sorted(ops, key=lambda e: (e.start, -e.dur)):
        while stack and e.start >= stack[-1][0].end - 1e-12:
            ev, child = stack.pop()
            out.append((ev, max(0.0, ev.dur - child)))
        if stack:
            stack[-1][1] += e.dur
        stack.append([e, 0.0])
    while stack:
        ev, child = stack.pop()
        out.append((ev, max(0.0, ev.dur - child)))
    return out


_HLO_RE = re.compile(r"^%?([^\s=]+)\s*=\s*\(?\s*([a-z0-9]+\[[0-9,]*\])?")


def stable_name(name: str) -> str:
    """An operation's name without the counter XLA appends, so that the
    same operation keeps its name from one compile to the next. A TPU
    trace names an operation by its whole HLO line (``%fusion.5 =
    bf16[8,128]{...} fusion(...)``): that becomes ``fusion bf16[8,128]``,
    the kind of operation and the shape of its first result."""
    shape = ""
    m = _HLO_RE.match(name)
    if m and "=" in name:
        name, shape = m.group(1), m.group(2) or ""
    name = name.lstrip("%")
    name = re.sub(r"[.\-_]\d+$", "", re.sub(r"\.\d+(?=\.|$)", "", name))
    return f"{name} {shape}".strip()


def top_ops(ops: Sequence[Ev], k: int = 10) -> List[List]:
    """The k operations that took most device time, by stable name."""
    agg: Dict[str, float] = {}
    for e, t in self_times(ops):
        agg[stable_name(e.name)] = agg.get(stable_name(e.name), 0.0) + t
    return [[n, t] for n, t in sorted(agg.items(), key=lambda x: -x[1])[:k]]


def matching(ops: Sequence[Ev], pattern: str, what: str) -> List[Ev]:
    """Events whose name or statistics match; a trace without one is an
    error, because a metric read from nothing would say 0."""
    rx = re.compile(pattern)
    hit = [e for e in ops if rx.search(e.text or e.name)]
    if not hit:
        raise TraceError(f"no device operation matches {pattern!r} ({what})")
    return hit


def outermost(evs: Sequence[Ev]) -> List[Ev]:
    """Drop events nested inside another of the same list."""
    out, end = [], -1.0
    for e in sorted(evs, key=lambda e: (e.start, -e.dur)):
        if e.start >= end - 1e-12:
            out.append(e)
            end = e.end
    return out


def exposed_collective_seconds(events: Sequence[Ev], plane: str, t0: float,
                               t1: float) -> Tuple[float, float]:
    """(collective seconds, seconds of them in which no compute ran) on
    one device. Collectives are taken from every line of the device's
    plane, compute from its operations line."""
    coll = [e for e in events if e.plane == plane and e.dur > 0
            and COLLECTIVE_RE.search(e.name)]
    if not coll:
        raise TraceError(f"no collective operation on {plane}")
    ops = device_ops(events, plane)
    # a while or a fusion that merely CONTAINS a collective is neither
    compute = [e for e, t in self_times(ops)
               if not COLLECTIVE_RE.search(e.name) and t > 0.5 * e.dur]
    c_iv = clip(coll, t0, t1)
    total = union_length(c_iv)
    both = union_length(c_iv) + union_length(clip(compute, t0, t1)) \
        - union_length(c_iv + clip(compute, t0, t1))
    return total, total - both


def idle_gaps(ops: Sequence[Ev], host: Sequence[Ev], t0: float, t1: float,
              k: int = 10) -> List[List]:
    """The idle time of one device by what the host was doing: each gap
    between device operations goes to the host span that covers most of
    it; returns the k largest sums ``[span name, seconds]``."""
    gaps, end = [], t0
    for s, e in sorted(clip(ops, t0, t1)):
        if s > end:
            gaps.append((end, s))
        end = max(end, e)
    if t1 > end:
        gaps.append((end, t1))
    agg: Dict[str, float] = {}
    hs = sorted(host, key=lambda e: e.start)
    for gs, ge in gaps:
        best, cover = "no benchmark span", 0.0
        for h in hs:
            if h.start >= ge:
                break
            c = min(h.end, ge) - max(h.start, gs)
            # the innermost covering span wins ties (later start)
            if c > 0 and c >= cover:
                best, cover = h.name, c
        agg[best] = agg.get(best, 0.0) + (ge - gs)
    return [[n, t] for n, t in sorted(agg.items(), key=lambda x: -x[1])[:k]]


def host_spans(events: Iterable[Ev], prefix: str = "bench/") -> List[Ev]:
    return [e for e in events if e.name.startswith(prefix)
            and not e.plane.startswith("/device:")]


def window_of(spans: Sequence[Ev], name: str) -> Optional[Tuple[float, float]]:
    for e in spans:
        if e.name == name:
            return e.start, e.end
    return None
