"""A slice of the measured window under the JAX profiler, and the host
spans the benchmark itself records (on the host's clock, and as
``TraceAnnotation`` on the profiler's, so that idle gaps can be laid to
what the host was doing)."""

import contextlib
import glob
import os
import shutil
import time
from collections import defaultdict

from . import trace as tr
from .manifest import ROOT

TRACE_DIR = os.path.join(ROOT, ".bench_trace")
WINDOW_SPAN = "bench/traced_window"


class Spans:
    """Host spans by name: durations in seconds on the host's clock."""

    def __init__(self):
        self.durations = defaultdict(list)
        self.counters = {}

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/" + name):
            yield
        self.durations[name].append(time.perf_counter() - t0)


class Profiler:
    """start() ... stop() around a few steps; events() parses the result."""

    def __init__(self, cell: str):
        self.dir = os.path.join(TRACE_DIR, cell)
        self.active = False
        self.done = False     # a slice was traced (one slice a run)
        self._ann = None

    def start(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        self._ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._ann.__enter__()
        self.active = True

    def stop(self):
        import jax

        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False

    def events(self):
        files = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not files:
            raise tr.TraceError(f"the profiler wrote no trace under {self.dir}")
        return tr.load_xplane(max(files, key=os.path.getmtime))


def traced_run(events, chips: int) -> dict:
    """What every reader gets from a trace: the device operations of each
    chip, the benchmark's host spans, the traced window, and the busy
    seconds averaged over the chips."""
    planes = tr.device_planes(events)
    if len(planes) < chips:
        raise tr.TraceError(f"trace holds device planes {planes}, "
                            f"the cell used {chips} chip(s)")
    planes = planes[:chips]
    host = tr.host_spans(events)
    win = tr.window_of(host, WINDOW_SPAN)
    if win is None:
        raise tr.TraceError(f"no {WINDOW_SPAN} span in the trace")
    ops = {p: tr.device_ops(events, p) for p in planes}
    for p, o in ops.items():
        if not o:
            lines = sorted({e.line for e in events if e.plane == p})
            raise tr.TraceError(f"no operation ran on {p} (lines: {lines})")
    busy = [tr.busy_seconds(o, *win) for o in ops.values()]
    return {"events": events, "planes": planes, "ops": ops, "host": host,
            "window": win, "busy_s": sum(busy) / len(busy),
            "window_s": win[1] - win[0]}


def spans_in_window(t: dict, name: str):
    """The benchmark's spans ``bench/<name>`` that lie inside the traced
    window; a trace without one is an error."""
    t0, t1 = t["window"]
    spans = [h for h in t["host"] if h.name == "bench/" + name
             and h.start >= t0 and h.end <= t1]
    if not spans:
        raise tr.TraceError(f"no bench/{name} span in the traced window")
    return spans


def breakdown(run: dict) -> dict:
    p = run["planes"][0]
    t0, t1 = run["window"]
    inside = [e for e in run["ops"][p] if e.end > t0 and e.start < t1]
    host = [h for h in run["host"] if h.name != WINDOW_SPAN]
    return {"device_ops": tr.top_ops(inside, 10),
            "idle_gaps": tr.idle_gaps(inside, host, t0, t1, 10)}


def dump(run: dict, path: str, seconds: float = 0.0) -> None:
    """Write what a reader sees of a trace as plain JSON (gzip): the
    device operations and module runs of the first chip and the
    benchmark's host spans, from the traced window's start for
    ``seconds`` (0 = all of it). Fixtures are made with this."""
    import gzip
    import json

    t0, t1 = run["window"]
    if seconds:
        t1 = min(t1, t0 + seconds)
    p = run["planes"]
    keep = [e for e in run["events"]
            if e.start >= t0 and e.end <= t1 and e.dur > 0
            and ((e.plane in p and e.line in (tr.OPS_LINE, "XLA Modules"))
                 or e.name.startswith("bench/"))]
    rows = [[e.name, e.start - t0, e.dur, e.plane, e.line, e.text] for e in keep]
    lines = sorted({(e.plane, e.line) for e in run["events"]})
    with gzip.open(path, "wt") as f:
        json.dump({"window": [0.0, t1 - t0], "lines": lines, "events": rows}, f)


def load_dump(path: str):
    """The events of a file written by ``dump`` (plus its window)."""
    import gzip
    import json

    with gzip.open(path, "rt") as f:
        d = json.load(f)
    evs = [tr.Ev(*r) for r in d["events"]]
    evs.append(tr.Ev(WINDOW_SPAN, d["window"][0], d["window"][1] - d["window"][0],
                     "/host:CPU", "python", WINDOW_SPAN))
    return evs
