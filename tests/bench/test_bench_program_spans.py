"""The readers PR 24 adds, on hand-written events with a known answer:
the program's own spans in a traced slice (self time with nested
children, an idle gap split among the innermost spans it lasts through,
the four idle metrics summing to the idle time, an error for a span that
is not there, nothing for a program from before PR 24), the compile
account by program name, and the enlarged BENCHMARK.json."""

import glob
import importlib
import os

import pytest

from benchmark import manifest as mf
from benchmark import profiling
from benchmark import program_spans as ps
from benchmark import trace as tr
from benchmark.reducers import (compile_account, idle_by_program_span,
                                idle_pct, program_span_ms, rid_interval_ms)

D0 = "/device:TPU:0"
NEW_METRICS = sorted(
    os.path.basename(f)[:-5] for f in glob.glob(
        os.path.join(mf.BENCH_DIR, "metrics", "*.json"))
    if mf.load_json(f)["reducer"] in ("program_span_ms", "idle_by_program_span",
                                      "compile_account", "rid_interval_ms"))
IDLE_FAMILY = ("idle_in_sched_ms.serve", "idle_in_decode_host_ms.serve",
               "idle_in_prefill_host_ms.serve", "idle_outside_step_ms.serve")


def op(name, start, dur):
    return tr.Ev(name, start, dur, D0, tr.OPS_LINE, name)


def span(name, start, dur, *args):
    return tr.Ev(name, start, dur, "/host:CPU", "python",
                 " ".join((name,) + args))


# Two serving steps in a window of 20 s. The device runs the prefill at
# [3, 5], a decode step at [6.5, 9] and the next at [13, 19]; it idles
# [0, 3] (1 s before the step, 1 s of scheduling, 1 s of packing the
# prompt), [5, 6.5] (picking the token, then packing the slot arrays),
# [9, 13] (emit, export, between the steps, the next step's scheduling
# and packing) and [19, 20] (the wait's tail and the emit).
OPS = [op("fusion.1", 3.0, 2.0), op("fusion.2", 6.5, 2.5), op("fusion.3", 13.0, 6.0)]
SPANS = [
    span(profiling.WINDOW_SPAN, 0.0, 20.0),
    span("bench/serve_step", 0.9, 9.7),
    span("req/submit", 0.5, 0.0, "r7"),
    span("serving/step", 1.0, 9.5),
    span("serving/schedule", 1.0, 0.2, "expire"),
    span("serving/schedule", 1.2, 0.8, "admit"),
    span("serving/admit", 1.9, 0.0, "r7"),
    span("serving/prefill", 2.0, 4.0, "r7"),
    span("serving/prefill/pack", 2.0, 1.0),
    span("serving/prefill/dispatch", 3.0, 0.5),
    span("serving/prefill/scatter", 3.5, 0.5),
    span("serving/prefill/pick", 4.0, 2.0),
    span("serving/schedule", 6.0, 0.1, "capacity"),
    span("serving/decode", 6.1, 3.9, "r7|r8"),
    span("serving/decode/pack", 6.1, 0.4),
    span("serving/decode/dispatch", 6.5, 0.1),
    span("serving/decode/wait", 6.6, 2.4),
    span("serving/decode/emit", 9.0, 1.0),
    span("serving/export", 10.0, 0.5),
    span("serving/step", 11.0, 9.0),
    span("serving/schedule", 11.0, 0.5, "expire"),
    span("serving/schedule", 11.5, 0.5, "capacity"),
    span("serving/decode", 12.0, 7.8, "r7|r8"),
    span("serving/decode/pack", 12.0, 1.0),
    span("serving/decode/dispatch", 13.0, 0.2),
    span("serving/decode/wait", 13.2, 6.0),
    span("serving/decode/emit", 19.2, 0.6),
    span("serving/export", 19.8, 0.2),
]


def run_of(events):
    return {"trace": profiling.traced_run(events, 1), "notes": []}


@pytest.fixture
def run():
    return run_of(OPS + SPANS)


def metric(run, name):
    spec = mf.Manifest().metric_file(name)
    reader = importlib.import_module(f"benchmark.reducers.{spec['reducer']}")
    return reader.read(run, spec["params"])


def test_the_programs_spans_are_found_with_their_arguments(run):
    assert ps.family("serving/decode/pack") == "serving/"
    mine = ps.in_window(run["trace"], "serving/")
    assert {"serving/step", "serving/admit"} <= {e.name for e in mine}
    assert not {"bench/serve_step", "req/submit"} & {e.name for e in mine}
    assert len(ps.named(run["trace"], "serving/step")) == 2
    dec = ps.named(run["trace"], "serving/decode")[0]
    assert ps.first_argument(dec).split("|") == ["r7", "r8"]
    assert ps.under("serving/decode/pack", ["serving/decode"])
    assert not ps.under("serving/decode_other", ["serving/decode"])
    assert not ps.under("serving/prefill_chunk", ["serving/prefill"])


def test_self_time_of_a_parent_is_its_length_less_its_children(run):
    selfs = {(e.name, e.start): s
             for e, s in tr.self_times(ps.in_window(run["trace"], "serving/"))}
    # the first step: 9.5 s less 2 x schedule, prefill, schedule, decode, export
    assert selfs[("serving/step", 1.0)] == pytest.approx(9.5 - 1.0 - 4.0 - 0.1 - 3.9 - 0.5)
    assert selfs[("serving/prefill", 2.0)] == pytest.approx(0.0)
    assert selfs[("serving/decode", 12.0)] == pytest.approx(0.0)
    assert selfs[("serving/decode/pack", 12.0)] == pytest.approx(1.0)


def test_median_length_of_a_span_and_the_sum_inside_a_parent(run):
    assert metric(run, "decode_pack_ms.serve") == pytest.approx(1e3 * (0.4 + 1.0) / 2)
    assert metric(run, "decode_emit_ms.serve") == pytest.approx(1e3 * (1.0 + 0.6) / 2)
    assert metric(run, "prefill_span_ms.serve") == pytest.approx(4000.0)
    # the scheduler ran three times in the first step (1.1 s), twice in
    # the second (1.0 s)
    assert metric(run, "sched_ms.serve") == pytest.approx(1050.0)
    assert any("serving/prefill: n=1 " in n for n in run["notes"])


def test_an_idle_gap_is_split_among_the_innermost_spans_it_lasts_through(run):
    rows = idle_by_program_span.idle_by_span(run["trace"], "serving/")
    # [0, 3]: a second before the step, one of scheduling, one of packing
    # the prompt. [5, 6.5]: the pick 1, the scheduler 0.1, the pack 0.4.
    assert rows["serving/prefill/pack"] == pytest.approx(1.0)
    assert rows["serving/prefill/pick"] == pytest.approx(1.0)
    # [9, 13]: emit 1, export 0.5, between the steps 0.5, schedule 1,
    # pack 1. [19, 20]: the wait's tail 0.2, emit 0.6, export 0.2.
    assert rows["serving/decode/pack"] == pytest.approx(0.4 + 1.0)
    assert rows["serving/decode/emit"] == pytest.approx(1.0 + 0.6)
    assert rows["serving/decode/wait"] == pytest.approx(0.2)
    assert rows["serving/schedule"] == pytest.approx(1.0 + 0.1 + 1.0)
    assert rows["serving/export"] == pytest.approx(0.5 + 0.2)
    assert rows[idle_by_program_span.OUTSIDE] == pytest.approx(1.0 + 0.5)
    assert "serving/step" not in rows      # its children leave it no idle time
    assert sum(rows.values()) == pytest.approx(20.0 - 10.5)


def test_the_four_idle_metrics_sum_to_the_idle_time_of_the_slice(run):
    got = {name: metric(run, name) for name in IDLE_FAMILY}
    t = run["trace"]
    idle_s = t["window_s"] * idle_pct.read(run, {}) / 100.0
    steps = len(ps.named(t, "serving/step"))
    assert sum(got.values()) * steps / 1e3 == pytest.approx(idle_s)
    assert got["idle_in_prefill_host_ms.serve"] == pytest.approx(1e3 * 2.0 / 2)
    assert got["idle_in_decode_host_ms.serve"] == pytest.approx(1e3 * 3.2 / 2)
    assert got["idle_in_sched_ms.serve"] == pytest.approx(1e3 * 2.1 / 2)
    # the export's 0.7 s and the 1.5 s outside both steps
    assert got["idle_outside_step_ms.serve"] == pytest.approx(1e3 * 2.2 / 2)
    (note,) = [n for n in run["notes"] if n.startswith("idle by program span")]
    assert "9.500000 s idle in a slice of 20.000000 s holding 2 serving/step" in note
    assert "1.500000 outside every span" in note
    assert "serving/decode/emit 2 800.000 1.600000" in note
    assert "serving/schedule 5 500.000 2.100000" in note
    assert "serving/decode/pack 2 700.000 1.400000" in note
    assert "serving/export 2 350.000 0.700000" in note
    assert "serving/step 2 9250.000 0.000000" in note


def test_idle_outside_every_span_and_in_the_scheduler_is_told_apart():
    # one step; the device idles [0, 1.5] outside any span, [2, 3] in the
    # scheduler and [9, 10] in the step's own remainder (the export)
    events = [op("fusion.0", 1.5, 0.5), op("fusion.1", 3.0, 6.0),
              span(profiling.WINDOW_SPAN, 0.0, 10.0),
              span("serving/step", 2.0, 8.0),
              span("serving/schedule", 2.0, 1.0, "admit"),
              span("serving/admit", 2.5, 2e-6, "r1"),     # an instant
              span("serving/decode", 3.0, 6.0, "r1"),
              span("serving/export", 9.0, 1.0)]
    run = run_of(events)
    assert metric(run, "idle_in_sched_ms.serve") == pytest.approx(1000.0)
    assert metric(run, "idle_outside_step_ms.serve") == pytest.approx(2500.0)
    assert metric(run, "idle_in_decode_host_ms.serve") == 0.0
    assert idle_by_program_span.idle_by_span(run["trace"], "serving/") == \
        pytest.approx({idle_by_program_span.OUTSIDE: 1.5,
                       "serving/schedule": 1.0, "serving/export": 1.0})


def test_nested_spans_are_cut_into_innermost_pieces(run):
    mine = ps.in_window(run["trace"], "serving/")
    pieces = ps.innermost_pieces(mine)
    assert all(a.end <= b.start + 1e-12 for a, b in zip(pieces, pieces[1:]))
    by = {}
    for e in pieces:
        by[e.name] = by.get(e.name, 0.0) + e.dur
    selfs = {}
    for e, s_ in tr.self_times([e for e in mine
                                if e.dur >= ps.SHORTEST_SPAN_S]):
        selfs[e.name] = selfs.get(e.name, 0.0) + s_
    assert by == pytest.approx({n: s_ for n, s_ in selfs.items() if s_ > 0})


def test_a_span_that_is_not_there_is_an_error_never_a_zero(run):
    with pytest.raises(tr.TraceError, match="engine/train_batch/feed"):
        metric(run, "feed_ms.train")
    with pytest.raises(tr.TraceError):
        program_span_ms.read(run, {"span": "serving/no_such_span"})
    with pytest.raises(tr.TraceError):
        idle_by_program_span.read(run, {"spans": ["serving/decode"],
                                        "per": "serving/no_such_span"})


def test_a_program_from_before_pr_24_leaves_the_metrics_out(monkeypatch):
    """Only the benchmark's own spans in the trace, and no compile account."""
    monkeypatch.setattr(ps, "compile_account", lambda: None)
    run = run_of(OPS + [s for s in SPANS if s.name.startswith("bench/")])
    for name in NEW_METRICS:
        assert metric(run, name) is None, name
    assert run["notes"] == []
    assert program_span_ms.read({"notes": []}, {"span": "serving/step"}) is None


def test_a_program_that_stops_recording_its_spans_is_an_error():
    """The annotations break (the account is there, the spans are not):
    no metric may quietly vanish from a run that passes."""
    run = run_of(OPS + [s for s in SPANS if s.name.startswith("bench/")])
    for name in NEW_METRICS:
        if mf.Manifest().metric_file(name)["reducer"] != "compile_account":
            with pytest.raises(tr.TraceError, match="no program span"):
                metric(run, name)


def test_training_spans_are_read_the_same_way():
    events = [op("fusion.1", 0.5, 9.0),
              span(profiling.WINDOW_SPAN, 0.0, 20.0),
              span("engine/train_batch", 0.0, 1.0),
              span("engine/train_batch/feed", 0.0, 0.25),
              span("engine/train_batch/dispatch", 0.25, 0.5),
              span("engine/train_batch/after", 0.75, 0.25),
              span("engine/train_batch", 10.0, 2.0),
              span("engine/train_batch/feed", 10.0, 0.75),
              span("engine/train_batch/dispatch", 10.75, 1.0)]
    run = run_of(events)
    assert metric(run, "feed_ms.train") == pytest.approx(500.0)
    assert metric(run, "dispatch_ms.train") == pytest.approx(750.0)


def test_queue_wait_is_admit_less_submit_of_the_same_request(run):
    assert metric(run, "queue_wait_ms") == pytest.approx(1400.0)
    events = [e for e in OPS + SPANS if e.name != "serving/admit"]
    events.append(span("serving/admit", 1.9, 0.0, "another"))
    with pytest.raises(tr.TraceError):
        metric(run_of(events), "queue_wait_ms")


ACCOUNT = {
    "ds_train_step": {"trace": {"count": 2, "seconds": 1.0},
                      "lower": {"count": 2, "seconds": 0.5},
                      "compile": {"count": 2, "seconds": 4.0}},
    "ds_init_opt_state": {"trace": {"count": 1, "seconds": 0.25},
                          "lower": {"count": 1, "seconds": 0.125},
                          "compile": {"count": 1, "seconds": 0.125}},
    "ds_inner_only_traced": {"trace": {"count": 3, "seconds": 0.5}},
    "eager": {"lower": {"count": 40, "seconds": 2.0},
              "compile": {"count": 40, "seconds": 3.0}},
    "step": {"lower": {"count": 1, "seconds": 9.0}},     # the reference's
}


def test_compile_account_counts_the_programs_of_the_prefix(monkeypatch):
    monkeypatch.setattr(ps, "compile_account", lambda: ACCOUNT)
    run = {"notes": []}
    assert metric(run, "programs_lowered.train") == 3
    assert metric(run, "compile_s.train") == pytest.approx(6.5)
    (note,) = run["notes"]
    assert note.index("ds_train_step: 2,") < note.index("ds_init_opt_state: 1,") \
        < note.index("eager: 40,")
    # the rest, the reference's step among it, is named as other and not counted
    assert note.index("eager: 40,") < note.index("other step: 1,")


def test_compile_account_of_a_program_without_one_leaves_the_metric_out(monkeypatch):
    run = {"notes": []}
    monkeypatch.setattr(ps, "compile_account", lambda: None)
    assert metric(run, "programs_lowered.serve") is None
    # an account that names nothing under the prefix (all lambdas and fn)
    monkeypatch.setattr(ps, "compile_account",
                        lambda: {"fn": ACCOUNT["ds_train_step"]})
    with pytest.raises(tr.TraceError, match="no lowered program ds_"):
        metric(run, "compile_s.serve")


def test_compile_account_reads_the_programs_own():
    """Against the real account: a named program is lowered once a shape."""
    import jax
    import jax.numpy as jnp

    from deeperspeed_tpu import monitor

    monitor.install_compile_listener()

    def zz_bench_probe(x):
        return x + 1

    f = jax.jit(zz_bench_probe)
    f(jnp.ones(3))
    f(jnp.ones(5))
    run = {"notes": []}
    params = {"prefix": "zz_bench_", "what": "lowered"}
    assert compile_account.read(run, params) == 2
    assert compile_account.read(run, dict(params, what="seconds")) > 0.0


@pytest.mark.parametrize("name", NEW_METRICS)
def test_manifest_and_validate_accept_each_new_metric(name):
    man = mf.Manifest()
    assert mf.validate(man.data) == []
    spec = man.metric_file(name)
    assert callable(importlib.import_module(
        f"benchmark.reducers.{spec['reducer']}").read)
    listed = [m for m in man.data["per_layer"] if m["name"] == name]
    if name == "queue_wait_ms":       # a file only, like slot_occupancy_pct
        assert listed == []
        return
    (m,) = listed
    (cell,) = m["workloads"]
    assert cell.endswith(name.rsplit(".", 1)[1])
    assert name in {x["name"] for x in man.metrics_for(cell, "per_layer")}
    other = next(c for c in man.cells() if c != cell)
    assert name not in {x["name"] for x in man.metrics_for(other, "per_layer")}
    assert m["moves"] in {x["name"] for x in man.metrics_for(cell, "end_to_end")}
    assert m["source"] == {"program_span_ms": "program_span",
                           "idle_by_program_span": "device_trace",
                           "compile_account": "program_counter"}[spec["reducer"]]


def test_new_entries_stand_at_the_end_and_nothing_before_them_moved():
    data = mf.Manifest().data
    names = [m["name"] for m in data["per_layer"]]
    old = ["host_dispatch_ms.train", "step_host_ms.serve", "train_step_device_ms",
           "decode_step_device_ms", "prefill_share_pct", "flash_attn_roofline",
           "device_idle_pct.train", "device_idle_pct.serve", "hbm_peak_gib.train",
           "hbm_peak_gib.serve"]
    assert names[:len(old)] == old
    assert sorted(names[len(old):]) == sorted(n for n in NEW_METRICS
                                              if n != "queue_wait_ms")
