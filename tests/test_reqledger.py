"""Request-path doctor tests: interval intersection, the by-construction
bucket-sum invariant on synthetic traces, head-of-line blocker naming on
a crafted two-request schedule, exact retry-waste accounting across a
replica failover, SLO burn-rate arithmetic, the slo CLI round-trip, the
widened latency-histogram tail, and token-exactness assertions over the
committed drill traces the CI gate runs against."""

import bisect
import json
import os

import pytest

from deeperspeed_tpu.monitor.metrics import (
    DEFAULT_LATENCY_BUCKETS,
    Histogram,
)
from deeperspeed_tpu.monitor.reqledger import (
    ATTRIBUTION_BUCKETS,
    attribute_window,
    build_index,
    build_ledger,
    interval_intersect,
    percentile,
    request_cost,
)
from deeperspeed_tpu.monitor import slo as slo_cli
from deeperspeed_tpu.serving import SLOConfig, SLOTracker

TRACES = os.path.join(os.path.dirname(__file__), os.pardir, "traces")


def _span(name, ts, dur, pid, **args):
    return {"name": name, "ph": "X", "ts": float(ts), "dur": float(dur),
            "pid": pid, "tid": 0, "args": args}


def _inst(name, ts, pid=0, **args):
    return {"name": name, "ph": "i", "ts": float(ts), "pid": pid,
            "tid": 0, "s": "p", "args": args}


def _single_engine_events():
    """One request A on pid 1: submit at 0, admitted at 1000, a 2000µs
    prefill whose tail 500µs is compile, two 500µs decode steps, finish
    at 4000 with 3 tokens. Every µs of both windows is attributable."""
    return [
        _inst("req/submit", 0, pid=1, rid="A", prompt_len=8),
        _inst("serving/admit", 1000, pid=1, rid="A", slot=0, ctx_len=8,
              admissions=1),
        _span("serving/prefill", 1000, 2000, 1, rid="A", ctx_len=8),
        # compile listener fires at END: interval is (1500, 2000),
        # inside A's own prefill -> the cold-bucket split
        _inst("xla_compile", 2000, pid=1, seconds=0.0005),
        _span("serving/decode", 3000, 500, 1, rids="A", n_active=1),
        _span("serving/decode", 3500, 500, 1, rids="A", n_active=1),
        _inst("serving/finish", 4000, pid=1, rid="A", reason="length",
              tokens=3, kv_block_s=0.01, admissions=1),
    ]


def test_interval_intersect():
    a = [(0.0, 10.0), (20.0, 30.0)]
    b = [(5.0, 25.0), (28.0, 40.0)]
    assert interval_intersect(a, b) == [(5.0, 10.0), (20.0, 25.0),
                                        (28.0, 30.0)]
    assert interval_intersect(a, []) == []
    assert interval_intersect([], b) == []
    # touching endpoints are empty, not zero-width intervals
    assert interval_intersect([(0.0, 5.0)], [(5.0, 9.0)]) == []


def test_percentile_nearest_rank():
    vals = list(range(1, 101))
    assert percentile(vals, 50) == 50
    assert percentile(vals, 99) == 99
    assert percentile(vals, 100) == 100
    assert percentile([5.0], 99) == 5.0
    assert percentile([], 50) == 0.0


def test_buckets_sum_to_window_by_construction():
    idx = build_index(_single_engine_events())
    tline = idx.timelines["A"]
    for window in (tline.ttft_window(), tline.e2e_window()):
        att = attribute_window(idx, tline, window)
        assert set(att["buckets"]) == set(ATTRIBUTION_BUCKETS)
        assert sum(att["buckets"].values()) == \
            pytest.approx(att["window_us"])
    ttft = attribute_window(idx, tline, tline.ttft_window())
    # 3000µs TTFT: 500 compile (inside the prefill), 1500 warm prefill,
    # 1000 engine queue residency (submit -> admit); nothing unexplained
    assert ttft["buckets"]["compile"] == pytest.approx(500.0)
    assert ttft["buckets"]["prefill"] == pytest.approx(1500.0)
    assert ttft["buckets"]["sched_queue"] == pytest.approx(1000.0)
    assert ttft["residual_fraction"] == 0.0
    e2e = attribute_window(idx, tline, tline.e2e_window())
    assert e2e["buckets"]["decode"] == pytest.approx(1000.0)
    assert e2e["residual_fraction"] == 0.0


def test_hol_blocking_names_the_blocker():
    # A's 10000µs prefill occupies pid 7 while B waits: B's TTFT must be
    # dominated by hol_blocking and name A as the blocker
    events = [
        _inst("req/submit", 0, pid=7, rid="A", prompt_len=200),
        _inst("serving/admit", 100, pid=7, rid="A", slot=0, ctx_len=200,
              admissions=1),
        _span("serving/prefill", 100, 10000, 7, rid="A", ctx_len=200),
        _inst("req/submit", 500, pid=7, rid="B", prompt_len=32),
        _inst("serving/admit", 10100, pid=7, rid="B", slot=1, ctx_len=32,
              admissions=1),
        _span("serving/prefill", 10100, 300, 7, rid="B", ctx_len=32),
    ]
    idx = build_index(events)
    b = idx.timelines["B"]
    att = attribute_window(idx, b, b.ttft_window())
    assert att["buckets"]["hol_blocking"] == pytest.approx(9600.0)
    assert att["buckets"]["prefill"] == pytest.approx(300.0)
    assert att["residual_fraction"] == 0.0
    assert list(att["blockers"]) == ["A"]
    assert att["blockers"]["A"] == pytest.approx(9600.0)


def test_warmup_rids_excluded_but_still_block():
    # same schedule, but the blocker is a compile-warmup request: it is
    # dropped from the doctored population yet still charged as the
    # p99 victim's blocker — warmup in front of real traffic is real
    # blocking
    events = [
        _inst("serving/admit", 100, pid=7, rid="warm-256", slot=0,
              ctx_len=254, admissions=1),
        _span("serving/prefill", 100, 10000, 7, rid="warm-256",
              ctx_len=254),
        _inst("req/submit", 500, pid=7, rid="B", prompt_len=32),
        _inst("serving/admit", 10100, pid=7, rid="B", slot=1, ctx_len=32,
              admissions=1),
        _span("serving/prefill", 10100, 300, 7, rid="B", ctx_len=32),
        _inst("serving/finish", 10500, pid=7, rid="B", reason="length",
              tokens=1, kv_block_s=0.001, admissions=1),
    ]
    report = build_ledger(events)
    assert list(report["requests"]) == ["B"]
    assert report["p99_victim"]["rid"] == "B"
    assert report["p99_victim"]["dominant_bucket"] == "hol_blocking"
    assert report["p99_victim"]["top_blocker"] == "warm-256"
    assert report["top_blockers"][0]["rid"] == "warm-256"
    # --include-warmup semantics: empty prefix tuple keeps it
    full = build_ledger(events, exclude_prefixes=())
    assert set(full["requests"]) == {"B", "warm-256"}


def _failover_events():
    """Rid R dispatched to r0 (pid 1), generates 3 tokens, the replica
    dies; the router requeues and re-dispatches to r1 (pid 2), which
    replays the prompt and finishes with 5 tokens."""
    return [
        _inst("lifecycle/rollout", 1, pid=0, replica="r0", version="v1"),
        _inst("lifecycle/rollout", 2, pid=0, replica="r1", version="v2"),
        _inst("req/submit", 0, pid=100, rid="R", prompt_len=8),
        _inst("req/accept", 10, pid=100, rid="R", cost_tokens=8),
        _inst("serving/dispatch", 50, pid=100, rid="R", replica="r0",
              attempt=0),
        _inst("serving/admit", 100, pid=1, rid="R", slot=0, ctx_len=8,
              admissions=1),
        _span("serving/prefill", 100, 200, 1, rid="R", ctx_len=8),
        _span("serving/decode", 300, 50, 1, rids="R", n_active=1),
        _span("serving/decode", 350, 50, 1, rids="R", n_active=1),
        # r0 SIGKILLed; router notices and holds the request back
        _inst("req/requeue", 500, pid=100, rid="R", backoff_s=0.001),
        _inst("serving/dispatch", 2000, pid=100, rid="R", replica="r1",
              attempt=1),
        _inst("serving/admit", 2100, pid=2, rid="R", slot=0, ctx_len=8,
              admissions=1),
        _span("serving/prefill", 2100, 200, 2, rid="R", ctx_len=8),
        _span("serving/decode", 2300, 50, 2, rids="R", n_active=1),
        _span("serving/decode", 2350, 50, 2, rids="R", n_active=1),
        _span("serving/decode", 2400, 50, 2, rids="R", n_active=1),
        _span("serving/decode", 2450, 50, 2, rids="R", n_active=1),
        _inst("serving/finish", 2500, pid=2, rid="R", reason="length",
              tokens=5, kv_block_s=0.02, admissions=1),
    ]


def test_retry_wasted_tokens_exact_across_failover():
    idx = build_index(_failover_events())
    cost = request_cost(idx, idx.timelines["R"])
    assert cost["attempts"] == 2
    # attempt 0 generated 1 prefill + 2 decode tokens, all replayed
    assert cost["retry_wasted_tokens"] == 3
    assert cost["tokens_total"] == 8
    assert cost["tokens_final"] == 5
    assert cost["tokens_final"] == cost["finish_tokens_reported"]
    assert cost["replica"] == "r1"
    assert cost["version"] == "v2"
    assert cost["kv_block_s"] == pytest.approx(0.02)
    # the requeue hold shows up as retry_backoff in the attribution
    tline = idx.timelines["R"]
    att = attribute_window(idx, tline, tline.e2e_window())
    assert att["buckets"]["retry_backoff"] == pytest.approx(1500.0)
    assert sum(att["buckets"].values()) == pytest.approx(att["window_us"])
    # economics roll up under the final replica / its weight version
    report = build_ledger(_failover_events())
    econ = report["economics"]
    assert econ["replica"]["r1"]["retry_wasted_tokens"] == 3
    assert econ["version"]["v2"]["tokens"] == 5
    assert report["cost_per_1k_tokens"] > 0


def test_slo_tracker_burn_rate():
    trk = SLOTracker(SLOConfig(ttft_p99_ms=100.0))
    assert trk.enabled
    for _ in range(98):
        assert not trk.observe("ttft", 0.050)
    assert trk.observe("ttft", 0.200)
    assert trk.observe("ttft", 0.300)
    # 2 violations / 100 observations / 0.01 budget = burning at 2x
    assert trk.burn_rate("ttft") == pytest.approx(2.0)
    s = trk.summary()["ttft"]
    assert s["observations"] == 100
    assert s["violations"] == 2
    assert s["violation_rate"] == pytest.approx(0.02)
    assert s["burn_rate"] == pytest.approx(2.0)
    # unpromised axis is a no-op
    assert not trk.observe("tpot", 10.0)
    assert trk.burn_rate("tpot") == 0.0
    assert not SLOTracker(None).observe("ttft", 10.0)


def test_slo_cli_round_trip(tmp_path, capsys):
    trace = tmp_path / "doctor_trace.json"
    trace.write_text(json.dumps({"traceEvents": _single_engine_events()}))
    out = tmp_path / "report.json"
    rc = slo_cli.main([str(trace), "--json", str(out),
                       "--max-residual", "0.05"])
    assert rc == 0
    shown = capsys.readouterr().out
    assert "gate OK" in shown
    report = json.loads(out.read_text())
    assert report["requests"]["A"]["cost"]["tokens_final"] == 3
    assert report["worst_residual_fraction"] == 0.0
    # a directory containing exactly one trace resolves to it
    assert slo_cli.resolve_input(str(tmp_path)) == str(trace)
    assert slo_cli.main([str(tmp_path)]) == 0
    # bad inputs are rc 2, not a stack trace
    assert slo_cli.main([str(tmp_path / "missing.json")]) == 2
    empty = tmp_path / "empty_trace.json"
    empty.write_text(json.dumps({"traceEvents": []}))
    assert slo_cli.main([str(empty)]) == 2


def test_latency_buckets_cover_the_serving_tail():
    # the regression that motivated the widening: a 631ms TTFT must land
    # in a real bucket, not the terminal catch-all
    bounds = DEFAULT_LATENCY_BUCKETS
    assert list(bounds) == sorted(bounds)
    i = bisect.bisect_left(bounds, 0.631)
    assert i < len(bounds) - 1, "0.631s fell in the terminal bucket"
    assert bounds[i] == 0.75
    # the 100ms..10s band has enough resolution to separate a 150ms
    # p50 from a multi-second p99
    tail = [b for b in bounds if 0.1 <= b <= 10.0]
    assert len(tail) >= 10
    h = Histogram(buckets=bounds)
    h.observe(0.631)
    cum = 0
    for bound, c in zip(h.buckets, h._counts):
        cum += c
        if bound >= 0.75:
            break
    assert cum == 1


@pytest.mark.skipif(
    not os.path.exists(os.path.join(TRACES, "obs_drill_merged.json")),
    reason="committed drill trace not present")
def test_committed_drill_trace_token_exactness():
    report = build_ledger(os.path.join(TRACES, "obs_drill_merged.json"))
    checked = 0
    for rid, row in report["requests"].items():
        c = row["cost"]
        if c["finish_tokens_reported"] is not None:
            assert c["tokens_final"] == c["finish_tokens_reported"], rid
            checked += 1
    assert checked > 0
    # the drill SIGKILLs a replica mid-decode: failover waste must be
    # visible, and the doctor must still explain >= 95% of every TTFT
    assert sum(r["cost"]["retry_wasted_tokens"]
               for r in report["requests"].values()) > 0
    assert report["worst_residual_fraction"] <= 0.05


@pytest.mark.skipif(
    not os.path.exists(os.path.join(TRACES, "serving_bench_trace.json")),
    reason="committed bench trace not present")
def test_committed_bench_trace_p99_not_hol_dominated():
    """The committed trace (a fixture: the script that wrote it is gone)
    is a shared-prefix run WITH prefix reuse + chunked prefill, which
    exist to kill head-of-line blocking, so the p99 victim must not be
    hol_blocking-dominated, while attribution still explains the
    tail."""
    report = build_ledger(
        os.path.join(TRACES, "serving_bench_trace.json"))
    victim = report["p99_victim"]
    assert victim["dominant_bucket"] != "hol_blocking"
    assert victim["dominant_bucket"] != "residual"
    assert report["worst_residual_fraction"] <= 0.05
    for rid, row in report["requests"].items():
        c = row["cost"]
        if c["finish_tokens_reported"] is not None:
            assert c["tokens_final"] == c["finish_tokens_reported"], rid
