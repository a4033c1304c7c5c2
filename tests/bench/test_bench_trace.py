"""The reduction from a trace to numbers, on hand-written events with a
known answer, and on a small trace recorded on the chip by PR 23."""

import os

import pytest

from benchmark import peaks, profiling
from benchmark import trace as tr
from benchmark.reducers import (collective_exposed_pct, device_ms_per_span,
                                flash_roofline_pct, host_ms_outside_device,
                                idle_pct, program_ms, program_share_pct)

D0, D1 = "/device:TPU:0", "/device:TPU:1"


def op(name, start, dur, plane=D0, line=tr.OPS_LINE, text=""):
    return tr.Ev(name, start, dur, plane, line, text or name)


def host(name, start, dur):
    return tr.Ev(name, start, dur, "/host:CPU", "python", name)


# one device: a while that holds two fusions and an all-reduce, a gap, a copy
OPS = [op("while.3", 0.0, 10.0), op("fusion.1", 0.0, 4.0),
       op("all-reduce.2", 4.0, 2.0), op("fusion.7", 7.0, 3.0),
       op("copy.1", 12.0, 1.0)]
HOST = [host(profiling.WINDOW_SPAN, 0.0, 14.0), host("bench/train_step", 0.0, 11.0),
        host("bench/feed", 10.5, 1.6), host("bench/train_step", 11.0, 3.0)]


def traced(events, chips=1):
    return profiling.traced_run(events, chips)


def test_busy_idle_and_self_times():
    t = traced(OPS + HOST)
    assert t["window_s"] == 14.0 and t["busy_s"] == 11.0
    assert idle_pct.read({"trace": t}, {}) == pytest.approx(100 * 3 / 14)
    assert dict((e.name, s) for e, s in tr.self_times(OPS))["while.3"] == 1.0
    assert tr.top_ops(OPS, 2) == [["fusion", 7.0], ["all-reduce", 2.0]]
    assert tr.union_length([(0, 2), (1, 3), (5, 6)]) == 4


def test_idle_gaps_go_to_the_host_span_that_covers_them():
    b = profiling.breakdown(traced(OPS + HOST))
    assert b["idle_gaps"][0] == ["bench/feed", 2.0]
    assert b["idle_gaps"][1] == ["bench/train_step", 1.0]
    assert b["device_ops"][0] == ["fusion", 7.0]


def test_device_time_per_span_and_host_time_outside_it():
    run = {"trace": traced(OPS + HOST)}
    # spans: [0, 11] holds 10 busy seconds, [11, 14] holds 1: median 5.5 s
    assert device_ms_per_span.read(run, {"span": "train_step"}) == pytest.approx(5500.0)
    assert host_ms_outside_device.read(run, {"span": "train_step"}) == pytest.approx(1500.0)
    with pytest.raises(tr.TraceError):
        device_ms_per_span.read(run, {"span": "no_such_span"})


def test_exposed_collectives_over_two_devices():
    # device 1 overlaps its all-reduce with a fusion on an async line
    evs = OPS + HOST + [op("fusion.1", 0.0, 6.0, D1),
                        op("all-reduce-start.2", 4.0, 2.0, D1, "XLA Async Ops"),
                        op("fusion.9", 6.0, 1.0, D1)]
    total0, exposed0 = tr.exposed_collective_seconds(evs, D0, 0.0, 14.0)
    total1, exposed1 = tr.exposed_collective_seconds(evs, D1, 0.0, 14.0)
    assert (total0, exposed0) == (2.0, 2.0) and (total1, exposed1) == (2.0, 0.0)
    t = traced(evs, chips=2)
    assert collective_exposed_pct.read({"trace": t}, {}) == pytest.approx(100 * 1.0 / 14)
    assert collective_exposed_pct.read({"trace": traced(OPS + HOST)}, {}) is None
    with pytest.raises(tr.TraceError):
        tr.exposed_collective_seconds([op("fusion.1", 0, 1)], D0, 0, 1)


def test_programs_by_name_and_their_share():
    mods = [op("jit_decode_step(123)", 0.0, 2.0, line="XLA Modules"),
            op("jit_decode_step(123)", 3.0, 4.0, line="XLA Modules"),
            op("jit__lambda_(9)", 8.0, 2.0, line="XLA Modules")]
    run = {"trace": traced(OPS + HOST + mods)}
    assert program_ms.read(run, {"pattern": "decode_step"}) == pytest.approx(3000.0)
    assert program_share_pct.read(run, {"pattern": "_lambda_"}) == pytest.approx(25.0)
    with pytest.raises(tr.TraceError, match="no program matches"):
        program_ms.read(run, {"pattern": "verify_step"})


def test_flash_roofline_from_shapes_and_named_kernels():
    shape = {"batch": 1, "heads": 16, "seq": 2048, "head_dim": 128}
    f_fwd = peaks.flash_call_flops(causal=True, backward=False, **shape)
    f_bwd = peaks.flash_call_flops(causal=True, backward=True, **shape)
    assert f_fwd == 4 * 16 * 2048 * 2048 * 128 / 2 and f_bwd == 2.5 * f_fwd
    pk = peaks.peaks_for("TPU v5 lite")
    # kernels that take exactly twice the least time: 50% of the roofline
    t_fwd, t_bwd = 2 * f_fwd / pk["flops_per_s"], 2 * f_bwd / pk["flops_per_s"]
    evs = [op("custom-call.1", 0.0, t_fwd, text="custom-call.1 _fwd_kernel"),
           op("custom-call.2", 1.0, t_bwd, text="custom-call.2 _bwd_kernel"),
           host(profiling.WINDOW_SPAN, 0.0, 2.0)]
    run = {"trace": traced(evs), "device": {"kind": "TPU v5 lite"}, "notes": [],
           "attention": {"flops": dict(shape, causal=True),
                         "bytes": dict(shape, itemsize=2)}}
    params = {"forward": "_fwd_kernel", "backward": "_bwd_kernel"}
    assert flash_roofline_pct.read(run, params) == pytest.approx(50.0)
    assert "compute-bound" in run["notes"][0]
    with pytest.raises(tr.TraceError, match="flash backward"):
        flash_roofline_pct.read(run, dict(params, backward="absent_kernel"))
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9")


def test_a_trace_without_device_work_is_an_error():
    with pytest.raises(tr.TraceError):
        traced(HOST)
    with pytest.raises(tr.TraceError):
        traced(OPS)   # no traced-window span


# ---- a small trace recorded on the chip ------------------------------ #

FIXTURE = os.path.join(os.path.dirname(profiling.__file__), "fixtures",
                       "neox-1.3b.train.slice.json.gz")


def test_recorded_trace_reduces_to_the_numbers_read_when_it_was_made():
    """The first 0.15 s of neox-1.3b.train's traced window on a TPU v5 lite
    (PR 23): one micro-step, 24 backward and 32 forward flash calls."""
    import json

    from benchmark import manifest as mf

    t = traced(profiling.load_dump(FIXTURE))
    assert t["planes"] == [D0] and t["window_s"] == pytest.approx(0.15)
    assert t["busy_s"] == pytest.approx(0.1492953, rel=1e-5)
    assert idle_pct.read({"trace": t}, {}) == pytest.approx(0.46977, rel=1e-3)
    spec = mf.load_json(os.path.join(mf.BENCH_DIR, "metrics", "flash_attn_roofline.json"))
    ops = t["ops"][D0]
    assert len(tr.matching(ops, spec["params"]["forward"], "forward")) == 32
    assert len(tr.matching(ops, spec["params"]["backward"], "backward")) == 24
    shape = {"batch": 1, "heads": 16, "seq": 2048, "head_dim": 128}
    run = {"trace": t, "device": {"kind": "TPU v5 lite"}, "notes": [],
           "attention": {"flops": dict(shape, causal=True),
                         "bytes": dict(shape, itemsize=2)}}
    share = flash_roofline_pct.read(run, spec["params"])
    assert share == pytest.approx(67.85, rel=1e-3) and share < 100
    top = profiling.breakdown(t)["device_ops"]
    assert top[0][0] == "fusion bf16[2048]" and len(top) == 10
    assert all(isinstance(n, str) and s > 0 for n, s in top)
    json.dumps(profiling.breakdown(t))


def test_stable_names_of_hlo_lines():
    assert tr.stable_name("%fusion.557 = (bf16[2048]{0:T(1024)}, bf16[8,8]{1,0}) "
                          "fusion(bf16[2]{0} %x)") == "fusion bf16[2048]"
    assert tr.stable_name("%all-reduce-start.5 = f32[4]{0} all-reduce-start(...)") \
        == "all-reduce-start f32[4]"
    assert tr.stable_name("fusion.12") == "fusion"
    assert tr.stable_name("jit_decode_step(123)") == "jit_decode_step(123)"
