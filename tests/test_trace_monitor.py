"""Unified telemetry tests: Chrome-trace tracer (ring, lanes, threads),
trace-schema validator + CLI, metrics registry + Prometheus endpoint,
recompile watchdog (silent across a multi-request serving run, firing on
an injected shape change), the "monitor" config block through
deepspeed.initialize, TensorBoardMonitor context-manager/atexit flush,
and the ThroughputTimer zero-division clamp."""

import json
import threading
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deeperspeed_tpu as deepspeed
from deeperspeed_tpu.models.gpt import GPTConfig, make_gpt
from deeperspeed_tpu.monitor import (
    Monitor,
    MonitorConfig,
    RecompileError,
    RecompileWatchdog,
    Tracer,
    get_monitor,
    get_tracer,
    init_monitor,
    set_tracer,
    shutdown_monitor,
    trace_counter,
    trace_instant,
    trace_span,
    validate_events,
    validate_file,
)
from deeperspeed_tpu.monitor.metrics import (
    CONTENT_TYPE,
    MetricsRegistry,
    MetricsServer,
)
from deeperspeed_tpu.monitor.validate import main as validate_main
from deeperspeed_tpu.runtime.config import ConfigError, TrainingConfig
from deeperspeed_tpu.serving import ServingEngine, idle_slots
from deeperspeed_tpu.utils.tensorboard import TensorBoardMonitor
from deeperspeed_tpu.utils.timer import (
    SynchronizedWallClockTimer,
    ThroughputTimer,
)


@pytest.fixture(autouse=True)
def _clean_global_monitor():
    """Telemetry state is process-global; leave no tracer/monitor behind."""
    yield
    shutdown_monitor(save=False)
    set_tracer(None)


def _serving_model():
    cfg = GPTConfig(vocab_size=97, n_layer=2, n_head=2, d_model=32,
                    max_seq=64, remat=False, dtype=jnp.float32,
                    attn_impl="xla")
    init_fn, _, _, _ = make_gpt(cfg)
    return cfg, init_fn(jax.random.PRNGKey(0))


# ------------------------------------------------------------------ #
# tracer
# ------------------------------------------------------------------ #


def test_tracer_span_emits_complete_event():
    t = Tracer()
    with t.span("fwd", lane="engine", micro_step=3):
        pass
    (ev,) = t.events()
    assert ev["ph"] == "X" and ev["name"] == "fwd"
    assert ev["dur"] >= 0 and ev["ts"] >= 0
    assert ev["args"] == {"micro_step": 3}
    assert validate_events(t.to_dict()["traceEvents"]) == []


def test_tracer_lanes_get_stable_small_tids_and_metadata():
    t = Tracer()
    with t.span("a", lane="engine"):
        pass
    with t.span("b", lane="serving"):
        pass
    with t.span("c", lane="engine"):
        pass
    a, b, c = t.events()
    assert a["tid"] == c["tid"] != b["tid"]
    names = {m["args"]["name"] for m in t._metadata()
             if m["name"] == "thread_name"}
    assert names == {"engine", "serving"}


def test_tracer_ring_bounds_memory_and_counts_drops():
    drops = []
    t = Tracer(ring_size=16, on_drop=drops.append)
    for i in range(100):
        t.instant(f"e{i}")
    assert len(t.events()) == 16
    # 84 user events evicted, plus the rate-limited trace/dropped note
    # evicting one more when it joined the full ring
    assert t.dropped == 85
    assert sum(drops) == t.dropped
    assert t.to_dict()["otherData"]["dropped_events"] == 85
    # eviction cannot orphan anything: spans are self-contained X events
    assert validate_events(t.to_dict()["traceEvents"]) == []


def test_tracer_thread_safety():
    t = Tracer(ring_size=100_000)

    def emit(k):
        for i in range(200):
            with t.span(f"w{k}", lane=f"lane{k}"):
                pass
            t.counter("load", i, lane=f"lane{k}")

    threads = [threading.Thread(target=emit, args=(k,)) for k in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert len(t.events()) == 8 * 400
    assert validate_events(t.to_dict()["traceEvents"]) == []


def test_global_helpers_are_noops_without_tracer():
    assert get_tracer() is None
    with trace_span("x", lane="engine"):
        pass
    trace_instant("y")
    trace_counter("z", 1.0)  # nothing to assert beyond "does not crash"


def test_global_helpers_record_through_installed_tracer():
    t = Tracer()
    prev = set_tracer(t)
    try:
        with trace_span("s", lane="engine"):
            trace_instant("i", lane="engine")
        trace_counter("c", {"q": 2}, lane="serving")
    finally:
        set_tracer(prev)
    assert [e["ph"] for e in t.events()] == ["i", "X", "C"]


# ------------------------------------------------------------------ #
# validator (+ CLI)
# ------------------------------------------------------------------ #


def test_validator_flags_corrupt_events():
    assert validate_events("nope")  # not a list
    assert validate_events([[]])  # event not a dict
    assert validate_events([{"name": "x", "ph": "Q", "ts": 0,
                             "pid": 1, "tid": 1}])  # unknown phase
    assert validate_events([{"name": "x", "ph": "X", "ts": 0, "dur": 1,
                             "tid": 1}])  # missing pid
    assert validate_events([{"name": "x", "ph": "X", "ts": -5, "dur": 1,
                             "pid": 1, "tid": 1}])  # negative ts
    assert validate_events([{"name": "x", "ph": "X", "ts": 0,
                             "pid": 1, "tid": 1}])  # X without dur
    assert validate_events([{"ph": "i", "ts": 0, "pid": 1,
                             "tid": 1}])  # missing name


def test_validator_checks_begin_end_balance():
    def ev(ph, name="x"):
        return {"name": name, "ph": ph, "ts": 0.0, "pid": 1, "tid": 1}

    assert validate_events([ev("B"), ev("E")]) == []
    assert validate_events([ev("B")])          # dangling B
    assert validate_events([ev("E")])          # E without B
    # balance is tracked per (pid, tid)
    other = dict(ev("E"), tid=2)
    assert validate_events([ev("B"), other])


def test_validator_cli(tmp_path, capsys):
    good = tmp_path / "good.json"
    t = Tracer()
    with t.span("a", lane="engine"):
        pass
    t.save(str(good))
    assert validate_file(str(good)) == []
    assert validate_main([str(good)]) == 0

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [{"ph": "X"}]}))
    assert validate_main([str(bad)]) == 1
    assert validate_main([str(tmp_path / "missing.json")]) == 1
    assert validate_main([]) == 2
    capsys.readouterr()


# ------------------------------------------------------------------ #
# metrics registry + endpoint
# ------------------------------------------------------------------ #


def test_registry_renders_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("reqs_total", "Requests.").inc(3)
    reg.gauge("depth", "Queue depth.", labels={"pool": "a"}).set(2)
    h = reg.histogram("lat_seconds", "Latency.", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.render()
    assert "# TYPE reqs_total counter" in text
    assert "reqs_total 3" in text
    assert 'depth{pool="a"} 2' in text
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text
    assert "lat_seconds_count 3" in text


def test_counter_rejects_negative_and_gauge_moves_both_ways():
    reg = MetricsRegistry()
    c = reg.counter("c_total")
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g")
    g.inc(5)
    g.dec(2)
    assert "g 3" in reg.render()


def test_metrics_server_serves_exposition_text():
    reg = MetricsRegistry()
    reg.counter("up_total", "Liveness.").inc()
    srv = MetricsServer(reg, port=0)
    srv.start()
    try:
        with urllib.request.urlopen(srv.url) as resp:
            body = resp.read().decode()
            ctype = resp.headers["Content-Type"]
        assert ctype == CONTENT_TYPE
        assert "up_total 1" in body
    finally:
        srv.close()


# ------------------------------------------------------------------ #
# recompile watchdog
# ------------------------------------------------------------------ #


def test_watchdog_warms_then_fires_on_shape_change():
    wd = RecompileWatchdog(mode="warn")
    f = jax.jit(lambda x: x + 1)
    wd.watch("f", f)
    assert wd.observe() == []          # cache empty: not yet warm
    f(jnp.ones(3))
    assert wd.observe() == []          # first compile = warmup
    f(jnp.ones(3))
    assert wd.observe() == []          # cache hit: silent
    f(jnp.ones(4))                     # shape change -> second trace
    assert wd.observe() == ["f"]
    assert wd.fired[0]["name"] == "f"
    assert wd.observe() == []          # each growth reported once


def test_watchdog_strict_raises():
    wd = RecompileWatchdog(mode="strict")
    f = jax.jit(lambda x: x * 2)
    wd.watch("f", f)
    f(jnp.ones(2))
    wd.observe()
    f(jnp.ones(5))
    with pytest.raises(RecompileError):
        wd.observe()


def test_watchdog_off_mode_never_fires():
    wd = RecompileWatchdog(mode="off")
    f = jax.jit(lambda x: x - 1)
    wd.watch("f", f)
    f(jnp.ones(2))
    f(jnp.ones(3))
    assert wd.observe() == []
    assert wd.fired == []


def test_watchdog_rejects_unknown_mode():
    with pytest.raises(ValueError):
        RecompileWatchdog(mode="loud")


def test_watchdog_silent_across_serving_run_then_fires_on_injection():
    """The acceptance property: a multi-request serving run (staggered
    arrivals, preemption pressure absent) keeps the decode step at ONE
    compile and the watchdog silent; an injected shape change fires it."""
    cfg, params = _serving_model()
    eng = ServingEngine(
        cfg, params,
        {"num_slots": 2, "num_blocks": 16, "block_size": 8,
         "max_seq_len": 64, "max_new_tokens": 8},
        monitor_config={"watchdog": "warn"},
    )
    for i in range(5):
        eng.submit([1 + i, 2, 3, 4], max_new_tokens=4)
    eng.run()
    assert eng.decode_compile_count == 1
    assert eng.telemetry.watchdog.fired == []
    assert "serving/decode_step" in eng.telemetry.watchdog.watched()

    # inject: run the decode step at a different slot count (a shape the
    # engine itself can never produce) and observe
    n2 = eng.scfg.num_slots + 1
    eng._decode_step(
        eng.params, jnp.array(eng.kv.k), jnp.array(eng.kv.v),
        jnp.asarray(idle_slots(n2, eng.scfg.blocks_per_slot)),
        jnp.zeros(n2, jnp.int32))
    assert eng.telemetry.watchdog.observe() == ["serving/decode_step"]
    assert eng.decode_compile_count == 2


# ------------------------------------------------------------------ #
# serving end-to-end trace
# ------------------------------------------------------------------ #


def test_serving_run_produces_valid_trace_with_all_layers(tmp_path):
    trace_path = tmp_path / "serve.json"
    cfg, params = _serving_model()
    eng = ServingEngine(
        cfg, params,
        {"num_slots": 2, "num_blocks": 16, "block_size": 8,
         "max_seq_len": 64, "max_new_tokens": 4},
        monitor_config={"trace_path": str(trace_path),
                        "watchdog": "strict"},
    )
    for i in range(4):
        eng.submit([1 + i, 2, 3], max_new_tokens=3)
    out = eng.run()
    assert len(out) == 4
    assert eng.telemetry.save_trace() == str(trace_path)
    shutdown_monitor(save=False)

    assert validate_file(str(trace_path)) == []
    doc = json.loads(trace_path.read_text())
    events = doc["traceEvents"]
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    # spans from the step loop, the prefill path, and the decode layer
    for span in ("serving/step", "serving/prefill", "serving/decode"):
        assert by_name[span][0]["ph"] == "X"
    # scheduler instants + load counter
    assert by_name["serving/admit"][0]["ph"] == "i"
    assert {e["args"]["reason"] for e in by_name["serving/finish"]} \
        == {"length"}
    assert by_name["serving/load"][0]["ph"] == "C"
    # everything rides the named serving lane
    lane_tids = {m["tid"] for m in events
                 if m["ph"] == "M" and m["name"] == "thread_name"
                 and m["args"]["name"] == "serving"}
    assert by_name["serving/decode"][0]["tid"] in lane_tids


def test_serving_metrics_registry_and_endpoint():
    cfg, params = _serving_model()
    eng = ServingEngine(
        cfg, params,
        {"num_slots": 2, "num_blocks": 16, "block_size": 8,
         "max_seq_len": 64, "max_new_tokens": 4},
        monitor_config={"trace_enabled": False, "metrics_port": 0},
    )
    n_req = 3
    for i in range(n_req):
        eng.submit([1 + i, 7], max_new_tokens=3)
    eng.run()
    with urllib.request.urlopen(eng.telemetry.metrics_server.url) as resp:
        text = resp.read().decode()
    assert f"serving_prefills_total {n_req}" in text
    assert f'serving_requests_finished_total{{reason="length"}} {n_req}' \
        in text
    assert f"serving_tokens_generated_total {3 * n_req}" in text
    assert "serving_ttft_seconds_count 3" in text
    assert "# TYPE serving_ttft_seconds histogram" in text


# ------------------------------------------------------------------ #
# the "monitor" config block + training engine wiring
# ------------------------------------------------------------------ #


def _loss_fn(params, batch):
    x, y = batch
    return jnp.mean((x @ params["w"] - y) ** 2)


def _train_config(extra):
    return dict({
        "train_batch_size": 8,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
    }, **extra)


def test_monitor_block_enables_and_validates():
    tc = TrainingConfig(_train_config({"monitor": {"watchdog": "strict"}}))
    assert tc.monitor_enabled
    assert tc.monitor_config().watchdog == "strict"
    tc = TrainingConfig(_train_config({}))
    assert not tc.monitor_enabled and tc.monitor_config() is None
    tc = TrainingConfig(_train_config({"monitor": {"enabled": False,
                                                   "ring_size": 4}}))
    assert not tc.monitor_enabled and tc.monitor_config() is None
    with pytest.raises(ConfigError):
        TrainingConfig(_train_config({"monitor": {"bogus_key": 1}}))
    with pytest.raises(ConfigError):
        TrainingConfig(_train_config({"monitor": {"watchdog": "loud"}}))
    with pytest.raises(ConfigError):
        TrainingConfig(_train_config({"monitor": {"ring_size": 0}}))
    with pytest.raises(ConfigError):
        TrainingConfig(_train_config({"monitor": "yes"}))


def test_train_run_traces_and_counts_steps(tmp_path):
    trace_path = tmp_path / "train.json"
    engine, _, _, _ = deepspeed.initialize(
        model=_loss_fn,
        model_parameters={"w": jnp.zeros((8, 2))},
        config_params=_train_config({
            "monitor": {"trace_path": str(trace_path),
                        "watchdog": "strict"},
        }),
    )
    assert engine.monitor is get_monitor()
    x = np.random.RandomState(0).randn(8, 8).astype(np.float32)
    y = np.random.RandomState(1).randn(8, 2).astype(np.float32)
    for _ in range(3):
        engine.train_batch(batch=(jnp.asarray(x), jnp.asarray(y)))
    # strict watchdog stayed silent: the fused train step compiled once
    assert engine.monitor.watchdog.fired == []
    assert "train_steps_total 3" in engine.monitor.registry.render()
    shutdown_monitor(save=True)
    assert validate_file(str(trace_path)) == []
    events = json.loads(trace_path.read_text())["traceEvents"]
    spans = [e for e in events if e["name"] == "engine/train_batch"]
    assert len(spans) == 3 and all(e["ph"] == "X" for e in spans)


def test_engine_without_monitor_block_adopts_global():
    mon = init_monitor({"trace_enabled": True})
    engine, _, _, _ = deepspeed.initialize(
        model=_loss_fn,
        model_parameters={"w": jnp.zeros((8, 2))},
        config_params=_train_config({}),
    )
    assert engine.monitor is mon


def test_monitor_lifecycle_restores_previous_tracer():
    outer = Tracer()
    set_tracer(outer)
    mon = Monitor({"trace_path": None}).start()
    assert get_tracer() is mon.tracer is not outer
    mon.shutdown(save=False)
    assert get_tracer() is outer


def test_monitor_config_rejects_bad_values():
    with pytest.raises(ValueError):
        MonitorConfig.from_dict({"metrics_port": 99999})
    with pytest.raises(ValueError):
        MonitorConfig.from_dict({"tb_export_interval": -1})
    cfg = MonitorConfig.from_dict(None)
    assert cfg.enabled and cfg.watchdog == "warn"


# ------------------------------------------------------------------ #
# satellites: TensorBoardMonitor lifecycle + timers
# ------------------------------------------------------------------ #


def test_tensorboard_monitor_context_manager(tmp_path):
    import glob

    with TensorBoardMonitor(output_path=str(tmp_path), job_name="ctx") as m:
        m.add_scalar("Train/x", 1.0, 0)
    assert m._closed
    assert glob.glob(str(tmp_path / "ctx" / "*"))
    # flush/close after close are no-ops, not crashes (atexit safety)
    m.flush()
    m.close()


def test_tensorboard_monitor_registers_atexit_flush(tmp_path):
    import atexit

    seen = []
    real_register = atexit.register
    real_unregister = atexit.unregister
    try:
        atexit.register = lambda fn, *a, **kw: seen.append(("reg", fn))
        atexit.unregister = lambda fn: seen.append(("unreg", fn))
        m = TensorBoardMonitor(output_path=str(tmp_path), job_name="ax")
        m.close()
    finally:
        atexit.register = real_register
        atexit.unregister = real_unregister
    assert ("reg", m.flush) in seen and ("unreg", m.flush) in seen


def test_wallclock_timer_safe_start_recovers():
    timers = SynchronizedWallClockTimer()
    t = timers("phase")
    t.start()
    t.stop()
    kept = t.elapsed_
    t.start()            # a run that dies here leaves started_ dangling
    t.safe_start()       # recovery: dangling interval dropped...
    t.stop()
    assert t.elapsed_ >= kept  # ...completed intervals kept
    with pytest.raises(AssertionError):
        t.start() or t.start()  # double-start still asserts


def test_wallclock_timer_elapsed_restarts_running_timer():
    t = SynchronizedWallClockTimer.Timer("x")
    t.start()
    first = t.elapsed(reset=True)
    assert first >= 0.0
    assert t.started_          # elapsed() restarted the running timer
    t.stop()
    assert t.elapsed(reset=False) >= 0.0


def test_throughput_timer_zero_elapsed_does_not_divide_by_zero():
    tt = ThroughputTimer(batch_size=4, start_step=0, steps_per_output=1,
                         logging_fn=lambda msg: None)
    frozen = [100.0]
    tt.start()
    tt.start_time = frozen[0]
    import deeperspeed_tpu.utils.timer as timer_mod

    real_time = timer_mod.time.time
    timer_mod.time.time = lambda: frozen[0]  # stop at the same instant
    try:
        tt.stop(global_step=True)  # duration == 0.0 -> clamped, no raise
    finally:
        timer_mod.time.time = real_time
    assert tt.step_elapsed_time == 0.0


# ------------------------------------------------------------------ #
# the program's spans on the profiler's clock (one span, two sinks)
# ------------------------------------------------------------------ #

SERVING_CHILDREN = {
    "serving/step": ("serving/schedule", "serving/prefill", "serving/decode",
                     "serving/export"),
    "serving/prefill": ("serving/prefill/pack", "serving/prefill/dispatch",
                        "serving/prefill/scatter", "serving/prefill/pick"),
    "serving/decode": ("serving/decode/pack", "serving/decode/dispatch",
                       "serving/decode/wait", "serving/decode/emit"),
}
TRAIN_CHILDREN = ("engine/train_batch/feed", "engine/train_batch/dispatch",
                  "engine/train_batch/after")
SERVING_CONFIG = {"num_slots": 2, "num_blocks": 16, "block_size": 8,
                  "max_seq_len": 64, "max_new_tokens": 4}


def _profiler_events(path):
    """(name, start ns, end ns, {argument: value}) of the host plane."""
    import glob
    import warnings

    from jax.profiler import ProfileData

    (pb,) = glob.glob(str(path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    out = []
    with warnings.catch_warnings():
        # reading an event's statistics warns once an event (jaxlib's
        # binding of the type has no __module__)
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(pb).planes:
            if plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """A toy serving run and three toy training steps under ONE live
    jax.profiler session, with no Tracer installed: the spans reach the
    profiler on their own."""
    assert get_tracer() is None
    cfg, params = _serving_model()
    eng = ServingEngine(cfg, params, SERVING_CONFIG)
    engine, _, _, _ = deepspeed.initialize(
        model=_loss_fn, model_parameters={"w": jnp.zeros((8, 2))},
        config_params=_train_config({}))
    x = jnp.asarray(np.random.RandomState(0).randn(8, 8).astype(np.float32))
    y = jnp.asarray(np.random.RandomState(1).randn(8, 2).astype(np.float32))
    path = tmp_path_factory.mktemp("xplane")
    jax.profiler.start_trace(str(path))
    try:
        for i in range(3):
            eng.submit([1 + i, 2, 3], max_new_tokens=3, request_id=f"r{i}")
        eng.run()
        for _ in range(3):
            engine.train_batch(batch=(x, y))
    finally:
        jax.profiler.stop_trace()
    return {"events": _profiler_events(path), "engine": eng}


def _named(events, name):
    return [e for e in events if e[0] == name]


def _within(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


@pytest.mark.parametrize("parent", sorted(SERVING_CHILDREN))
def test_profiler_session_holds_the_serving_spans_nested(profiled, parent):
    events = profiled["events"]
    parents = _named(events, parent)
    assert parents, f"no {parent} span reached the profiler"
    for child in SERVING_CHILDREN[parent]:
        spans = _named(events, child)
        assert spans, f"no {child} span reached the profiler"
        for c in spans:
            assert any(_within(c, p) for p in parents), (child, parent)


def test_profiler_spans_carry_their_arguments(profiled):
    events = profiled["events"]
    steps = _named(events, "serving/step")
    assert [s[3]["step"] for s in steps] == list(range(len(steps)))
    assert {s[3]["what"] for s in _named(events, "serving/schedule")} \
        == {"expire", "admit", "capacity"}
    # a decode span of a step with several active slots names every one
    from deeperspeed_tpu.monitor.tracer import RID_SEP

    decodes = _named(events, "serving/decode")
    assert max(d[3]["n_active"] for d in decodes) == 2
    for d in decodes:
        rids = d[3]["rids"].split(RID_SEP)
        assert len(rids) == d[3]["n_active"]
        assert set(rids) <= {"r0", "r1", "r2"}


@pytest.mark.parametrize("rid", ["r0", "r1", "r2"])
def test_one_requests_spans_share_its_rid(profiled, rid):
    events = profiled["events"]
    for name in ("req/submit", "serving/admit", "serving/prefill",
                 "serving/finish"):
        mine = [e for e in _named(events, name) if e[3].get("rid") == rid]
        assert len(mine) == 1, (name, rid)
    t = {n: next(e for e in _named(events, n) if e[3].get("rid") == rid)
         for n in ("req/submit", "serving/admit", "serving/prefill",
                   "serving/finish")}
    assert t["req/submit"][1] <= t["serving/admit"][1] \
        <= t["serving/prefill"][1] <= t["serving/finish"][1]


def test_profiler_session_holds_the_training_spans_nested(profiled):
    events = profiled["events"]
    steps = _named(events, "engine/train_batch")
    assert [s[3]["step"] for s in steps] == [0, 1, 2]
    for step in steps:
        kids = [next(c for c in _named(events, n) if _within(c, step))
                for n in TRAIN_CHILDREN]
        # feed, then the fused call, then the bookkeeping: in that order
        assert kids[0][2] <= kids[1][1] and kids[1][2] <= kids[2][1]


def test_no_session_no_tracer_records_nothing_and_queue_wait_fills(profiled):
    assert get_tracer() is None
    with trace_span("serving/step", lane="serving", step=0) as sp:
        assert sp.note(mfu=1.0) is sp     # a no-op, not a recorder
        assert sp.elapsed_s() >= 0.0
    eng = profiled["engine"]
    assert not hasattr(eng.metrics, "timers")
    assert len(eng.metrics.queue_wait_s) == 3
    assert all(w >= 0.0 for w in eng.metrics.queue_wait_s)
    summary = eng.metrics.summary()["queue_wait_s"]
    assert summary["max"] == max(eng.metrics.queue_wait_s)
    for r in eng.sched.finished:
        assert r.arrival_t <= r.admit_t <= r.first_token_t


def test_queue_wait_counts_the_first_admission_only():
    """Three requests on two slots: the third waits for a slot. Its wait
    is admit less arrival on the engine's clock, and is in the registry."""
    now = [0.0]
    cfg, params = _serving_model()
    eng = ServingEngine(cfg, params, SERVING_CONFIG, clock=lambda: now[0],
                        monitor_config={"trace_enabled": False})
    for i in range(3):
        eng.submit([1 + i, 2, 3], max_new_tokens=2)
    while eng.has_work():
        now[0] += 1.0
        eng.step()
    assert eng.metrics.queue_wait_s[:2] == [1.0, 1.0]
    assert eng.metrics.queue_wait_s[2] > 1.0
    assert "serving_queue_wait_seconds_count 3" \
        in eng.telemetry.registry.render()


def test_span_and_ring_tracer_get_the_same_spans():
    """With a Tracer installed the ring holds what the profiler gets."""
    t = Tracer()
    set_tracer(t)
    cfg, params = _serving_model()
    eng = ServingEngine(cfg, params, SERVING_CONFIG)
    eng.submit([5, 6, 7], max_new_tokens=2, request_id="solo")
    eng.run()
    names = {e["name"] for e in t.events()}
    for parent, kids in SERVING_CHILDREN.items():
        assert {parent, *kids} <= names
    assert validate_events(t.to_dict()["traceEvents"], strict=True) == []


# ------------------------------------------------------------------ #
# the compile account by program name
# ------------------------------------------------------------------ #


def _lowered(account, name):
    return account.get(name, {}).get("lower", {}).get("count", 0)


def test_compile_account_names_the_serving_programs_and_counts_shapes():
    from deeperspeed_tpu.monitor import compile_account

    # a width no other test of this file uses, so that nothing here is
    # found already lowered in JAX's own caches
    cfg = GPTConfig(vocab_size=89, n_layer=2, n_head=2, d_model=48,
                    max_seq=64, remat=False, dtype=jnp.float32,
                    attn_impl="xla")
    params = make_gpt(cfg)[0](jax.random.PRNGKey(1))
    before = compile_account()
    eng = ServingEngine(cfg, params, SERVING_CONFIG)   # installs the listener
    eng.submit([1, 2, 3], max_new_tokens=3)            # bucket 8: one page
    eng.run()
    one = compile_account()
    for name in ("ds_decode_step", "ds_prefill", "ds_scatter_prefill_pages"):
        assert _lowered(one, name) - _lowered(before, name) == 1, name
        assert one[name]["trace"]["count"] >= 1
        assert one[name]["compile"]["seconds"] > 0.0
    assert eng.decode_compile_count == 1
    # a prompt of another bucket holds another number of pages: the
    # prefill and the scatter are lowered again, the decode step is not
    eng.submit(list(range(1, 12)), max_new_tokens=2)   # bucket 16: two pages
    eng.run()
    two = compile_account()
    assert _lowered(two, "ds_scatter_prefill_pages") \
        - _lowered(one, "ds_scatter_prefill_pages") == 1
    assert _lowered(two, "ds_prefill") - _lowered(one, "ds_prefill") == 1
    assert _lowered(two, "ds_decode_step") == _lowered(one, "ds_decode_step")
    # what an eager operation dispatches is summed under one row
    assert _lowered(two, "eager") > 0
    assert not any(n in two for n in ("convert_element_type",
                                      "broadcast_in_dim"))


def test_compile_account_names_the_train_step():
    from deeperspeed_tpu.monitor import compile_account

    before = compile_account()
    engine, _, _, _ = deepspeed.initialize(
        model=_loss_fn, model_parameters={"w": jnp.zeros((6, 3))},
        config_params=_train_config({}))
    x = jnp.ones((8, 6))
    y = jnp.ones((8, 3))
    for _ in range(3):
        engine.train_batch(batch=(x, y))
    after = compile_account()
    assert _lowered(after, "ds_train_step") > _lowered(before, "ds_train_step")
    assert not any("lambda" in n for n in after
                   if _lowered(after, n) > _lowered(before, n))


def test_compile_instants_name_the_program_and_phase():
    t = Tracer()
    set_tracer(t)
    from deeperspeed_tpu.monitor import install_compile_listener

    install_compile_listener()

    def ds_probe_program(x):
        return x * 3 + 1

    jax.jit(ds_probe_program)(jnp.ones(7))
    mine = [e["args"] for e in t.events() if e["name"] == "xla_compile"
            and "ds_probe_program" in e["args"]["fun_name"]]
    assert [a["phase"] for a in mine] == ["lower", "compile"]
    assert all(a["fun_name"] == "jit(ds_probe_program)" for a in mine)


def test_trace_event_names_lint_passes_with_the_new_names():
    """The registry in monitor/validate.py and every call site agree, in
    both directions, with the spans this file's tests look for."""
    import os

    from deeperspeed_tpu.analysis import astlint

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rule = astlint.TraceEventNamesRule()
    found = astlint.lint_paths(root, rules=[rule])
    assert [f.to_dict() for f in found] == []
    emitted = {n for n, exact, _, _ in
               rule._emitted(astlint.collect_modules(root)) if exact}
    for parent, kids in SERVING_CHILDREN.items():
        assert {parent, *kids} <= emitted
    assert set(TRAIN_CHILDREN) <= emitted
