"""Serving fleet tests: router admission/shed, wall-clock deadlines,
heartbeat + progress watchdogs with bounded failover (deterministic stub
replicas on a fake clock), kill-retry token identity over real thread
replicas, drain + rolling restart losing nothing, the draining-submit
and progress-timeout engine fixes, finish-reason metrics/validator
schemas, and (slow) the subprocess SIGKILL drill path."""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeperspeed_tpu.models.gpt import GPTConfig, make_gpt
from deeperspeed_tpu.monitor.metrics import MetricsRegistry
from deeperspeed_tpu.monitor.validate import validate_events
from deeperspeed_tpu.serving import (
    EngineDrainingError,
    FINISH_TIMEOUT,
    FleetRouter,
    RouterConfig,
    ServingConfig,
    ServingEngine,
    ShedError,
    build_thread_fleet,
)
from deeperspeed_tpu.serving.fleet import ReplicaUnavailableError
from deeperspeed_tpu.serving.metrics import record_finish_outcome

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module", autouse=True)
def _compile_cache(tmp_path_factory):
    """Every replica in this module compiles the SAME tiny engine; the
    persistent compilation cache turns all but the first compile into a
    ~10ms deserialize, which is what keeps multi-replica fleets + their
    single-engine references affordable in the fast tier. Restored on
    teardown so compile-counting tests elsewhere see stock behavior."""
    d = tmp_path_factory.mktemp("xla_cache")
    jax.config.update("jax_compilation_cache_dir", str(d))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    yield
    jax.config.update("jax_compilation_cache_dir", None)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)


def _cfg(**kw):
    d = dict(vocab_size=97, n_layer=2, n_head=2, d_model=32, max_seq=128,
             remat=False, dtype=jnp.float32, attn_impl="xla")
    d.update(kw)
    return GPTConfig(**d)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    init_fn, _, _, _ = make_gpt(cfg)
    params = init_fn(jax.random.PRNGKey(0))
    return cfg, params


_SCFG = dict(num_slots=4, block_size=8, num_blocks=64, max_seq_len=128,
             max_new_tokens=64, prefill_buckets=(16, 128))


def _warm_factory(cfg, params, **scfg_kw):
    scfg = ServingConfig(**{**_SCFG, **scfg_kw})

    def factory():
        eng = ServingEngine(cfg, params, scfg)
        eng.submit([1, 2, 3], max_new_tokens=2, request_id="_warm")
        eng.submit([4, 5, 6], max_new_tokens=2, temperature=0.5,
                   request_id="_warm2")
        eng.run()
        return eng

    return factory


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class StubReplica:
    """Scripted replica: records submits/cancels, emits pushed events.
    Lets the watchdog/deadline/backoff state machines run on a fake
    clock with zero real concurrency."""

    def __init__(self, name, clock):
        self.name = name
        self._clock = clock
        self.alive = True
        self.heartbeat_t = clock()
        self.progress = 0
        self.restarts = 0
        self.submitted = []
        self.cancelled = []
        self._events = []

    def submit(self, spec):
        if not self.alive:
            raise ReplicaUnavailableError(self.name)
        self.submitted.append(dict(spec))

    def cancel(self, rid, reason="timeout"):
        self.cancelled.append((rid, reason))

    def push(self, **ev):
        self._events.append(ev)

    def poll_events(self):
        evs, self._events = self._events, []
        return evs

    def kill(self):
        self.alive = False

    def restart(self):
        self.restarts += 1
        self.alive = True
        self.heartbeat_t = self._clock()
        self.progress = 0

    def stop(self, timeout_s=1.0):
        self.alive = False

    def drain(self, timeout_s=1.0):
        return []

    def inflight_rids(self):
        return []


def _stub_router(clock, **rcfg_kw):
    kw = dict(num_replicas=2, max_queue_depth=64, retry_max=2,
              retry_backoff_base_s=0.1, retry_backoff_max_s=1.0,
              heartbeat_timeout_s=1000.0, progress_timeout_s=1000.0,
              replica_max_restarts=1, poll_interval_s=0.001)
    kw.update(rcfg_kw)
    stubs = [StubReplica("s0", clock), StubReplica("s1", clock)]
    return FleetRouter(stubs, RouterConfig(**kw), clock=clock), stubs


# ------------------------------------------------------------------ #
# engine satellites: draining submit, progress-based timeout
# ------------------------------------------------------------------ #

def test_engine_submit_rejected_while_draining(model):
    cfg, params = model
    eng = ServingEngine(cfg, params, ServingConfig(**_SCFG))
    eng.submit([1, 2, 3], max_new_tokens=4)
    eng.step()                    # admit to a slot
    leftovers = eng.drain()
    assert leftovers == []        # active work finishes during drain
    with pytest.raises(EngineDrainingError):
        eng.submit([4, 5, 6], max_new_tokens=4)


def test_engine_timeout_requires_lack_of_progress(model):
    """A request making steady token progress must survive far past
    request_timeout_s of wall time; the moment progress stops for a full
    timeout window, it is evicted."""
    cfg, params = model
    clock = FakeClock()
    eng = ServingEngine(
        cfg, params,
        ServingConfig(**{**_SCFG, "request_timeout_s": 5.0}),
        clock=clock)
    rid = eng.submit(list(range(1, 7)), max_new_tokens=40)
    # 6 steps x 3s: age since arrival reaches 18s >> 5s, but every step
    # emits a token, so the progress clock keeps it alive
    for _ in range(6):
        eng.step()
        clock.t += 3.0
    req = eng.get(rid)
    assert req.state == "active"
    assert len(req.generated) >= 6
    # now freeze progress for one full window -> evicted on next step
    clock.t += 5.0
    eng.step()
    assert eng.get(rid).state == "finished"
    assert eng.get(rid).finish_reason == FINISH_TIMEOUT


# ------------------------------------------------------------------ #
# router: admission control
# ------------------------------------------------------------------ #

def test_shed_is_structured_rejection():
    clock = FakeClock()
    router, _ = _stub_router(clock, max_queue_depth=2)
    router.submit([1, 2, 3], max_new_tokens=4)
    router.submit([1, 2, 3], max_new_tokens=4)
    with pytest.raises(ShedError) as ei:
        router.submit([1, 2, 3], max_new_tokens=4)
    assert ei.value.retry_after_s > 0
    assert ei.value.reason == "queue_depth"
    assert router.metrics.shed == 1
    assert router.metrics.accepted == 2


def test_shed_on_token_budget():
    clock = FakeClock()
    router, _ = _stub_router(clock, max_inflight_tokens=20)
    router.submit([1] * 8, max_new_tokens=8)   # 16 of 20
    with pytest.raises(ShedError) as ei:
        router.submit([1] * 8, max_new_tokens=8)
    assert ei.value.reason == "token_budget"
    # finishing the first request releases its budget charge
    rid = next(iter(router.results()))
    router._states[0].replica.push(ev="fin", rid=rid, tokens=[7],
                                   reason="length")
    router.step()
    router.submit([1] * 8, max_new_tokens=8)   # fits again


# ------------------------------------------------------------------ #
# router: watchdogs, failover, deadlines (stub replicas, fake clock)
# ------------------------------------------------------------------ #

def test_heartbeat_watchdog_fails_over_with_retry():
    clock = FakeClock()
    # replica_restart off: the dead replica stays down, so the retry
    # MUST land on the survivor (restart rejoin is tested separately)
    router, (s0, s1) = _stub_router(clock, heartbeat_timeout_s=5.0,
                                    replica_restart=False)
    rid = router.submit([1, 2, 3], max_new_tokens=4)
    router.step()
    assert len(s0.submitted) == 1          # dispatched to s0
    clock.t = 6.0                          # s0 heartbeat goes stale...
    s1.heartbeat_t = clock.t               # ...s1 stays fresh
    router.step()
    downs = router.metrics.summary()["replica_downs"]
    assert [d["cause"] for d in downs] == ["heartbeat"]
    assert not s0.alive                    # router killed the zombie
    clock.t = 7.0                          # past the retry backoff
    s1.heartbeat_t = clock.t
    router.step()
    assert len(s1.submitted) == 1          # failover re-dispatch
    assert s1.submitted[0]["rid"] == rid
    # the retried spec carries the SAME seed -> token-identical replay
    assert s1.submitted[0]["seed"] == s0.submitted[0]["seed"]
    assert router.metrics.retries == 1
    s1.push(ev="first", rid=rid)
    s1.push(ev="fin", rid=rid, tokens=[9, 9], reason="length")
    router.step()
    assert router.outcomes() == {rid: "length"}
    assert router.result(rid).tokens == [9, 9]


def test_progress_watchdog_catches_stall():
    clock = FakeClock()
    router, (s0, s1) = _stub_router(clock, progress_timeout_s=5.0)
    router.submit([1, 2, 3], max_new_tokens=4)
    router.step()
    assert len(s0.submitted) == 1
    # heartbeats keep flowing but the decode counter never moves
    for t in (2.0, 4.0, 6.0):
        clock.t = t
        s0.heartbeat_t = t
        s1.heartbeat_t = t
        router.step()
    downs = router.metrics.summary()["replica_downs"]
    assert [d["cause"] for d in downs] == ["stalled"]
    assert not s0.alive


def test_idle_replica_never_trips_progress_watchdog():
    clock = FakeClock()
    router, (s0, s1) = _stub_router(clock, progress_timeout_s=5.0)
    for t in (3.0, 9.0, 20.0):   # no work assigned, progress frozen
        clock.t = t
        s0.heartbeat_t = t
        s1.heartbeat_t = t
        router.step()
    assert router.metrics.summary()["replica_downs"] == []


def test_retry_budget_exhausted_is_terminal_failed():
    clock = FakeClock()
    router, (s0, s1) = _stub_router(clock, retry_max=0,
                                    heartbeat_timeout_s=5.0)
    rid = router.submit([1, 2, 3], max_new_tokens=4)
    router.step()
    clock.t = 6.0
    s1.heartbeat_t = clock.t
    router.step()   # s0 down; retry budget 0 -> terminal, not lost
    assert router.outcomes() == {rid: "failed"}
    assert router.unfinished() == []


def test_deadline_enforced_at_router():
    clock = FakeClock()
    router, (s0, s1) = _stub_router(clock, default_deadline_s=5.0)
    rid = router.submit([1, 2, 3], max_new_tokens=4)
    router.step()
    clock.t = 4.0
    s0.heartbeat_t = s1.heartbeat_t = clock.t
    router.step()
    assert router.outcomes() == {}         # within budget
    clock.t = 6.0
    s0.heartbeat_t = s1.heartbeat_t = clock.t
    router.step()
    assert router.outcomes() == {rid: FINISH_TIMEOUT}
    assert (rid, FINISH_TIMEOUT) in s0.cancelled
    # late fin from the replica must not resurrect the request
    s0.push(ev="fin", rid=rid, tokens=[1], reason="length")
    router.step()
    assert router.outcomes() == {rid: FINISH_TIMEOUT}


def test_crashed_replica_restarts_with_backoff():
    clock = FakeClock()
    router, (s0, s1) = _stub_router(clock, heartbeat_timeout_s=5.0,
                                    replica_max_restarts=1)
    router.submit([1, 2, 3], max_new_tokens=4)
    router.step()
    clock.t = 6.0
    s1.heartbeat_t = clock.t
    router.step()                          # s0 marked down, restart armed
    assert s0.restarts == 0                # backoff not yet elapsed
    clock.t = 10.0
    s1.heartbeat_t = clock.t
    router.step()
    assert s0.restarts == 1                # restarted and healthy again


# ------------------------------------------------------------------ #
# real thread replicas: kill-retry token identity, drain/rolling restart
# ------------------------------------------------------------------ #

def _fleet_rcfg(**kw):
    d = dict(num_replicas=2, max_queue_depth=64, retry_max=3,
             retry_backoff_base_s=0.01, retry_backoff_max_s=0.1,
             heartbeat_timeout_s=60.0, progress_timeout_s=60.0,
             poll_interval_s=0.002)
    d.update(kw)
    return RouterConfig(**d)


def _reference_outputs(factory, prompts, news, temps, rids):
    eng = factory()
    for p, n, t, rid in zip(prompts, news, temps, rids):
        eng.submit(p, max_new_tokens=n, temperature=t, request_id=rid)
    eng.run()
    return {rid: eng.get(rid).output for rid in rids}


def test_thread_fleet_kill_retry_token_identity(model):
    """SIGKILL-analogue on a thread replica mid-decode: the router
    requeues its in-flight requests and the retried outputs — greedy AND
    sampled — are token-identical to an unkilled single-engine run."""
    cfg, params = model
    factory = _warm_factory(cfg, params)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 97, rng.integers(4, 12)).tolist()
               for _ in range(6)]
    news = [100] * 6
    temps = [0.0, 0.7] * 3
    rids = [f"q{i}" for i in range(6)]
    ref = _reference_outputs(factory, prompts, news, temps, rids)

    fleet = build_thread_fleet(2, factory)
    router = FleetRouter(fleet, _fleet_rcfg())
    try:
        for p, n, t, rid in zip(prompts, news, temps, rids):
            router.submit(p, max_new_tokens=n, temperature=t,
                          request_id=rid)
        router.step()                       # dispatch
        # a few decode steps land (a toy replica's whole share takes
        # tens of milliseconds: watch its progress, not the clock)
        seen = fleet[0].progress
        deadline = time.monotonic() + 30.0
        while fleet[0].progress < seen + 9 and time.monotonic() < deadline:
            time.sleep(0.0005)
        fleet[0].kill()
        outcomes = router.run_until_idle(timeout_s=120)
        assert all(v in ("length", "eos") for v in outcomes.values()), \
            outcomes
        assert sorted(outcomes) == sorted(rids)   # zero loss
        for rid in rids:
            assert router.result(rid).tokens == ref[rid], rid
        downs = router.metrics.summary()["replica_downs"]
        assert any(d["cause"] == "dead" for d in downs)
    finally:
        router.shutdown()


def test_drain_and_rolling_restart_lose_nothing(model):
    cfg, params = model
    factory = _warm_factory(cfg, params)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 97, 8).tolist() for _ in range(6)]
    news = [32] * 6
    temps = [0.0, 0.5] * 3
    rids = [f"d{i}" for i in range(6)]
    ref = _reference_outputs(factory, prompts, news, temps, rids)

    fleet = build_thread_fleet(2, factory)
    router = FleetRouter(fleet, _fleet_rcfg())
    try:
        for p, n, t, rid in zip(prompts, news, temps, rids):
            router.submit(p, max_new_tokens=n, temperature=t,
                          request_id=rid)
        router.step()
        router.rolling_restart(timeout_s=60)
        outcomes = router.run_until_idle(timeout_s=120)
        assert sorted(outcomes) == sorted(rids)
        assert all(v in ("length", "eos") for v in outcomes.values()), \
            outcomes
        for rid in rids:
            assert router.result(rid).tokens == ref[rid], rid
        assert all(st.replica.restarts == 1 for st in router._states)
        # graceful lifecycle: drained work is not charged retry budget,
        # so nothing went down and nothing "failed"
        assert router.metrics.summary()["replica_downs"] == []
    finally:
        router.shutdown()


# ------------------------------------------------------------------ #
# finish reasons: metrics labels + trace schema validation
# ------------------------------------------------------------------ #

def test_finish_reason_counter_labels():
    reg = MetricsRegistry()
    for reason in ("length", "eos", "timeout", "shed", "retried",
                   "failed"):
        record_finish_outcome(reg, reason)
    record_finish_outcome(reg, "length")
    assert reg.counter("serving_finish_total",
                       labels={"reason": "length"}).value == 2
    assert reg.counter("serving_finish_total",
                       labels={"reason": "shed"}).value == 1


def test_validator_enforces_fleet_instant_schemas():
    def instant(name, args):
        return {"ph": "i", "name": name, "ts": 1, "pid": 1, "tid": 1,
                "s": "t", "args": args}

    good = [
        instant("serving/finish", {"rid": "a", "reason": "length"}),
        instant("serving/shed", {"rid": "b", "retry_after_s": 0.1}),
        instant("serving/retry", {"rid": "a", "attempt": 2,
                                  "replica": "r1"}),
        instant("serving/replica_down", {"replica": "r0",
                                         "cause": "dead",
                                         "inflight": 3}),
    ]
    assert validate_events(good) == []
    bad = [instant("serving/shed", {"rid": "b"}),
           {"ph": "i", "name": "serving/retry", "ts": 1, "pid": 1,
            "tid": 1, "s": "t"}]
    errors = validate_events(bad)
    assert len(errors) == 2
    assert "retry_after_s" in errors[0]
    assert "args" in errors[1]


def test_fleet_config_block():
    scfg = ServingConfig.from_dict(
        {"fleet": {"num_replicas": 3, "max_queue_depth": 16,
                   "default_deadline_s": 30.0}})
    assert scfg.fleet.num_replicas == 3
    assert scfg.fleet.default_deadline_s == 30.0
    with pytest.raises(ValueError, match="unknown fleet config"):
        ServingConfig.from_dict({"fleet": {"replicas": 3}})
    with pytest.raises(ValueError, match="retry_max"):
        RouterConfig(retry_max=-1)


# ------------------------------------------------------------------ #
# subprocess replicas: real SIGKILL + the drill (slow)
# ------------------------------------------------------------------ #

_SUB_SPEC = {
    "gpt": {"vocab_size": 97, "n_layer": 2, "n_head": 2, "d_model": 32,
            "max_seq": 128, "remat": False, "attn_impl": "xla"},
    "init_seed": 0,
    "serving": {"num_slots": 4, "block_size": 8, "num_blocks": 64,
                "max_seq_len": 128, "max_new_tokens": 64,
                "prefill_buckets": [16, 128]},
    "warm": True,
}


@pytest.mark.slow
def test_subprocess_sigkill_mid_decode_token_identity(tmp_path):
    """The real thing: SIGKILL a subprocess replica mid-decode; the
    router requeues its rids and the retried greedy outputs are
    token-identical to an unkilled in-process reference run."""
    from deeperspeed_tpu.serving.fleet import build_subprocess_fleet
    from deeperspeed_tpu.serving.replica_worker import build_engine

    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 97, 8).tolist() for _ in range(4)]
    rids = [f"k{i}" for i in range(4)]
    ref_eng = build_engine(_SUB_SPEC)
    for p, rid in zip(prompts, rids):
        ref_eng.submit(p, max_new_tokens=96, request_id=rid)
    ref_eng.run()
    ref = {rid: ref_eng.get(rid).output for rid in rids}

    fleet = build_subprocess_fleet(2, _SUB_SPEC,
                                   workdir=str(tmp_path))
    router = FleetRouter(fleet, _fleet_rcfg(heartbeat_timeout_s=30.0))
    try:
        for p, rid in zip(prompts, rids):
            router.submit(p, max_new_tokens=96, request_id=rid)
        router.step()
        # wait for the replica's decode counter to move past its warmup
        # tokens, so the SIGKILL provably lands MID-decode
        deadline = time.time() + 20
        while fleet[0].progress < 12 and time.time() < deadline:
            router.step()
            time.sleep(0.005)
        assert fleet[0].progress >= 12, "replica never started decoding"
        fleet[0].kill()                      # actual SIGKILL
        outcomes = router.run_until_idle(timeout_s=180)
        assert sorted(outcomes) == sorted(rids)
        assert all(v == "length" for v in outcomes.values()), outcomes
        for rid in rids:
            assert router.result(rid).tokens == ref[rid], rid
        s = router.metrics.summary()
        assert any(d["cause"] == "dead" for d in s["replica_downs"])
        assert s["retries"] >= 1
    finally:
        router.shutdown()


@pytest.mark.slow
@pytest.mark.drill
def test_fleet_drill_quick(tmp_path):
    """CI wrapper for scripts/fleet_drill.py: quick Poisson trace with a
    SIGKILLed and a stalled replica; asserts the zero-loss audit passed
    and the drill trace survives the monitor validator CLI."""
    out = tmp_path / "BENCH_fleet.json"
    trace = tmp_path / "fleet_drill_trace.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "fleet_drill.py"),
         "--quick", "--out", str(out), "--trace", str(trace)],
        env=env, capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    result = json.loads(out.read_text())
    assert result["pass"] is True
    assert result["failover"]["fault"]["lost_accepted"] == []
    assert result["failover"]["fault"]["retries"] >= 1
    causes = {d["cause"]
              for d in result["failover"]["fault"]["replica_downs"]}
    assert {"dead", "stalled"} <= causes
    assert result["shed_curve"]["points"][-1]["shed_rate"] > 0
    # the satellite's exact CLI contract
    rc = subprocess.run(
        [sys.executable, "-m", "deeperspeed_tpu.monitor.validate",
         str(trace)], env=env, capture_output=True, text=True)
    assert rc.returncode == 0, rc.stdout + rc.stderr
