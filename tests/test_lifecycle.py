"""Lifecycle control-plane tests: config validation, the weight-version
registry (publish/retire/prune protection), the re-mesh hook state
machine, engine.remesh guard rails + subprocess bit-identity, version-
pinned routing on mixed-version fleets (incl. mid-decode failover and
the repin fallback), and (slow) the end-to-end drill wrapper."""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deeperspeed_tpu as deepspeed
from deeperspeed_tpu.lifecycle import (
    LifecycleConfig,
    RemeshHook,
    VersionRegistry,
    live_tags,
)
from deeperspeed_tpu.models.gpt import GPTConfig, make_gpt
from deeperspeed_tpu.runtime.config import TrainingConfig
from deeperspeed_tpu.serving import (
    FleetRouter,
    RouterConfig,
    ServingConfig,
    ServingEngine,
)
from deeperspeed_tpu.serving.fleet import ThreadReplica

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module", autouse=True)
def _compile_cache(tmp_path_factory):
    """Same trick as test_fleet.py: every replica compiles the same tiny
    engine, so the persistent cache makes fleet tests affordable."""
    d = tmp_path_factory.mktemp("xla_cache")
    jax.config.update("jax_compilation_cache_dir", str(d))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    yield
    jax.config.update("jax_compilation_cache_dir", None)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)


# ------------------------------------------------------------------ #
# config
# ------------------------------------------------------------------ #

def test_lifecycle_config_defaults_and_validation():
    cfg = LifecycleConfig.from_dict({})
    assert cfg.enabled and cfg.remesh_enabled and cfg.publish
    assert cfg.remesh_signal == "SIGUSR1"
    assert cfg.signal_number() == int(__import__("signal").SIGUSR1)
    assert cfg.keep_live_versions == 2

    with pytest.raises(ValueError, match="unknown lifecycle config"):
        LifecycleConfig.from_dict({"remesh_debouce_s": 1.0})  # typo
    with pytest.raises(ValueError, match="not a signal name"):
        LifecycleConfig.from_dict({"remesh_signal": "SIGWAT"})
    with pytest.raises(ValueError, match="keep_live_versions"):
        LifecycleConfig.from_dict({"keep_live_versions": 0})
    with pytest.raises(ValueError, match="remesh_debounce_s"):
        LifecycleConfig.from_dict({"remesh_debounce_s": -1.0})


def test_master_config_lifecycle_block():
    cfg = TrainingConfig({
        "train_batch_size": 8,
        "lifecycle": {"enabled": True, "keep_live_versions": 3},
    })
    lc = cfg.lifecycle_config()
    assert lc is not None and lc.keep_live_versions == 3
    assert TrainingConfig({"train_batch_size": 8}).lifecycle_config() \
        is None
    from deeperspeed_tpu.runtime.config import ConfigError
    with pytest.raises(ConfigError):
        TrainingConfig({"train_batch_size": 8, "lifecycle": "yes"})
    with pytest.raises(ConfigError):
        TrainingConfig({"train_batch_size": 8,
                        "lifecycle": {"no_such_key": 1}})


# ------------------------------------------------------------------ #
# version registry (over real committed checkpoints)
# ------------------------------------------------------------------ #

def _loss_fn(p, b):
    x, y = b
    return jnp.mean((x @ p["w"] - y) ** 2)


def _engine(resilience=None, lifecycle=None, seed=0):
    cfg = {
        "train_batch_size": 8,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
    }
    if resilience is not None:
        cfg["resilience"] = resilience
    if lifecycle is not None:
        cfg["lifecycle"] = lifecycle
    params = {"w": jax.random.normal(jax.random.PRNGKey(seed), (4, 2))
              * 0.1}
    engine, _, _, _ = deepspeed.initialize(
        model=_loss_fn, model_parameters=params, config_params=cfg)
    return engine


def _batch(seed=0):
    rs = np.random.RandomState(seed)
    return (jnp.asarray(rs.randn(8, 4).astype(np.float32)),
            jnp.asarray(rs.randn(8, 2).astype(np.float32)))


def test_version_registry_publish_retire(tmp_path):
    engine = _engine(resilience={"async_save": False,
                                 "preemption_guard": False})
    engine.train_batch(batch=_batch(0))
    engine.save_checkpoint(str(tmp_path))
    engine.train_batch(batch=_batch(1))
    engine.save_checkpoint(str(tmp_path))

    reg = VersionRegistry(str(tmp_path), keep_live=1)
    v1 = reg.publish("global_step1")
    assert (v1.version, v1.tag, v1.step) == (1, "global_step1", 1)
    # idempotent while live: no duplicate version for the same tag
    assert reg.publish("global_step1").version == 1
    v2 = reg.publish("global_step2")
    assert v2.version == 2
    # keep_live=1 retired v1 on the next publish
    assert [v.version for v in reg.list() if v.live] == [2]
    assert reg.latest().version == 2
    assert reg.live_tags() == {"global_step2": 2}
    assert live_tags(str(tmp_path)) == {"global_step2": 2}

    # only committed tags are publishable
    with pytest.raises(ValueError, match="refusing to publish"):
        reg.publish("global_step99")
    (tmp_path / "global_step3").mkdir()          # torn/staging dir
    with pytest.raises(ValueError, match="refusing to publish"):
        reg.publish("global_step3")

    assert reg.retire(2) and not reg.retire(2)   # second call: no-op
    assert reg.latest() is None
    assert live_tags(str(tmp_path)) == {}
    # version numbers are never reused after retirement
    engine.train_batch(batch=_batch(2))
    engine.save_checkpoint(str(tmp_path))
    assert reg.publish("global_step3").version == 3


def test_version_pair_target_plus_drafter(tmp_path):
    """Speculative serving rolls out (target, drafter) as ONE unit: the
    record carries both tags, survives the JSON round trip, pairs
    idempotently, and the rollout pointer ships the drafter tag to the
    replica's set_weights."""
    engine = _engine(resilience={"async_save": False,
                                 "preemption_guard": False})
    engine.train_batch(batch=_batch(0))
    engine.save_checkpoint(str(tmp_path))        # global_step1 (drafter)
    engine.train_batch(batch=_batch(1))
    engine.save_checkpoint(str(tmp_path))        # global_step2 (target)

    reg = VersionRegistry(str(tmp_path))
    v1 = reg.publish("global_step2", drafter="global_step1")
    assert v1.drafter == "global_step1"
    # idempotent for the SAME pair...
    assert reg.publish("global_step2",
                       drafter="global_step1").version == v1.version
    # ...but a different drafter for the same target is a NEW routable
    # unit (acceptance-rate comparability pins the pair, not the target)
    v2 = reg.publish("global_step2", drafter=None)
    assert v2.version == v1.version + 1 and v2.drafter is None

    # serde: the pair survives VERSIONS.json
    fresh = VersionRegistry(str(tmp_path))
    assert {v.version: v.drafter for v in fresh.list()} == \
        {v1.version: "global_step1", v2.version: None}

    # an uncommitted drafter tag is rejected exactly like a torn target
    with pytest.raises(ValueError, match="drafter tag"):
        reg.publish("global_step2", drafter="global_step99")

    # the rollout pointer ships both tags
    from deeperspeed_tpu.lifecycle.controller import RolloutDriver
    drv = RolloutDriver(router=None, registry=reg)
    ptr = drv._checkpoint_pointer(v1)
    assert ptr["tag"] == "global_step2"
    assert ptr["drafter_tag"] == "global_step1"
    assert "drafter_tag" not in drv._checkpoint_pointer(v2)

    # publisher-side: an armed drafter_tag rides every publish
    from deeperspeed_tpu.lifecycle.controller import VersionPublisher
    engine.train_batch(batch=_batch(2))
    engine.save_checkpoint(str(tmp_path))        # global_step3
    pub = VersionPublisher(str(tmp_path), registry=reg)
    pub.drafter_tag = "global_step1"
    rec = pub.poll()
    assert rec is not None and rec.tag == "global_step3"
    assert rec.drafter == "global_step1"


def test_publisher_autowires_and_publishes_on_save(tmp_path):
    """An engine with resilience + lifecycle blocks publishes every
    committed interval autosave with no extra wiring."""
    engine = _engine(
        resilience={"save_dir": str(tmp_path), "save_interval_steps": 1,
                    "async_save": False, "preemption_guard": False},
        lifecycle={"enabled": True})
    for i in range(3):
        engine.train_batch(batch=_batch(i))
    lc = engine._lifecycle
    assert lc is not None and lc.publisher.published == 3
    reg = VersionRegistry(str(tmp_path))
    assert [v.version for v in reg.list()] == [1, 2, 3]
    # default keep_live=2: only the newest two stay live
    assert sorted(reg.live_tags().values()) == [2, 3]


def test_prune_never_deletes_live_version_tags(tmp_path):
    """The satellite regression: keep_last pruning must not delete a
    tag published as a LIVE weight version — the fleet may still be
    routing to it."""
    engine = _engine(
        resilience={"save_dir": str(tmp_path), "save_interval_steps": 1,
                    "keep_last": 1, "async_save": False,
                    "preemption_guard": False},
        lifecycle={"enabled": True, "keep_live_versions": 2})
    for i in range(4):
        engine.train_batch(batch=_batch(i))
    tags = {p.name for p in tmp_path.iterdir() if p.is_dir()}
    alive = set(VersionRegistry(str(tmp_path)).live_tags())
    assert alive == {"global_step3", "global_step4"}
    # keep_last=1 alone would leave only global_step4; the live v3 tag
    # must survive because the registry still lists it
    assert alive <= tags, (alive, tags)
    # retention still works once a tag leaves the live window (prune
    # runs before publish at each boundary, so it lags one save)
    assert "global_step1" not in tags, tags
    # one more step: global_step2 was retired at the boundary-4 publish,
    # so the boundary-5 prune is free to drop it; the new live window
    # {4, 5} plus the just-retired 3 remain
    engine.train_batch(batch=_batch(4))
    tags = {p.name for p in tmp_path.iterdir() if p.is_dir()}
    assert tags == {"global_step3", "global_step4", "global_step5"}, tags
    assert set(VersionRegistry(str(tmp_path)).live_tags()) == \
        {"global_step4", "global_step5"}


# ------------------------------------------------------------------ #
# remesh hook + engine guard rails
# ------------------------------------------------------------------ #

class _FakeCfg:
    elastic_valid_world_sizes = [1, 2, 4, 8]


class _FakeEngine:
    """Records remesh calls; starts at a sentinel world size so a
    pool of 1 always forces a flip regardless of the host's device
    count (choose_world caps at min(len(jax.devices()), pool))."""

    def __init__(self):
        self._config = _FakeCfg()
        self.data_parallel_size = 999
        self.remeshed = []

    def remesh(self, world):
        self.data_parallel_size = world
        self.remeshed.append(world)
        return world


def test_remesh_hook_state_machine(tmp_path):
    pool = tmp_path / "pool"
    hook = RemeshHook(LifecycleConfig(remesh_debounce_s=0.0),
                      pool_file=str(pool))
    eng = _FakeEngine()
    assert not hook.poll(eng)            # nothing pending
    assert hook.read_pool() is None      # unreadable file -> None

    hook.request()
    assert hook.pending
    pool.write_text("1\n")               # only world 1 fits the pool
    assert hook.poll(eng)
    assert eng.remeshed == [1] and hook.remeshes == 1
    assert hook.last_world == 1 and not hook.pending

    # a second signal resolving to the CURRENT world is a no-op
    hook.request()
    assert not hook.poll(eng)
    assert eng.remeshed == [1] and not hook.pending

    # debounce: a just-arrived signal waits for a quiet boundary
    hook2 = RemeshHook(LifecycleConfig(remesh_debounce_s=60.0))
    hook2.request()
    assert not hook2.poll(eng)
    assert hook2.pending                 # still latched for later

    # disabled hook ignores signals entirely
    hook3 = RemeshHook(LifecycleConfig(remesh_enabled=False))
    hook3.request()
    assert not hook3.poll(eng)


def test_remesh_hook_no_elasticity_stays_put():
    class _NoElastic:
        class _config:  # noqa: N801 - mimics engine attr
            elastic_valid_world_sizes = None
        data_parallel_size = 1

    hook = RemeshHook(LifecycleConfig(remesh_debounce_s=0.0))
    hook.request()
    assert not hook.poll(_NoElastic())
    assert hook.remeshes == 0


def test_engine_remesh_guards():
    engine = _engine()
    # same world: no-op, no elasticity needed
    assert engine.remesh(engine.data_parallel_size) == \
        engine.data_parallel_size
    with pytest.raises(RuntimeError, match="elasticity"):
        engine.remesh(2)


_REMESH_TRAINER = """\
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import numpy as np
import deeperspeed_tpu as ds
from tests.simple_model import init_linear_stack, linear_stack_loss

DIMS = [16, 32, 16]
cfg = {
    "steps_per_print": 1000,
    "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
    "zero_optimization": {"stage": 0},
    "comm": {"mode": "int8", "bucket_mb": 0.005, "error_feedback": True},
    "elasticity": {
        "enabled": True, "max_train_batch_size": 64,
        "micro_batch_sizes": [8], "min_gpus": 1, "max_gpus": 64,
        "version": 0.1, "canonical_shards": 16,
    },
}

def batch(seed):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(64, DIMS[0])).astype(np.float32)
    y = (np.tanh(x[:, :DIMS[-1]]) * 0.5).astype(np.float32)
    return (x, y)

def run(remesh_at=None, new_world=4, steps=6):
    params = init_linear_stack(jax.random.PRNGKey(0), DIMS)
    engine, _, _, _ = ds.initialize(
        model=linear_stack_loss, model_parameters=params, config=cfg)
    losses = []
    for s in range(steps):
        if remesh_at is not None and s == remesh_at:
            assert engine.remesh(new_world) == new_world
            assert engine.data_parallel_size == new_world
        losses.append(float(np.asarray(engine.train_batch(batch(s)))))
    return losses

ref = run()
shrink = run(remesh_at=3, new_world=4)
deep = run(remesh_at=2, new_world=2)
assert max(abs(a - b) for a, b in zip(ref, shrink)) == 0.0, shrink
assert max(abs(a - b) for a, b in zip(ref, deep)) == 0.0, deep
print("REMESH_OK")
"""


@pytest.mark.slow
def test_remesh_bit_identity_vs_uninterrupted(tmp_path):
    """Live 8->4 and 8->2 flips mid-run (int8 comm + error feedback,
    canonical_shards=16) produce losses bit-identical to an
    uninterrupted 8-device run — the tentpole's core claim."""
    script = tmp_path / "probe.py"
    script.write_text(_REMESH_TRAINER)
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=560,
                          cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr[-3000:]
    assert "REMESH_OK" in proc.stdout


# ------------------------------------------------------------------ #
# version-pinned routing over mixed-version thread fleets
# ------------------------------------------------------------------ #

_SCFG = dict(num_slots=4, block_size=8, num_blocks=64, max_seq_len=128,
             max_new_tokens=64, prefill_buckets=(16, 128))


def _gpt_cfg():
    return GPTConfig(vocab_size=97, n_layer=2, n_head=2, d_model=32,
                     max_seq=128, remat=False, dtype=jnp.float32,
                     attn_impl="xla")


def _version_factory(seed):
    """Engine factory for one weight version: distinct init seed ->
    distinct weights -> distinct token streams."""
    cfg = _gpt_cfg()
    init_fn, _, _, _ = make_gpt(cfg)
    params = init_fn(jax.random.PRNGKey(seed))
    scfg = ServingConfig(**_SCFG)

    def factory():
        eng = ServingEngine(cfg, params, scfg)
        eng.submit([1, 2, 3], max_new_tokens=2, request_id="_warm")
        eng.submit([4, 5, 6], max_new_tokens=2, temperature=0.5,
                   request_id="_warm2")
        eng.run()
        return eng

    return factory


def _reference_outputs(factory, prompts, news, temps, rids):
    eng = factory()
    for p, n, t, rid in zip(prompts, news, temps, rids):
        eng.submit(p, max_new_tokens=n, temperature=t, request_id=rid)
    eng.run()
    return {rid: eng.get(rid).output for rid in rids}


def _versioned_fleet(assignments):
    """[(name, factory, version), ...] -> started thread replicas with
    their version labels applied via set_weights."""
    fleet = [ThreadReplica(name, factory, poll_interval_s=0.001)
             for name, factory, _ in assignments]
    for rep in fleet:
        rep.start()
    for rep, (_, _, version) in zip(fleet, assignments):
        rep.wait_ready()
        rep.set_weights(None, version)
    return fleet


def _rcfg(**kw):
    d = dict(num_replicas=2, max_queue_depth=64, retry_max=3,
             retry_backoff_base_s=0.01, retry_backoff_max_s=0.1,
             heartbeat_timeout_s=60.0, progress_timeout_s=60.0,
             poll_interval_s=0.002)
    d.update(kw)
    return RouterConfig(**d)


def _request_trace(n, seed=0):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, 97, int(rng.integers(4, 12))).tolist()
               for _ in range(n)]
    news = [100] * n
    temps = [0.0, 0.7] * (n // 2) + [0.0] * (n % 2)
    rids = [f"v{i}" for i in range(n)]
    return prompts, news, temps, rids


def _kill_mid_decode(replica):
    """Kill once a few of its decode steps have landed. A toy replica's
    whole share takes tens of milliseconds, so its progress is watched,
    not the clock."""
    seen = replica.progress
    deadline = time.monotonic() + 30.0
    while replica.progress < seen + 9 and time.monotonic() < deadline:
        time.sleep(0.0005)
    replica.kill()


def test_mixed_version_fleet_failover_stays_pinned():
    """Mixed v1/v2 fleet with a v1 replica SIGKILL-analogue mid-decode:
    every request's tokens match the single-engine reference of the
    version it PINNED to — greedy and sampled — even across failover
    (the retry lands on the surviving v1 replica, never v2)."""
    f1, f2 = _version_factory(0), _version_factory(1)
    prompts, news, temps, rids = _request_trace(6)
    ref = {1: _reference_outputs(f1, prompts, news, temps, rids),
           2: _reference_outputs(f2, prompts, news, temps, rids)}

    fleet = _versioned_fleet([("a", f1, 1), ("b", f1, 1), ("c", f2, 2)])
    router = FleetRouter(fleet, _rcfg(num_replicas=3))
    try:
        for p, n, t, rid in zip(prompts, news, temps, rids):
            router.submit(p, max_new_tokens=n, temperature=t,
                          request_id=rid)
        router.step()                       # dispatch + pin
        pinned_v1 = [rid for rid in rids
                     if router.result(rid).version == 1]
        _kill_mid_decode(fleet[0])          # one v1 replica dies
        outcomes = router.run_until_idle(timeout_s=120)
        assert sorted(outcomes) == sorted(rids)
        assert all(v in ("length", "eos") for v in outcomes.values()), \
            outcomes
        for rid in rids:
            rec = router.result(rid)
            assert rec.version in (1, 2), rid
            assert rec.repins == 0, rid     # pins survived the kill
            assert rec.tokens == ref[rec.version][rid], \
                (rid, rec.version)
        # the kill provably hit pinned-v1 work and it stayed v1
        assert pinned_v1
        assert all(router.result(rid).version == 1 for rid in pinned_v1)
        assert any(d["cause"] == "dead"
                   for d in router.metrics.summary()["replica_downs"])
    finally:
        router.shutdown()


def test_version_starvation_repins_with_full_regeneration():
    """When a pinned version loses its LAST replica, the request repins
    to a surviving version and its ENTIRE stream is regenerated there —
    the output equals the new version's reference, never a splice of
    two weight sets."""
    f1, f2 = _version_factory(0), _version_factory(1)
    prompts, news, temps, rids = _request_trace(4, seed=1)
    ref2 = _reference_outputs(f2, prompts, news, temps, rids)

    fleet = _versioned_fleet([("a", f1, 1), ("b", f2, 2)])
    router = FleetRouter(fleet, _rcfg(replica_restart=False))
    try:
        for p, n, t, rid in zip(prompts, news, temps, rids):
            router.submit(p, max_new_tokens=n, temperature=t,
                          request_id=rid)
        router.step()
        pinned_v1 = [rid for rid in rids
                     if router.result(rid).version == 1]
        assert pinned_v1                    # someone is on v1
        _kill_mid_decode(fleet[0])          # v1's ONLY replica dies
        outcomes = router.run_until_idle(timeout_s=120)
        assert sorted(outcomes) == sorted(rids)
        assert all(v in ("length", "eos") for v in outcomes.values()), \
            outcomes
        for rid in pinned_v1:
            rec = router.result(rid)
            assert rec.repins >= 1, rid
            assert rec.version == 2, rid
            assert rec.tokens == ref2[rid], rid
    finally:
        router.shutdown()


# ------------------------------------------------------------------ #
# the drill wrapper (slow tier)
# ------------------------------------------------------------------ #

@pytest.mark.slow
@pytest.mark.drill
def test_lifecycle_drill_quick(tmp_path):
    """CI wrapper for scripts/lifecycle_drill.py: two weight pushes and
    one live pool shrink under Poisson load; asserts the bit-identity,
    zero-loss and goodput audits passed and both traces survive the
    strict validator CLI."""
    out = tmp_path / "BENCH_lifecycle.json"
    trace = tmp_path / "lifecycle_drill_trace.json"
    ttrace = tmp_path / "lifecycle_trainer_trace.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "lifecycle_drill.py"),
         "--quick", "--out", str(out), "--trace", str(trace),
         "--trainer-trace", str(ttrace)],
        env=env, capture_output=True, text=True, timeout=840)
    assert proc.returncode == 0, proc.stdout + proc.stderr[-4000:]
    result = json.loads(out.read_text())
    assert result["pass"] is True
    assert result["remesh"]["max_loss_delta"] == 0.0
    assert result["remesh"]["remeshes"] == 1
    assert result["serving"]["lost_accepted"] == 0
    assert result["weight_pushes"] >= 2
    assert result["goodput"]["restart_s"] < 0.5
    assert result["goodput"]["remesh_s"] > 0.0
    assert result["supervisor"]["launches"] == 1
    for path in (trace, ttrace):
        rc = subprocess.run(
            [sys.executable, "-m", "deeperspeed_tpu.monitor.validate",
             "--strict", str(path)],
            env=env, capture_output=True, text=True)
        assert rc.returncode == 0, rc.stdout + rc.stderr
