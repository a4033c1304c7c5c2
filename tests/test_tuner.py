"""Autotune tests: the admissible-space enumerator's edge cases (no tp
beyond the head count, exactly-one ``-1`` inference, HBM-infeasible
candidates reported rather than dropped, space-hash determinism), the
watchdog-safety contract of AOT candidate capture (a 10-candidate sweep
against a strict RecompileWatchdog with zero firings and untouched jit
caches), the wire model's mode ordering, provenance signing + tamper
detection through both verify_provenance and the analysis gate, and the
emitted config round-tripping runtime config validation unchanged."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from deeperspeed_tpu.autotune import (
    CommCandidate,
    ModelSpec,
    aot_capture,
    enumerate_comm_variants,
    enumerate_mesh_layouts,
    enumerate_serving_buckets,
    knob_fingerprint,
    make_provenance,
    platform_budget,
    price_layout,
    price_serving,
    rank_candidates,
    resolve_block,
    sandboxed_cost_index,
    space_hash,
    spearman,
    verify_provenance,
)
from deeperspeed_tpu.autotune.costmodel import (
    build_candidate_engine,
    effective_micro,
)
from deeperspeed_tpu.analysis.provenance import check_config_provenance
from deeperspeed_tpu.monitor import Tracer, set_tracer, shutdown_monitor
from deeperspeed_tpu.monitor.perf import _cache_size
from deeperspeed_tpu.monitor.watchdog import RecompileWatchdog
from deeperspeed_tpu.runtime.comm import wiremodel
from deeperspeed_tpu.runtime.comm.bucketing import Bucket, BucketPlan
from deeperspeed_tpu.runtime.comm.config import CommConfig
from deeperspeed_tpu.runtime.config import ConfigError, TrainingConfig

TINY = ModelSpec()  # vocab 256, 2 layers, 4 heads, d_model 64, seq 32


@pytest.fixture(autouse=True)
def _clean_global_monitor():
    """Telemetry state is process-global; leave no tracer/monitor behind."""
    yield
    shutdown_monitor(save=False)
    set_tracer(None)


# ------------------------------------------------------------------ #
# enumerator edge cases
# ------------------------------------------------------------------ #


def test_enumerator_world8_covers_mesh_bench_layouts():
    names = {c.name for c in enumerate_mesh_layouts(8, TINY)}
    # the canonical mesh_bench sweep must be a subset of the admissible
    # space (the bench now sources its list from this enumerator)
    for required in ("dp8", "fsdp8", "fsdp8_zero3", "dp2_fsdp4",
                     "dp2_fsdp4_zero2", "dp2_tp4", "dp2_sp4"):
        assert required in names, f"{required} missing from {sorted(names)}"


def test_enumerator_no_tp_beyond_head_count():
    # n_head=3: no tp extent > 1 divides it, so tp stays out of the space
    odd = ModelSpec(n_head=3, d_model=48)
    for c in enumerate_mesh_layouts(8, odd):
        assert c.extents()["tp"] == 1
    # and with 4 heads, tp=8 is still inadmissible at world=8
    for c in enumerate_mesh_layouts(8, TINY):
        assert c.extents()["tp"] <= TINY.n_head


def test_enumerator_sp_divides_seq():
    short = ModelSpec(seq=12)  # 8 does not divide 12 -> no sp8
    names = {c.name for c in enumerate_mesh_layouts(8, short)}
    assert "sp8" not in names
    assert "dp2_sp4" in names  # 4 divides 12


def test_enumerator_zero_stages_need_fsdp():
    for c in enumerate_mesh_layouts(8, TINY):
        if c.zero_stage > 1:
            assert c.extents()["fsdp"] > 1, (
                f"{c.name}: ZeRO stage {c.zero_stage} without an fsdp axis")


def test_enumerator_deterministic_order():
    a = enumerate_mesh_layouts(8, TINY)
    b = enumerate_mesh_layouts(8, TINY)
    assert [c.name for c in a] == [c.name for c in b]


def test_resolve_block_infers_exactly_one_axis():
    assert resolve_block({"dp": 2, "fsdp": -1}, 8)["fsdp"] == 4
    assert resolve_block(None, 8) == {"dp": 8, "fsdp": 1, "tp": 1, "sp": 1}
    with pytest.raises(ValueError, match="at most one"):
        resolve_block({"dp": -1, "fsdp": -1}, 8)
    with pytest.raises(ValueError, match="does not divide"):
        resolve_block({"dp": 3, "fsdp": -1}, 8)
    with pytest.raises(ValueError, match="!="):
        resolve_block({"dp": 2, "fsdp": 2}, 8)  # 4 != 8, nothing inferred


def test_space_hash_deterministic_and_sensitive():
    layouts = enumerate_mesh_layouts(8, TINY)
    comms = enumerate_comm_variants()
    servings = enumerate_serving_buckets(TINY)
    h1 = space_hash(8, TINY, layouts, comms, [{"mode": "off"}], servings)
    h2 = space_hash(8, TINY, layouts, comms, [{"mode": "off"}], servings)
    assert h1 == h2 and len(h1) == 16
    # any perturbation of the space must change the fingerprint
    h3 = space_hash(8, TINY, layouts[:-1], comms, [{"mode": "off"}], servings)
    h4 = space_hash(8, ModelSpec(n_layer=3), layouts, comms,
                    [{"mode": "off"}], servings)
    assert h1 != h3 and h1 != h4


def test_comm_variant_admissibility():
    cands = enumerate_comm_variants(modes=("fp32", "int8"),
                                    bucket_mbs=(1.0,), include_none=True)
    assert [c.name for c in cands] == ["psum_fp32", "fp32_b1mb", "int8_b1mb"]
    assert cands[0].block is None
    with pytest.raises(ValueError, match="unknown comm mode"):
        enumerate_comm_variants(modes=("fp7",))


def test_serving_buckets_double_from_min_pool():
    cands = enumerate_serving_buckets(TINY, num_slots=8, max_seq_len=64,
                                      block_sizes=(16,), pool_doublings=2)
    blocks = [c.block["num_blocks"] for c in cands]
    assert blocks == [33, 66, 132]  # 8*(64/16)+1 doubled twice
    # pool bytes follow serving/'s own formula exactly
    sc_bytes = cands[0].kv_pool_bytes
    assert sc_bytes == 33 * 16 * 2 * TINY.n_layer * TINY.kv_heads * \
        TINY.head_dim * TINY.dtype_bytes


# ------------------------------------------------------------------ #
# cost model: infeasible reported, never dropped
# ------------------------------------------------------------------ #


def test_hbm_infeasible_candidates_reported_with_reason():
    # 1 KiB "HBM": every serving pool overflows, none may vanish
    budget = platform_budget(hbm_gb=1.0 / (1 << 20))
    cands = enumerate_serving_buckets(TINY, pool_doublings=2)
    prices = [price_serving(c, TINY, budget) for c in cands]
    ranked, pruned = rank_candidates(prices)
    assert ranked == []
    assert len(pruned) == len(cands)  # reported, not dropped
    for p in pruned:
        assert not p.feasible
        assert "HBM" in p.reason and "exceeds" in p.reason


def test_serving_feasible_prefers_bigger_pool():
    budget = platform_budget()  # cpu default: 1 GiB, tiny model fits
    cands = enumerate_serving_buckets(TINY, num_slots=8, max_seq_len=64,
                                      block_sizes=(16,), pool_doublings=2)
    ranked, pruned = rank_candidates(
        [price_serving(c, TINY, budget) for c in cands])
    assert pruned == []
    # same bucket grid => waste ties; the bigger pool must win the tie
    assert ranked[0].detail["serving"]["num_blocks"] == 132


def test_serving_spec_variants_enumerate_and_price():
    """draft_ks adds speculative candidates alongside each plain one:
    the block carries the sub-config, the name says so, and the drafter
    KV pool + drafter params are priced into the HBM need."""
    # an 8-layer target with a 1-layer drafter: the regime speculation
    # is FOR (with TINY's 2 layers a half-depth drafter never pays,
    # and the cost model correctly says so)
    deep = ModelSpec(n_layer=8)
    cands = enumerate_serving_buckets(deep, num_slots=8, max_seq_len=64,
                                      block_sizes=(16,),
                                      pool_doublings=0, draft_ks=(0, 4),
                                      drafter_layers=1)
    assert len(cands) == 2
    plain, spec = cands
    assert "speculative" not in plain.block
    assert spec.block["speculative"] == {"draft_k": 4,
                                         "drafter": {"n_layer": 1}}
    assert spec.name.endswith("_spec4")
    # drafter pool rides the same bytes formula, layers = n_layer + 1
    assert spec.kv_pool_bytes == plain.kv_pool_bytes * \
        (deep.n_layer + 1) / deep.n_layer

    budget = platform_budget()
    p_plain = price_serving(plain, deep, budget, accept_rate=0.7)
    p_spec = price_serving(spec, deep, budget, accept_rate=0.7)
    assert p_spec.detail["drafter_param_bytes"] > 0
    assert p_plain.components["decode_cost"] == 1.0
    # a decent drafter at 0.7 acceptance buys back more decode steps
    # than its own rounds cost...
    assert p_spec.components["spec_speedup"] > 1.0
    assert p_spec.predicted_step_s < p_plain.predicted_step_s
    # ...and a drafter that never lands is pure overhead: the cost
    # model must NOT recommend speculation at zero acceptance
    p_cold = price_serving(spec, deep, budget, accept_rate=0.0)
    assert p_cold.components["spec_speedup"] < 1.0
    assert p_cold.predicted_step_s > p_plain.predicted_step_s


def test_rank_candidates_rejects_unreasoned_pruning():
    from deeperspeed_tpu.autotune.costmodel import CandidatePrice
    bogus = CandidatePrice(name="x", kind="layout", feasible=False, reason="")
    with pytest.raises(AssertionError):
        rank_candidates([bogus])


def test_effective_micro_holds_global_tokens_constant():
    layouts = {c.name: c for c in enumerate_mesh_layouts(8, TINY)}
    for name, c in layouts.items():
        rows = effective_micro(c, 8, micro=2) * c.dp_size
        assert rows == 16, f"{name}: global rows {rows} != 16"


# ------------------------------------------------------------------ #
# wire model
# ------------------------------------------------------------------ #


def _plan(n_buckets=2, padded=4096):
    buckets = tuple(
        Bucket(index=i, leaf_ids=(i,), shapes=((padded,),), offsets=(0,),
               length=padded, padded=padded)
        for i in range(n_buckets))
    return BucketPlan(buckets=buckets, n_leaves=n_buckets,
                      total_elements=n_buckets * padded, pad_to=1)


def test_wiremodel_mode_ordering():
    plan, world = _plan(), 8
    by_mode = {
        m: wiremodel.plan_wire_bytes(plan, CommConfig.from_dict({"mode": m}),
                                     world)
        for m in ("int8", "bf16", "fp32")
    }
    assert by_mode["int8"] < by_mode["bf16"] < by_mode["fp32"]
    # fp32 two-phase: 64 bits/elem * ring factor
    expect = int(2 * 4096 * 8 * 2 * (world - 1) / world)
    assert by_mode["fp32"] == expect


def test_wiremodel_launches_and_degenerate_world():
    plan = _plan(n_buckets=5)
    assert wiremodel.plan_collective_launches(plan, 8) == 10
    assert wiremodel.plan_collective_launches(plan, 1) == 0
    assert wiremodel.plan_wire_bytes(
        plan, CommConfig.from_dict({"mode": "fp32"}), 1) == 0
    s = wiremodel.wire_summary(None, None, 8, 1000)
    assert s["mode"] == "psum_fp32" and s["vs_dense_fp32"] == 1.0


# ------------------------------------------------------------------ #
# watchdog-safe AOT capture (the regression the fix closes)
# ------------------------------------------------------------------ #


def test_aot_capture_sweep_never_trips_live_watchdog():
    """Sweep 10 candidate entry points through the sandboxed capture while
    a strict watchdog guards a live, warmed training step: zero firings,
    every jit cache byte-identical, and no perf events leaked into the
    live tracer."""
    world = jax.device_count()
    layout = enumerate_mesh_layouts(world, TINY)[0]
    engine = build_candidate_engine(TINY, layout, world)

    # a real training process around the capture: live tracer + strict
    # watchdog on the engine's actual jitted step
    tracer = Tracer()
    prev = set_tracer(tracer)
    try:
        rows = (engine.train_micro_batch_size_per_gpu()
                * engine.gradient_accumulation_steps()
                * engine.data_parallel_size)
        batch = jnp.zeros((rows, TINY.seq + 1), dtype=jnp.int32)
        engine.train_batch(batch)  # warm the real step
        live_fn = engine._train_batch_fn()
        wd = RecompileWatchdog(mode="strict")
        wd.watch("engine/train_step", live_fn)
        wd.mark_warm()
        live_cache_before = _cache_size(live_fn)
        assert live_cache_before and live_cache_before > 0

        idx = sandboxed_cost_index()
        candidates = [
            (f"cand/{i}", jax.jit(lambda x, k=i: (x * (k + 1)).sum()),
             (jax.ShapeDtypeStruct((64, 64), jnp.float32),))
            for i in range(10)
        ]
        for name, fn, avals in candidates:
            before = _cache_size(fn)
            rec = aot_capture(name, fn, avals, index=idx)
            assert rec.error is None and rec.flops >= 0
            assert _cache_size(fn) == before  # AOT never populated it
            assert wd.observe() == []  # strict mode would raise anyway

        assert wd.fired == []
        assert _cache_size(live_fn) == live_cache_before
        # emit=False: the sandbox stamped nothing into the live tracer
        assert [e for e in tracer.events()
                if e.get("name") == "perf/compiled"] == []
    finally:
        set_tracer(prev)


def test_aot_capture_raises_on_cache_growth():
    """A capture path that executes the candidate (growing its cache)
    must raise — that is the bug that fires live recompile watchdogs."""

    class Leaky:
        """observe() impostor that CALLS the function."""

        def observe(self, name, fn, args, kwargs=None):
            fn(jnp.ones((4, 4)))
            return None

    fn = jax.jit(lambda x: x.sum())
    with pytest.raises(RuntimeError, match="grew the candidate's jit cache"):
        aot_capture("leak", fn, (jax.ShapeDtypeStruct((4, 4), jnp.float32),),
                    index=Leaky())


def test_price_layout_full_path_is_feasible_and_clean():
    world = jax.device_count()
    layout = enumerate_mesh_layouts(world, TINY)[0]
    price, engine = price_layout(layout, TINY, world, platform_budget(),
                                 index=sandboxed_cost_index())
    assert engine is None  # dropped unless keep_engine=True
    assert price.feasible, price.reason
    assert price.flops > 0 and price.predicted_step_s > 0
    assert set(price.components) == {"compute_s", "memory_s", "wire_s",
                                     "launch_s"}


def test_price_layout_engine_failure_reported_not_raised():
    bad = ModelSpec(n_head=3)  # 64 % 3 != 0: model construction must fail
    world = jax.device_count()
    layout = enumerate_mesh_layouts(world, TINY)[0]
    price, engine = price_layout(layout, bad, world, platform_budget())
    assert engine is None and not price.feasible
    assert "engine construction failed" in price.reason


# ------------------------------------------------------------------ #
# provenance: signing, tampering, analysis gate, config round-trip
# ------------------------------------------------------------------ #


def _signed_config():
    cfg = {
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "train_batch_size": 16,  # 2 * 1 * world_size(8)
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "mesh": {"dp": 2, "fsdp": 4},
        "zero_optimization": {"stage": 2},
        "kernels": {"mode": "auto"},
        "comm": {"mode": "int8", "bucket_mb": 25.0},
    }
    cfg["provenance"] = make_provenance(
        cfg, space_hash="cafe0123beef4567", platform="cpu", devices=8,
        predicted_step_s=0.002, rev="deadbee")
    return cfg


def test_provenance_verifies_then_catches_hand_edit():
    cfg = _signed_config()
    ok, why = verify_provenance(cfg)
    assert ok, why
    # editing a NON-tuned key is the user's right: hash unaffected
    cfg["train_micro_batch_size_per_gpu"] = 64
    assert verify_provenance(cfg)[0]
    # editing a tuned knob breaks the signature
    cfg["zero_optimization"]["stage"] = 3
    ok, why = verify_provenance(cfg)
    assert not ok and "knob_hash mismatch" in why
    # no claim, no check
    assert verify_provenance({"mesh": {"dp": 8}})[0]
    # half-deleted record = malformed, not trivially ok
    ok, why = verify_provenance({"provenance": {"tool": "x"}})
    assert not ok and "missing keys" in why


def test_knob_fingerprint_ignores_untuned_keys():
    a = {"mesh": {"dp": 8}, "optimizer": {"type": "Adam"}}
    b = {"mesh": {"dp": 8}, "optimizer": {"type": "SGD"},
         "steps_per_print": 5}
    assert knob_fingerprint(a) == knob_fingerprint(b)
    assert knob_fingerprint(a) != knob_fingerprint({"mesh": {"dp": 4}})


def test_analysis_gate_flags_planted_hand_edit(tmp_path):
    cfgdir = tmp_path / "configs"
    cfgdir.mkdir()
    good = _signed_config()
    (cfgdir / "good.json").write_text(json.dumps(good))
    tampered = json.loads(json.dumps(good))
    tampered["mesh"]["dp"] = 8  # the planted hand-edit
    (cfgdir / "tampered.json").write_text(json.dumps(tampered))
    (cfgdir / "plain.json").write_text(json.dumps({"mesh": {"dp": 8}}))
    findings = check_config_provenance(str(tmp_path))
    assert [f.path for f in findings] == [os.path.join("configs",
                                                       "tampered.json")]
    assert findings[0].severity == "error"
    assert "knob_hash mismatch" in findings[0].message


def test_signed_config_roundtrips_runtime_validation():
    cfg = _signed_config()
    before = json.dumps(cfg, sort_keys=True)
    tc = TrainingConfig(cfg, world_size=8)
    assert json.dumps(cfg, sort_keys=True) == before  # parse mutates nothing
    assert tc.provenance_params["knob_hash"] == knob_fingerprint(cfg)
    assert tc.autotune_params is None and not tc.autotune_enabled


def test_config_autotune_block_declared():
    base = {"train_batch_size": 8, "optimizer": {"type": "Adam"}}
    tc = TrainingConfig({**base, "autotune": {"enabled": True}})
    assert tc.autotune_enabled and tc.autotune_params == {"enabled": True}
    with pytest.raises(ConfigError, match='"autotune" must be a dict'):
        TrainingConfig({**base, "autotune": True})
    with pytest.raises(ConfigError, match="missing keys"):
        TrainingConfig({**base, "provenance": {"tool": "x"}})


def test_repo_shipped_autotuned_config_verifies():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "configs", "gpt_125m_autotuned.json")
    with open(path) as fh:
        cfg = json.load(fh)
    ok, why = verify_provenance(cfg)
    assert ok, why
    assert cfg["provenance"]["tool"] == "deeperspeed_tpu.autotune"
    assert check_config_provenance(root) == []


# ------------------------------------------------------------------ #
# ranking math
# ------------------------------------------------------------------ #


def test_spearman_rank_correlation():
    assert spearman([1, 2, 3, 4], [10, 20, 30, 40]) == pytest.approx(1.0)
    assert spearman([1, 2, 3, 4], [40, 30, 20, 10]) == pytest.approx(-1.0)
    # monotone but nonlinear still ranks perfectly
    assert spearman([1, 2, 3, 4], [1, 8, 27, 64]) == pytest.approx(1.0)
    assert spearman([1, 2], []) == 0.0  # length mismatch -> no signal
    assert spearman([1, 1, 1], [2, 3, 4]) == 0.0  # zero variance


# ------------------------------------------------------------------ #
# CLI end-to-end (subprocess: needs its own 8-device process)
# ------------------------------------------------------------------ #


@pytest.mark.slow
def test_cli_quick_search_end_to_end(tmp_path):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = tmp_path / "tuned.json"
    report = tmp_path / "report.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "deeperspeed_tpu.autotune", "--devices", "8",
         "--quick", "--no-confirm", "--out", str(out),
         "--report", str(report)],
        env=env, capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, r.stdout + r.stderr
    cfg = json.loads(out.read_text())
    ok, why = verify_provenance(cfg)
    assert ok, why
    rep = json.loads(report.read_text())
    assert rep["best"]["name"]
    # every pruned candidate in the report states its reason
    for p in rep["pruned"]:
        assert p.get("reason")
