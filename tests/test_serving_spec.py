"""Drafter-backed speculative decoding in the serving engine
(serving/spec/): greedy output must be BIT-IDENTICAL to plain decode —
cold, over a prefix-cache hit, and under chunked prefill — and sampled
output token-identical via the matched-key verify contract; exactly
three compiled decode-path programs; drafter-pool backpressure falls
back to plain decode instead of failing; drafter weight swaps resync
lazily mid-stream; a spec-on fleet failover-retries to the same tokens
a spec-off engine emits; and the spec/* trace instants feed the request
ledger's token-exact accounting."""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeperspeed_tpu.models.gpt import GPTConfig, make_gpt
from deeperspeed_tpu.monitor.reqledger import (
    build_index,
    build_ledger,
    request_cost,
)
from deeperspeed_tpu.monitor.validate import validate_events
from deeperspeed_tpu.serving import (
    FleetRouter,
    RouterConfig,
    ServingConfig,
    ServingEngine,
    build_thread_fleet,
)
from deeperspeed_tpu.serving.config import SpeculativeConfig
from deeperspeed_tpu.serving.spec.runtime import truncated_drafter


@pytest.fixture(scope="module", autouse=True)
def _compile_cache(tmp_path_factory):
    """Same trick as test_fleet.py: every engine here compiles the same
    tiny model, so the persistent cache keeps the plain-vs-spec engine
    pairs (and the fleet test) affordable in the fast tier."""
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
    return cfg, init_fn(jax.random.PRNGKey(0))


_SPEC = {"draft_k": 3, "drafter": {"n_layer": 1}}


def _engine(cfg, params, spec=_SPEC, **kw):
    d = dict(num_slots=2, block_size=4, num_blocks=64, max_seq_len=128,
             prefill_buckets=(4, 8, 16, 32, 64, 128))
    d.update(kw)
    if spec is not None:
        d["speculative"] = dict(spec)
    return ServingEngine(cfg, params, ServingConfig(**d))


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, 97, (n,)).tolist()


# ------------------------------------------------------------------ #
# config plumbing
# ------------------------------------------------------------------ #


def test_speculative_config_block():
    scfg = ServingConfig.from_dict(
        {"speculative": {"draft_k": 2, "drafter": {"n_layer": 1}}})
    assert isinstance(scfg.speculative, SpeculativeConfig)
    assert scfg.speculative.draft_k == 2
    assert ServingConfig.from_dict({}).speculative is None
    with pytest.raises(ValueError, match="unknown speculative"):
        ServingConfig.from_dict({"speculative": {"k_draft": 2}})
    with pytest.raises(ValueError, match="draft_k"):
        SpeculativeConfig(draft_k=0)


def test_truncated_drafter_views_target_params(model):
    cfg, params = model
    dcfg, dparams = truncated_drafter(cfg, params, 1)
    assert dcfg.n_layer == 1
    # a view, not a copy: the drafter rides the target's arrays
    leaf = jax.tree.leaves(dparams["layers"])[0]
    assert leaf.shape[0] == 1
    with pytest.raises(ValueError, match="n_layer"):
        truncated_drafter(cfg, params, 5)


def test_plain_engine_without_spec_block_is_untouched(model):
    cfg, params = model
    eng = _engine(cfg, params, spec=None)
    assert eng._spec is None
    assert eng.draft_compile_count == -1
    with pytest.raises(RuntimeError, match="not enabled"):
        eng.set_drafter_params({})


# ------------------------------------------------------------------ #
# determinism: greedy spec == plain greedy, every admission path
# ------------------------------------------------------------------ #


@pytest.mark.parametrize("drafter_layers", [1, 2],
                         ids=["truncated", "target-as-drafter"])
def test_greedy_spec_identical_to_plain_cold(model, drafter_layers):
    """Depth 2 is the target itself as its own drafter: there drafts must
    LAND, not only be proposed."""
    cfg, params = model
    prompts = [_prompt(9, 1), _prompt(17, 2), _prompt(30, 3)]

    plain = _engine(cfg, params, spec=None)
    refs = [plain.submit(p, max_new_tokens=20) for p in prompts]
    ref_out = plain.run()

    eng = _engine(cfg, params, spec={"draft_k": 3, "drafter":
                                     {"n_layer": drafter_layers}})
    rids = [eng.submit(p, max_new_tokens=20) for p in prompts]
    out = eng.run()
    for r, rr in zip(rids, refs):
        assert out[r] == ref_out[rr]
    assert eng.metrics.spec_rounds > 0
    assert eng.metrics.spec_drafted > 0
    if drafter_layers == cfg.n_layer:
        assert eng.metrics.spec_accepted > 0


def test_greedy_spec_cache_hit_identical_to_miss(model):
    """A spec request admitted over shared radix blocks (drafter synced
    from its own prefix index) must emit the same greedy stream as a
    cold plain decode."""
    cfg, params = model
    sys_p = _prompt(14, 7)
    p1 = sys_p + _prompt(5, 8)
    p2 = sys_p + _prompt(9, 9)

    cold = _engine(cfg, params, spec=None)
    r1 = cold.submit(p1, max_new_tokens=12)
    r2 = cold.submit(p2, max_new_tokens=12)
    ref = cold.run()

    eng = _engine(cfg, params, prefix_caching=True)
    h1 = eng.submit(p1, max_new_tokens=12)
    eng.run()                                   # indexes p1
    h2 = eng.submit(p2, max_new_tokens=12)      # hits the shared prefix
    out = eng.run()
    assert eng.metrics.reuse_hits == 1
    assert out[h2] == ref[r2]
    assert eng.get(h1).output == ref[r1]
    assert eng.metrics.spec_rounds > 0


def test_greedy_spec_chunked_prefill_identical_to_unchunked(model):
    cfg, params = model
    prompts = [_prompt(37, 2), _prompt(18, 3), _prompt(61, 4)]

    plain = _engine(cfg, params, spec=None)
    refs = [plain.submit(p, max_new_tokens=10) for p in prompts]
    ref_out = plain.run()

    eng = _engine(cfg, params, prefill_chunk=16, prefill_token_budget=32)
    rids = [eng.submit(p, max_new_tokens=10) for p in prompts]
    out = eng.run()
    for r, rr in zip(rids, refs):
        assert out[r] == ref_out[rr]
    assert eng.metrics.prefill_chunks > 0
    assert eng.metrics.spec_rounds > 0


def test_sampled_spec_identical_to_plain(model):
    """The matched-key contract end to end: drafter and target draw
    with the same (seed, output-index) keys, so the sampled stream is
    the one plain per-token decode emits — for any drafter quality."""
    cfg, params = model
    prompts = [_prompt(8, 11), _prompt(21, 12), _prompt(13, 13)]
    temps = [0.7, 1.0, 0.9]
    rids = [f"s{i}" for i in range(3)]

    plain = _engine(cfg, params, spec=None)
    for p, t, rid in zip(prompts, temps, rids):
        plain.submit(p, max_new_tokens=18, temperature=t, request_id=rid)
    ref = plain.run()

    eng = _engine(cfg, params)
    for p, t, rid in zip(prompts, temps, rids):
        eng.submit(p, max_new_tokens=18, temperature=t, request_id=rid)
    out = eng.run()
    for rid in rids:
        assert out[rid] == ref[rid], rid
    assert eng.metrics.spec_rounds > 0
    # sampling accepts less than greedy-vs-self but must accept SOME
    # (drafter layer 0 is the target's own first layer)
    assert eng.metrics.spec_accepted >= 0


def test_spec_respects_eos_mid_draft(model):
    """An EOS inside the accepted draft window truncates the emission
    exactly where plain decode would have stopped."""
    cfg, params = model
    prompt = _prompt(10, 21)

    plain = _engine(cfg, params, spec=None, eos_token_id=3)
    r = plain.submit(prompt, max_new_tokens=40)
    ref = plain.run()[r]

    eng = _engine(cfg, params, eos_token_id=3)
    h = eng.submit(prompt, max_new_tokens=40)
    out = eng.run()[h]
    assert out == ref
    assert eng.get(h).finish_reason == plain.get(r).finish_reason


# ------------------------------------------------------------------ #
# three compiled programs, fallback eligibility, backpressure
# ------------------------------------------------------------------ #


def test_exactly_three_compiled_decode_programs(model):
    """Mixed traffic — greedy + sampled, short + long, early-finishing
    lanes — must hold the decode path at one compile per program."""
    cfg, params = model
    eng = _engine(cfg, params, num_slots=4)
    eng.submit(_prompt(6, 30), max_new_tokens=24)
    eng.submit(_prompt(40, 31), max_new_tokens=6)
    eng.submit(_prompt(12, 32), max_new_tokens=16, temperature=0.8)
    eng.submit(_prompt(25, 33), max_new_tokens=1)    # never speculates
    eng.run()
    assert eng.decode_compile_count <= 1      # fallback program
    assert eng.draft_compile_count == 1
    assert eng.verify_compile_count == 1
    assert eng.metrics.spec_fallback_lanes >= 1


def test_single_token_requests_never_speculate(model):
    cfg, params = model
    prompt = _prompt(11, 40)
    plain = _engine(cfg, params, spec=None)
    r = plain.submit(prompt, max_new_tokens=1)
    ref = plain.run()[r]
    eng = _engine(cfg, params)
    h = eng.submit(prompt, max_new_tokens=1)
    out = eng.run()[h]
    assert out == ref
    assert eng.metrics.spec_drafted == 0      # all lanes fell back


def test_drafter_pool_backpressure_falls_back_not_fails(model):
    """A drafter pool too small to mirror the context: the slot decodes
    on the plain program every round — same tokens, no crash, and the
    drafter pool never leaks into the target's accounting."""
    cfg, params = model
    prompt = _prompt(30, 41)                   # needs 8 drafter blocks

    plain = _engine(cfg, params, spec=None)
    r = plain.submit(prompt, max_new_tokens=16)
    ref = plain.run()[r]

    spec = dict(_SPEC, num_blocks=3)           # 2 usable blocks: 8 rows
    eng = _engine(cfg, params, spec=spec)
    h = eng.submit(prompt, max_new_tokens=16)
    out = eng.run()[h]
    assert out == ref
    assert eng.metrics.spec_drafted == 0
    assert eng.metrics.spec_fallback_lanes > 0
    assert eng._spec.kv.allocator.num_allocated == 0


def test_drafter_swap_mid_stream_resyncs_and_stays_identical(model):
    """set_drafter_params mid-decode (the lifecycle (target, drafter)
    rollout): slot mirrors drop, resync lazily, and the greedy stream
    is untouched — the verify contract holds for ANY drafter weights."""
    cfg, params = model
    prompts = [_prompt(9, 50), _prompt(22, 51)]

    plain = _engine(cfg, params, spec=None)
    refs = [plain.submit(p, max_new_tokens=24) for p in prompts]
    ref_out = plain.run()

    eng = _engine(cfg, params)
    rids = [eng.submit(p, max_new_tokens=24) for p in prompts]
    for _ in range(4):
        if eng.has_work():
            eng.step()
    prefills_before = eng.metrics.spec_drafter_prefills
    alt_init, _, _, _ = make_gpt(cfg)
    alt = alt_init(jax.random.PRNGKey(9))
    eng.set_drafter_params(truncated_drafter(cfg, alt, 1)[1])
    out = eng.run()
    for r, rr in zip(rids, refs):
        assert out[r] == ref_out[rr]
    # the swap dropped every slot mirror -> at least one resync prefill
    assert eng.metrics.spec_drafter_prefills > prefills_before


# ------------------------------------------------------------------ #
# fleet: failover retry + mixed spec-on/spec-off token identity
# ------------------------------------------------------------------ #


def _spec_factory(cfg, params):
    scfg = ServingConfig(num_slots=4, block_size=8, num_blocks=64,
                         max_seq_len=128, max_new_tokens=64,
                         prefill_buckets=(16, 128),
                         speculative=dict(_SPEC))

    def factory():
        eng = ServingEngine(cfg, params, scfg)
        eng.submit([1, 2, 3], max_new_tokens=8, request_id="_warm")
        eng.submit([4, 5, 6], max_new_tokens=8, temperature=0.5,
                   request_id="_warm2")
        eng.run()
        return eng

    return factory


@pytest.mark.slow
def test_spec_fleet_kill_retry_token_identity(model):
    """Kill a spec-decoding thread replica mid-stream: retried requests
    — greedy AND sampled — reproduce the tokens a SPEC-OFF single
    engine emits. One assertion, two contracts: failover retries are
    token-exact, and spec-on/spec-off replicas are interchangeable."""
    cfg, params = model
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 97, rng.integers(4, 12)).tolist()
               for _ in range(6)]
    news = [40] * 6
    temps = [0.0, 0.7] * 3
    rids = [f"q{i}" for i in range(6)]

    plain = ServingEngine(cfg, params,
                          ServingConfig(num_slots=4, block_size=8,
                                        num_blocks=64, max_seq_len=128,
                                        max_new_tokens=64,
                                        prefill_buckets=(16, 128)))
    for p, n, t, rid in zip(prompts, news, temps, rids):
        plain.submit(p, max_new_tokens=n, temperature=t, request_id=rid)
    plain.run()
    ref = {rid: plain.get(rid).output for rid in rids}

    fleet = build_thread_fleet(2, _spec_factory(cfg, params))
    router = FleetRouter(fleet, RouterConfig(
        num_replicas=2, max_queue_depth=64, retry_max=3,
        retry_backoff_base_s=0.01, retry_backoff_max_s=0.1,
        heartbeat_timeout_s=60.0, progress_timeout_s=60.0,
        poll_interval_s=0.002))
    try:
        for p, n, t, rid in zip(prompts, news, temps, rids):
            router.submit(p, max_new_tokens=n, temperature=t,
                          request_id=rid)
        router.step()                       # dispatch
        time.sleep(0.05)                    # a few rounds land
        fleet[0].kill()
        outcomes = router.run_until_idle(timeout_s=120)
        assert sorted(outcomes) == sorted(rids)   # zero loss
        for rid in rids:
            assert router.result(rid).tokens == ref[rid], rid
        # the surviving replica really speculated
        assert any(r.spec_stats.get("rounds", 0) > 0 for r in fleet)
    finally:
        router.shutdown()


# ------------------------------------------------------------------ #
# observability: strict schemas + ledger token exactness
# ------------------------------------------------------------------ #


def _inst(name, ts, pid=1, **args):
    return {"name": name, "ph": "i", "ts": float(ts), "pid": pid,
            "tid": 0, "s": "p", "args": args}


def _span(name, ts, dur, pid=1, **args):
    return {"name": name, "ph": "X", "ts": float(ts), "dur": float(dur),
            "pid": pid, "tid": 0, "args": args}


def test_spec_instants_strict_schemas():
    good = [
        _inst("spec/draft", 10, n_active=2, k=3, dur_us=120.0),
        _inst("spec/verify", 20, n_active=2, k=3, dur_us=340.0),
        _inst("spec/accept", 30, rid="A", accepted=2, k=3, emitted=3),
    ]
    assert validate_events(good) == []
    bad = [_inst("spec/accept", 30, rid="A", accepted=2, k=3)]
    errors = validate_events(bad)
    assert len(errors) == 1 and "emitted" in errors[0]


def _spec_round_events():
    """One request: prefill emits 1 token, then one spec round emits 3
    (2 accepted drafts + bonus) inside a single decode span — finish
    reports 4 total."""
    return [
        _inst("req/submit", 0, rid="A", prompt_len=8),
        _inst("serving/admit", 1000, rid="A", slot=0, ctx_len=8,
              admissions=1),
        _span("serving/prefill", 1000, 2000, rid="A", ctx_len=8),
        _span("serving/decode", 3000, 900, rids="A", n_active=1),
        _inst("spec/draft", 3100, n_active=1, k=3, dur_us=300.0),
        _inst("spec/verify", 3500, n_active=1, k=3, dur_us=400.0),
        _inst("spec/accept", 3900, rid="A", accepted=2, k=3, emitted=3),
        _inst("serving/finish", 4000, rid="A", reason="length",
              tokens=4, kv_block_s=0.01, admissions=1),
    ]


def test_ledger_counts_spec_emission_exactly():
    """One decode span emits `emitted` tokens, not 1: request_cost must
    match the finish event's token count bit-for-bit."""
    idx = build_index(_spec_round_events())
    assert len(idx.spec_drafts) == 1 and len(idx.spec_verifies) == 1
    cost = request_cost(idx, idx.timelines["A"])
    assert cost["tokens_final"] == 4
    assert cost["tokens_final"] == cost["finish_tokens_reported"]
    assert cost["spec_rounds"] == 1
    assert cost["spec_accept_rate"] == pytest.approx(2 / 3)

    report = build_ledger(_spec_round_events())
    sp = report["speculative"]
    assert sp["rounds"] == 1
    assert sp["drafted"] == 3 and sp["accepted"] == 2
    assert sp["accept_rate"] == pytest.approx(2 / 3)
    assert sp["per_rid"]["A"]["rounds"] == 1
    assert sp["draft_ms"] == pytest.approx(0.3)
    assert sp["verify_ms"] == pytest.approx(0.4)


def test_engine_trace_events_validate_strict(model, tmp_path):
    """A real spec engine run under the monitor: every emitted event —
    including the spec/* instants — passes the strict validator."""
    from deeperspeed_tpu.monitor import shutdown_monitor
    from deeperspeed_tpu.monitor.validate import validate_file

    cfg, params = model
    trace = str(tmp_path / "spec_trace.json")
    eng = ServingEngine(
        cfg, params,
        ServingConfig(num_slots=2, block_size=4, num_blocks=64,
                      max_seq_len=128,
                      prefill_buckets=(4, 8, 16, 32, 64, 128),
                      speculative=dict(_SPEC)),
        monitor_config={"trace_path": trace, "trace_enabled": True,
                        "watchdog": "warn"})
    try:
        eng.submit(_prompt(10, 60), max_new_tokens=12)
        eng.submit(_prompt(18, 61), max_new_tokens=12, temperature=0.7)
        eng.run()
    finally:
        shutdown_monitor(save=True)
    assert validate_file(trace) == []
    ledger = build_ledger(trace)
    assert ledger["speculative"]["rounds"] > 0
