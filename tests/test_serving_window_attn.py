"""A stack of ``window_attn`` and ``full_attn`` layers whose feed-forward
is routed experts (Mellum 2's: three layers that keep the last ``window``
keys in a ring of pages, then one that keeps every key, a period; two
page rules, two pools, one table) through ``ServingEngine``, at small
widths on the CPU: 8 layers in the published pattern, hidden 64, 4 heads
over 2 key heads of 16, window 8, pages of 4 rows (a ring is 2 pages), 8
experts 2 a token, ``prefill_chunk`` 8, float32. What the engine serves
(chunked prefill, then decode through the ring and the pages of every
key) is compared with the plain reference ``benchmark/refs/mellum.py`` on
seeded weights, and the parts with each other."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest as mf
from benchmark.adapters import mellum as adapter
from benchmark.refs import init as rinit
from benchmark.refs import mellum as ref
from deeperspeed_tpu.models import mixers, moe
from deeperspeed_tpu.models.gpt import (GPTConfig, GroupedAttnConfig,
                                        RopeScaling, make_gpt)
from deeperspeed_tpu.serving import ServingConfig, ServingEngine
from deeperspeed_tpu.serving.config import PageRule
from deeperspeed_tpu.serving.engine import prefill_chunk_for
from deeperspeed_tpu.serving.kv_cache import (BlockAllocator, PagedKVCache,
                                              page_rule_for, pool_bytes)
from deeperspeed_tpu.serving.scheduler import Request, Scheduler

TOY = mf.load_json(os.path.join(mf.ROOT, "tests", "bench", "data", "configs",
                                "toy-mellum.json"))
SERVING = {"num_slots": 3, "block_size": 4, "num_blocks": 73,
           "max_seq_len": 96, "prefill_chunk": 8,
           "prefill_token_budget": 8, "max_new_tokens": 32}
VOCAB, W, BS = TOY["vocab_size"], TOY["sliding_window"], 4


@pytest.fixture(scope="module")
def params():
    return rinit.init_tree(7, ref.leaf_specs(TOY), jnp.float32)


@pytest.fixture(scope="module")
def reference():
    return ref.Forward(ref.make(TOY))


def engine_for(params, **serving):
    return adapter.serving_engine(TOY, params, {**SERVING, **serving})


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, n).tolist() for n in lengths]


def served(params, lengths, new=10, seed=0, **serving):
    eng = engine_for(params, **serving)
    ps = prompts(lengths, seed)
    for i, p in enumerate(ps):
        eng.submit(p, max_new_tokens=new, request_id=f"r{i}")
    with jax.default_matmul_precision("highest"):
        out = eng.run()
    return eng, ps, [out[f"r{i}"] for i in range(len(ps))]


def gaps(reference, params, p, o):
    """How far each served token lies under the reference's best, and the
    logits' spread."""
    logits = np.asarray(reference.logits(params, p + o, len(p)))
    return logits.max(-1) - logits[np.arange(len(o)), o], logits.std()


# ------------------------------------------------------------------ #
# the engine against the plain reference
# ------------------------------------------------------------------ #


@pytest.mark.parametrize("lengths,new", [
    # window 8, chunk 8: 21 = 2 chunks and 5 (ragged: the ring keeps the
    # rows beyond the last token), 5 and 3 lie inside the window
    pytest.param((21, 5, 3), 12, id="ragged_last_chunks_and_prompts_inside_the_window"),
    # prompts that end on, one before and one after the ring's wrap
    pytest.param((16, 15, 17), 12, id="chunk_boundaries_on_before_and_after_a_wrap"),
    # 50 + 40: the decode run wraps the ring five times
    pytest.param((50, 7), 40, id="a_decode_run_that_wraps_the_ring_five_times"),
    pytest.param((64, 8, 24), 9, id="whole_chunks_and_a_prompt_of_one_window")])
def test_prefill_chunks_and_decode_agree_with_the_reference(
        params, reference, lengths, new):
    """Chunked prefill, then decode through the ring and the pages of
    every key: every served (greedy) token is the reference's best at its
    position. Tolerance 1e-4 of the logits' spread: both sides are float32
    and differ in the order of their sums alone."""
    eng, ps, outs = served(params, lengths, new)
    for p, o in zip(ps, outs):
        gap, spread = gaps(reference, params, p, o)
        assert len(o) == new and gap.max() <= 1e-4 * spread, (len(p), gap.max())
    assert eng.decode_compile_count == 1
    assert eng._chunk_step._cache_size() == 1       # one lowering, every chunk
    assert eng.prefill_compile_count == 0           # no bucketed prefill
    assert [a.num_allocated for a in eng.kv.allocators] == [0, 0]


def chunked_logits(eng, p):
    """The chunk program driven by hand through a fresh slot's table: the
    last chunk's logits and the chunks' counts of their experts."""
    cfg, scfg, kv = eng.cfg, eng.scfg, eng.kv
    want = scfg.pages_by_role(len(p) + 1)
    table = np.zeros(scfg.blocks_per_slot, np.int32)
    table[:want[0]] = kv.allocators[0].alloc(want[0])
    table[scfg.table_widths[0]:scfg.table_widths[0] + want[1]] = \
        kv.allocators[1].alloc(want[1])
    Cp = prefill_chunk_for(cfg, scfg)
    counts = []
    for lo in range(0, len(p), Cp):
        toks = np.zeros((1, Cp), np.int32)
        n = min(Cp, len(p) - lo)
        toks[0, :n] = p[lo:lo + n]
        (logits, c), kv.k, kv.v, kv.kc, kv.state = eng._chunk_step(
            eng.params, kv.k, kv.v, kv.kc, kv.state, jnp.asarray(toks),
            jnp.asarray(table), np.int32(1), np.int32(lo), np.int32(n))
        counts.append(np.asarray(c))
    return np.asarray(logits), counts


@pytest.mark.parametrize("length", [
    pytest.param(3, id="inside_the_window"),
    pytest.param(8, id="the_windows_last_position"),
    pytest.param(9, id="one_past_the_window"),
    pytest.param(21, id="two_wraps_behind_a_ragged_chunk"),
    pytest.param(64, id="eight_whole_chunks")])
def test_first_token_logits_of_a_chunked_prompt(params, reference, length):
    """The chunk program's own logits at the prompt's last position against
    the reference's. atol 3e-5 on logits that spread 1.5: float32 sums in
    another order. A chunk routes its real tokens alone."""
    (p,) = prompts((length,), seed=3)
    got, counts = chunked_logits(engine_for(params), p)
    want = np.asarray(reference.logits(params, p + [0], len(p)))[0]
    assert got.shape == want.shape == (VOCAB,) and got.dtype == np.float32
    np.testing.assert_allclose(got, want, atol=3e-5)
    # 8 layers x 2 experts a REAL token: the padding is routed nowhere
    assert sum(int(c[1]) for c in counts) == 8 * 2 * length


def test_the_whole_forward_agrees_with_the_reference(params, reference):
    """mixers.forward (no cache) is the program's own statement of the
    model. All positions, all columns."""
    cfg = adapter.model_config(TOY)
    (p,) = prompts((100,), seed=5)
    got = mixers.forward(cfg, params, jnp.asarray([p], jnp.int32))[0]
    want = reference.logits(params, p + [0], 1)
    assert got.shape == (100, VOCAB) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


@pytest.mark.parametrize("control", ["nowindow", "noyarn", "rawgates",
                                     "noqknorm"])
def test_a_reference_that_leaves_a_part_out_is_far_from_the_program(
        params, reference, control):
    """The controls of the cell's check at the toy size: each puts logits
    far from the sound forward's; the one that forgot the window agrees
    with it while the sequence is inside one window."""
    (p,) = prompts((60,), seed=2)
    want = np.asarray(reference.logits(params, p + [0], 1))
    ctl = ref.Forward(ref.make(TOY, control=control))
    low = np.asarray(ctl.logits(params, p + [0], 1))
    assert np.abs(low - want).max() > 1000 * 3e-5
    if control == "nowindow":
        np.testing.assert_allclose(low[:W], want[:W], atol=3e-5)


def test_yarn_frequencies_at_the_published_numbers():
    """The full layers' 64 inverse frequencies as the published config
    states them: unchanged below pair 18, divided by 16 from pair 35 on, a
    straight line of the two between; cos and sin times 0.1 ln 16 + 1."""
    real = mf.Manifest().config("mellum2-12b-a2.5b")
    cfg = adapter.model_config(real)
    full, window = cfg.gqa.full_rope, cfg.gqa.window_rope
    f, base = full.inv_freq(128), window.inv_freq(128)
    want = 500000.0 ** (-2.0 * np.arange(64) / 128)
    np.testing.assert_allclose(base, want, rtol=1e-6)
    np.testing.assert_allclose(f[:18], want[:18], rtol=1e-6)
    np.testing.assert_allclose(f[35:], want[35:] / 16, rtol=1e-6)
    r = (np.arange(18, 35) - 18) / 17
    np.testing.assert_allclose(f[18:35], (1 - r) * want[18:35]
                               + r * want[18:35] / 16, rtol=1e-6)
    assert full.attention_factor == pytest.approx(0.1 * np.log(16) + 1)
    assert window.attention_factor == 1.0 and cfg.gqa.window == 1024
    # the reference computes them by itself and agrees
    rf, a = ref.inv_freq(real["rope_parameters"]["full_attention"], 128)
    np.testing.assert_allclose(rf, f, rtol=1e-6)
    assert a == full.attention_factor


# ------------------------------------------------------------------ #
# the experts: nothing dropped, idle lanes routed nowhere
# ------------------------------------------------------------------ #


def test_every_token_to_one_expert_drops_nothing(params, reference):
    """A router planted so that every token's largest gate is expert 5's
    and its second expert 2's: 60 tokens x 8 layers all on two experts (a
    capacity of 1.25 x the mean would keep 19 of each 60). The whole
    forward and the served tokens are still the reference's."""
    planted = jax.tree.map(lambda a: a, params)
    for kind in ("full_attn", "window_attn"):
        r = np.zeros(planted[kind]["mlp"]["router"].shape, np.float32)
        r[:, :, 5], r[:, :, 2] = 0.5, 0.45      # every token, whatever its m
        # m is RMS-normed and mostly positive nowhere: plant through ln2
        planted[kind] = {**planted[kind], "mlp": {
            **planted[kind]["mlp"], "router": jnp.asarray(
                np.abs(np.asarray(planted[kind]["mlp"]["router"])) * 0
                + r)},
            "ln2": jnp.abs(planted[kind]["ln2"])}
    cfg = adapter.model_config(TOY)
    (p,) = prompts((60,), seed=9)
    m = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (60, 64)))
    experts, gate = moe.route_top_k(
        m, planted["full_attn"]["mlp"]["router"][0], 2, True)
    assert set(np.asarray(experts).ravel().tolist()) <= {5, 2}
    y, counts = moe.gated_experts(
        jax.tree.map(lambda a: a[0], planted["full_attn"]["mlp"]), m, 2)
    assert counts.tolist()[1] == 120 and counts.tolist()[0] <= 2
    assert counts.tolist()[2] >= 60         # one expert holds every token
    # against every expert computed outright for every token
    dense = sum(
        jnp.where((experts == e).any(-1, keepdims=True),
                  jnp.sum(jnp.where(experts == e, gate, 0.0), -1,
                          keepdims=True), 0.0)
        * mixers.gated_ffn(m, {k: planted["full_attn"]["mlp"][k][0, e]
                               for k in ("w_gate", "w_up", "w_down")},
                           jnp.float32)
        for e in range(8))
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense), atol=1e-5)
    # and through the whole model, against the reference
    got = mixers.forward(cfg, planted, jnp.asarray([p], jnp.int32))[0]
    want = ref.Forward(ref.make(TOY)).logits(planted, p + [0], 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


def test_idle_lanes_touch_no_expert(params):
    """A decode step whose every lane is idle reads 0 experts touched and
    0 assignments behind its tokens; a step of one live lane counts that
    lane's 2 experts a layer."""
    from deeperspeed_tpu.serving.engine import idle_slots

    eng = engine_for(params)
    N, bps = eng.scfg.num_slots, eng.scfg.blocks_per_slot
    nxt, eng.kv.k, eng.kv.v, _, _ = eng._decode_step(
        eng.params, eng.kv.k, eng.kv.v, jnp.asarray(idle_slots(N, bps)),
        jnp.zeros(N + 3, jnp.int32), None, None)
    assert nxt.shape == (N + 3,) and np.asarray(nxt)[N:].tolist() == [0, 0, 0]
    eng.submit([1, 2, 3], max_new_tokens=4, request_id="one")
    eng.run()
    m = eng.metrics
    # 3 decode steps of ONE live lane: 2 experts a layer, never more
    assert m.moe_assignments["decode"] == 3 * 8 * 2
    assert m.moe_experts_touched["decode"] == 3 * 8 * 2
    assert m.moe_max_load["decode"] == 3 * 1
    assert m.moe_assignments["chunk"] == 3 * 8 * 2      # 3 real tokens
    s = m.summary()["moe"]
    assert s["decode"]["experts_touched_per_layer"] == 2.0
    assert s["decode"]["max_load_per_call"] == 1.0


# ------------------------------------------------------------------ #
# the page rule: two rules, two pools, one table
# ------------------------------------------------------------------ #


def test_the_page_rule_of_the_cache():
    cfg = adapter.model_config(TOY)
    assert page_rule_for(cfg) == PageRule(ring=8)
    assert PageRule(ring=8).pools == (0, 1)
    assert PageRule().pools == (0,) and PageRule(32, 4).pools == (0, 0)
    with pytest.raises(ValueError, match="no cache"):
        PageRule(window=32, chunk=4, ring=8)
    # the published geometry: a page for every 64 positions of every key,
    # and the ring's 16 pages at most
    big = PageRule(ring=1024)
    for n, want in ((1, (1, 1)), (63, (1, 1)), (64, (1, 1)), (65, (2, 2)),
                    (1023, (16, 16)), (1024, (16, 16)), (1025, (17, 16)),
                    (32768, (512, 16))):
        assert big.counts(n, 64) == want, n
    scfg = ServingConfig(num_slots=32, block_size=64, num_blocks=16385,
                         max_seq_len=32768).for_cache(big)
    assert scfg.table_widths == (512, 16) and scfg.blocks_per_slot == 528
    assert scfg.pool_blocks == (16385, 32 * 16 + 1)
    # the configuration every other model runs is untouched
    plain = ServingConfig(num_slots=32, block_size=64, num_blocks=16385,
                          max_seq_len=32768)
    assert plain.pool_blocks == (16385,) and plain.table_widths == (512,)


def test_no_byte_of_a_pool_belongs_to_a_layer_that_never_reads_it(params):
    """The pool of every key is as deep as the 2 full layers, the rings'
    as the 6 window layers: bytes = pages x layers x a page's."""
    eng = engine_for(params)
    (kf, kr), (vf, vr) = eng.kv.k, eng.kv.v
    assert kf.shape == vf.shape == (2, 73, 2, 4, 16)
    assert kr.shape == vr.shape == (6, 3 * 2 + 1, 2, 4, 16)
    page = 2 * 2 * 4 * 16 * 4       # K and V, 2 key heads, 4 rows, float32
    assert pool_bytes(eng.kv) == (73 * 2 * page, 7 * 6 * page)
    from benchmark import peaks_mellum as pm
    assert pool_bytes(eng.kv) == pm.pool_bytes(3, 96, 4, 8, 2, 6, 2, 16, 4)
    # at the published geometry: 4.00 GiB and 0.376 GiB
    full, rings = pm.pool_bytes(32, 32768, 64, 1024, 2, 6, 4, 128, 2)
    assert full == 16385 * 2 * 131072 and rings == 513 * 6 * 131072
    assert eng.kv.allocators[0].num_blocks == 73
    assert eng.kv.allocators[1].num_blocks == 7


def scheduler(nb_full, nb_ring):
    scfg = ServingConfig.from_dict(SERVING).for_cache(PageRule(ring=W))
    allocs = [BlockAllocator(nb_full), BlockAllocator(nb_ring)]
    return Scheduler(scfg, allocs), allocs, scfg


def test_admission_asks_both_pools_and_both_are_freed():
    sched, (full, ring), scfg = scheduler(73, 7)
    assert scfg.table_widths == (24, 2)
    sched.submit(Request("a", list(range(21)), 8))
    slot, req, blocks = sched.pop_admissible()
    # 22 positions: 6 pages of every key, the ring's 2
    assert sched.slot_roles[slot] == [6, 2] and len(blocks) == 8
    assert full.num_allocated == 6 and ring.num_allocated == 2
    row = sched.slot_table_row(slot)
    assert len(row) == 26 and all(row[:6]) and not any(row[6:24])
    assert all(row[24:26])
    # growth: the full section a page for every 4 positions, the ring to
    # its 2 pages and no further
    req.cached_len = 40
    sched.ensure_decode_capacity()
    assert sched.slot_roles[slot] == [11, 2]
    assert full.num_allocated == 11 and ring.num_allocated == 2
    sched.finish(req, "length")
    assert full.num_allocated == 0 and ring.num_allocated == 0


@pytest.mark.parametrize("nb_full,nb_ring", [
    pytest.param(6, 7, id="the_pool_of_every_key_is_short"),
    pytest.param(73, 4, id="the_rings_pool_is_short")])
def test_admission_is_refused_when_either_pool_is_short(nb_full, nb_ring):
    """Two requests of 3 pages of every key and the ring's 2 each: the
    first is admitted, the second waits because ONE pool cannot give its
    share, and nothing of the other pool is held for it."""
    sched, (full, ring), _ = scheduler(nb_full, nb_ring)
    sched.submit(Request("a", list(range(9)), 2))
    sched.submit(Request("b", list(range(9)), 2))
    assert sched.pop_admissible() is not None
    assert (full.num_allocated, ring.num_allocated) == (3, 2)
    assert sched.pop_admissible() is None
    assert (full.num_allocated, ring.num_allocated) == (3, 2)
    assert len(sched.queue) == 1
    # once the first has gone, both pools give and the second enters
    sched.finish(sched.slots[0], "length")
    assert sched.pop_admissible() is not None
    assert (full.num_allocated, ring.num_allocated) == (3, 2)


def test_a_request_no_pool_could_ever_hold_is_refused_at_submit():
    sched, _, _ = scheduler(6, 7)
    with pytest.raises(ValueError, match="worst-case footprint"):
        sched.submit(Request("b", list(range(40)), 40))     # 20 pages of 5
    sched, _, _ = scheduler(73, 2)
    with pytest.raises(ValueError, match="worst-case footprint"):
        sched.submit(Request("b", list(range(9)), 2))       # a ring of 2 of 1
    with pytest.raises(ValueError, match="2 pools"):
        Scheduler(ServingConfig.from_dict(SERVING).for_cache(
            PageRule(ring=W)), BlockAllocator(9))


def test_what_cannot_be_served_is_refused_with_its_reason(params):
    with pytest.raises(ValueError, match="overwritten"):
        engine_for(params, prefix_caching=True)
    with pytest.raises(ValueError, match="divide the window"):
        engine_for(params, prefill_chunk=12, prefill_token_budget=12)
    with pytest.raises(ValueError, match="divide the window"):
        engine_for(params, prefill_chunk=16, prefill_token_budget=16)
    cfg = adapter.model_config(TOY)
    with pytest.raises(ValueError, match="page rule"):
        PagedKVCache(cfg, ServingConfig.from_dict(SERVING))
    with pytest.raises(ValueError, match="need cfg.gqa"):
        GPTConfig(n_layer=1, mixer_types=("window_attn",))
    with pytest.raises(NotImplementedError, match="share a stack with no other"):
        PagedKVCache(
            GPTConfig(n_layer=2, n_head=2, d_model=32,
                      mixer_types=("window_attn", "lightning"),
                      gqa=GroupedAttnConfig(window=8)),
            ServingConfig.from_dict(SERVING).for_cache(PageRule(ring=8)))
    with pytest.raises(NotImplementedError, match="is served only"):
        make_gpt(cfg)[2](None, None)


def test_the_config_of_the_stack(params):
    cfg = adapter.model_config(TOY)
    assert cfg.mixer_types == ("window_attn",) * 3 + ("full_attn",) \
        + ("window_attn",) * 3 + ("full_attn",)
    assert mixers.layer_runs(cfg) == [
        ("window_attn", 0, 3), ("full_attn", 0, 1),
        ("window_attn", 3, 3), ("full_attn", 1, 1)]
    assert (cfg.n_head, cfg.kv_heads, cfg.head_dim, cfg.d_model) == (4, 2, 16, 64)
    assert (cfg.moe_num_experts, cfg.moe_top_k, cfg.moe_normalize_gates,
            cfg.ffn_dim) == (8, 2, True, 24)
    assert cfg.gqa.full_rope == RopeScaling(
        theta=10000.0, factor=4.0, original_positions=32, beta_fast=4.0,
        beta_slow=1.0, attention_factor=1.1386294361119891)
    assert cfg.fp32_logits and not cfg.fp32_stream
    mine = mixers.init_params(jax.random.PRNGKey(0), cfg)
    want = jax.tree.map(lambda s: s.shape, ref.leaf_specs(TOY),
                        is_leaf=lambda s: isinstance(s, rinit.Spec))
    assert jax.tree.map(lambda a: a.shape, mine) == want
    # the values default to what every other configuration runs
    plain = GPTConfig()
    assert (plain.gqa, plain.fp32_logits, plain.moe_num_experts) == (None, False, 0)


def test_a_dense_feed_forward_is_a_value_of_the_same_stack(params):
    """The feed-forward is the configuration's: the same stack with
    ``moe_num_experts`` 0 carries dense gated weights, serves through the
    same programs, and its decode step's tokens come alone."""
    cfg = adapter.model_config(TOY, moe_num_experts=0, d_ff=48)
    dense = mixers.init_params(jax.random.PRNGKey(3), cfg)
    assert dense["window_attn"]["mlp"]["w_gate"].shape == (6, 64, 48)
    assert "router" not in dense["full_attn"]["mlp"]
    eng = ServingEngine(cfg, dense, ServingConfig.from_dict(SERVING))
    (p,) = prompts((21,), seed=6)
    eng.submit(p, max_new_tokens=6, request_id="r")
    out = eng.run()["r"]
    toks = list(p)
    for t in out:
        logits = mixers.forward(cfg, dense, jnp.asarray([toks], jnp.int32))[0, -1]
        assert int(jnp.argmax(logits)) == t
        toks.append(t)
    assert eng.metrics.moe_layer_calls == {"decode": 0, "chunk": 0}


def test_the_experts_of_every_mixed_layer_are_the_configurations():
    """``moe_*`` is no longer the ``attention`` kind's alone: a stack of
    another mixed kind with ``moe_num_experts`` set carries routed experts
    and its whole forward runs them."""
    cfg = GPTConfig(vocab_size=32, n_layer=2, n_head=2, d_model=32, d_ff=16,
                    mixer_types=("lightning", "lightning"), dtype=jnp.float32,
                    moe_num_experts=4, moe_top_k=2, moe_normalize_gates=True)
    p = mixers.init_params(jax.random.PRNGKey(0), cfg)
    assert p["lightning"]["mlp"]["w_gate"].shape == (2, 4, 32, 16)
    assert p["lightning"]["mlp"]["router"].shape == (2, 32, 4)
    out = mixers.forward(cfg, p, jnp.arange(16, dtype=jnp.int32)[None] % 32)
    assert out.shape == (1, 16, 32) and bool(jnp.isfinite(out).all())


# ------------------------------------------------------------------ #
# what the programs say of themselves: spans, span arguments, counters
# ------------------------------------------------------------------ #


def test_spans_carry_the_pages_by_rule_and_the_experts_counts(params):
    """``serving/decode/dispatch`` and ``serving/prefill`` carry the pages
    a step lists rule by rule; ``serving/decode/emit`` what the step it
    read counted of its experts (and of the chunks read with it); the
    metrics hold the pages HELD by rule, the ring's wraps and the experts'
    counts by program."""
    from deeperspeed_tpu.monitor.tracer import Tracer, set_tracer

    tracer = Tracer()
    set_tracer(tracer)
    try:
        eng = engine_for(params)
        for i, p in enumerate(prompts((21, 5))):
            eng.submit(p, max_new_tokens=12, request_id=f"r{i}")
        eng.run()
    finally:
        set_tracer(None)
    events = tracer.events()
    named = lambda n: [e for e in events if e["name"] == n]
    dispatch, emit = named("serving/decode/dispatch"), named("serving/decode/emit")
    assert dispatch and len(dispatch) == len(emit)
    for e in dispatch:
        assert {"full_pages", "window_pages", "wraps", "live_pages"} \
            <= set(e["args"])
    # two live slots at 22+ and 6+ positions: pages of 4 rows, a ring of 2
    # pages of which the one that takes the new key is read beside the list
    first = dispatch[0]["args"]
    assert int(first["full_pages"]) >= 6 and int(first["window_pages"]) <= 2
    prefill = [e for e in named("serving/prefill") if "full_pages" in e["args"]]
    assert len(prefill) == 4                    # 3 chunks and 1
    assert [int(e["args"]["full_pages"]) for e in prefill[:3]] == [0, 4, 8]
    assert [int(e["args"]["window_pages"]) for e in prefill[:3]] == [0, 12, 12]
    for e in emit:
        assert {"experts", "assignments", "max_load"} <= set(e["args"])
    with_chunks = [e for e in emit if "chunks" in e["args"]]
    assert sum(int(e["args"]["chunks"]) for e in with_chunks) == 4
    assert sum(int(e["args"]["chunk_assignments"]) for e in with_chunks) \
        == 8 * 2 * (21 + 5)
    m = eng.metrics
    assert m.moe_assignments["chunk"] == 8 * 2 * (21 + 5)
    assert m.moe_assignments["decode"] == sum(
        int(e["args"]["assignments"]) for e in emit)
    assert m.kv_held_rows == m.moe_assignments["decode"] // (8 * 2)
    s = m.summary()
    assert s["kv_pages_per_slot"]["window"] == 2.0      # the whole ring
    assert s["kv_pages_per_slot"]["full"] > 2.0
    assert s["window_wraps"] == m.window_wraps > 0
    assert 0 < s["moe"]["decode"]["experts_touched_per_layer"] <= 4.0
    # how the chunks attended over their past: the XLA forms, on the CPU
    chunks = named("serving/prefill_chunk")
    assert [e["args"]["attn"] for e in chunks] == ["xla"] * 4
    assert s["prefix_reuse"]["prefill_chunks"] == 4
    assert s["prefix_reuse"]["prefill_chunks_kernel_attn"] == 0


def test_the_span_and_the_count_say_when_the_chunks_kernel_engaged(
        params, monkeypatch):
    """``serving/prefill_chunk`` carries ``attn``, what
    ``kv_cache.chunk_attend_for`` returned when the program was built, and
    ``prefill_chunks_kernel_attn`` counts the chunks of a program that
    attends in ops/pallas/chunk_past_attn. The toy pool is no shape the
    kernel tiles, so the chooser is made to say so here; a stack of one
    cache rule says nothing."""
    from deeperspeed_tpu.monitor.tracer import Tracer, set_tracer
    from deeperspeed_tpu.serving import kv_cache as kvc

    said, chooser = [], kvc.chunk_attend_for

    def as_on_one_tpu(*args):
        said.append(args[1:])
        return chooser(*args)._replace(name="kernel")

    monkeypatch.setattr(kvc, "chunk_attend_for", as_on_one_tpu)
    tracer = Tracer()
    set_tracer(tracer)
    try:
        eng = engine_for(params)
        eng.submit(prompts((21,))[0], max_new_tokens=3, request_id="r")
        eng.run()
    finally:
        set_tracer(None)
    chunks = [e for e in tracer.events()
              if e["name"] == "serving/prefill_chunk"]
    assert [e["args"]["attn"] for e in chunks] == ["kernel"] * 3
    reuse = eng.metrics.summary()["prefix_reuse"]
    assert reuse["prefill_chunks_kernel_attn"] == reuse["prefill_chunks"] == 3
    # asked with the heads, the chunk's length and no mesh, by the engine
    # and by the program it built
    assert set(said) == {(4, 8, None)}


def test_the_experts_scopes_are_in_the_programs(params):
    """``ds.moe.route`` and ``ds.moe.experts`` mark the routing and the
    grouped products inside a layer, beside ``ds.attn`` and ``ds.mlp``."""
    from deeperspeed_tpu.serving.engine import idle_slots

    eng = engine_for(params)
    N, bps = eng.scfg.num_slots, eng.scfg.blocks_per_slot
    text = eng._decode_step.lower(
        eng.params, eng.kv.k, eng.kv.v, jnp.asarray(idle_slots(N, bps)),
        jnp.zeros(N + 3, jnp.int32), None, None).as_text(debug_info=True)
    for scope in ("ds.moe.route", "ds.moe.experts", "ds.attn", "ds.mlp"):
        assert scope in text, scope


# ------------------------------------------------------------------ #
# the cells the benchmark had: one rule, one pool, the same programs
# ------------------------------------------------------------------ #


@pytest.mark.parametrize("bench,cell,rule,widths,pool,programs", [
    ("BENCHMARK.toy.json", "toy-neox.serve", PageRule(), (8,),
     (2, 65, 8, 2, 32),
     {"ds_prefill", "ds_scatter_prefill_pages", "ds_decode_step"}),
    ("BENCHMARK.toy-sala.json", "toy-sala.serve-longdoc", PageRule(), (25,),
     (2, 121, 2, 8, 16), {"ds_prefill_chunk", "ds_decode_step"}),
    ("BENCHMARK.toy-h1.json", "toy-h1.serve-chat", PageRule(), (16,),
     (2, 65, 2, 8, 16), {"ds_prefill_chunk", "ds_decode_step"}),
    ("BENCHMARK.toy-eva.json", "toy-eva.serve-bytes",
     PageRule(window=32, chunk=4), (4, 4), (2, 41, 4, 8, 16),
     {"ds_prefill_chunk", "ds_decode_step"}),
])
def test_the_cells_the_benchmark_had_keep_their_rule_pool_and_programs(
        bench, cell, rule, widths, pool, programs):
    """A model without window layers is laid out as before this stack
    came: ONE rule, a table of its widths, ONE pool that is an array (not
    a pair) with one allocator, a decode step whose tokens come alone, and
    a request lowers the same serving programs, once each."""
    from benchmark import device, run as brun
    from benchmark.runners import serve
    from deeperspeed_tpu.monitor import compile_account

    data = os.path.join(mf.ROOT, "tests", "bench", "data")
    man = mf.Manifest(os.path.join(data, bench), extra_dirs=[mf.BENCH_DIR])
    devs = jax.devices()[:1]
    ctx = brun.build_context(man, cell, 7, 1.0, 0, devs,
                             device.describe(devs), lambda m: None)
    eng = serve.build_engine(ctx)
    scfg = eng.scfg
    assert scfg.page_rule == rule and rule.pools == (0,) * len(widths)
    assert tuple(scfg.table_widths) == widths
    assert scfg.blocks_per_slot == sum(widths)
    assert scfg.pool_blocks == (scfg.num_blocks,)
    assert eng.kv.k.shape == eng.kv.v.shape == pool
    assert eng.kv.allocators == [eng.kv.allocator]
    # only a stack whose chunk has a kernel form says how it attended (the
    # pages of two roles since PR 45): the XLA form, on the CPU
    assert eng._chunk_attn == ("xla" if rule.window else None)
    assert pool_bytes(eng.kv) == (eng.kv.k.nbytes + eng.kv.v.nbytes,)

    def lowered():
        return {name: acc.get("lower", {}).get("count", 0)
                for name, acc in compile_account().items()
                if name.startswith("ds_")}

    before = lowered()
    rng = np.random.default_rng(0)
    eng.submit(rng.integers(0, ctx.config["vocab_size"], 40).tolist(),
               max_new_tokens=4, request_id="a")
    eng.run()
    assert eng._prev.shape == (scfg.num_slots,)
    new = {k: v - before.get(k, 0) for k, v in lowered().items()
           if v - before.get(k, 0)}
    assert set(new) == programs and set(new.values()) == {1}
