"""A LOOPED stack (Ouro's: one stack of sandwich-normed ``full_attn``
layers run ``loop_steps`` times a token with the same weights, the final
norm after every pass, a cache layer of its own for every (pass, layer)
pair, an exit gate read out and not acted on) through ``ServingEngine``,
at small widths on the CPU: 3 layers run 3 times, hidden 64, 4 heads of
16, pages of 4 rows, ``prefill_chunk`` 8, float32. What the engine serves
(chunked prefill, then decode through the pages of 9 cache layers) is
compared with the plain reference ``benchmark/refs/ouro.py`` on seeded
weights, and the parts with each other."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest as mf
from benchmark.adapters import mellum as mellum_adapter
from benchmark.adapters import ouro as adapter
from benchmark.refs import init as rinit
from benchmark.refs import ouro as ref
from deeperspeed_tpu.models import mixers
from deeperspeed_tpu.models.gpt import GPTConfig, GroupedAttnConfig
from deeperspeed_tpu.serving import ServingConfig
from deeperspeed_tpu.serving.engine import (behind_tokens, exits_behind,
                                            prefill_chunk_for)
from deeperspeed_tpu.serving.kv_cache import (PagedKVCache, page_rule_for,
                                              position_bytes, write_rows)

DATA = os.path.join(mf.ROOT, "tests", "bench", "data", "configs")
TOY = mf.load_json(os.path.join(DATA, "toy-ouro.json"))
SERVING = {"num_slots": 3, "block_size": 4, "num_blocks": 73,
           "max_seq_len": 96, "prefill_chunk": 8,
           "prefill_token_budget": 8, "max_new_tokens": 32}
VOCAB, L, T, BS = (TOY["vocab_size"], TOY["num_hidden_layers"],
                   TOY["total_ut_steps"], 4)
LENGTHS, NEW = (21, 5, 13), 12


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


@pytest.fixture(scope="module")
def roomy(params):
    return served(params, LENGTHS, NEW)


def gaps(reference, params, p, o):
    """How far each served token lies under the reference's best, and the
    logits' spread."""
    logits = np.asarray(reference.logits(params, p + o, len(p)))
    return logits.max(-1) - logits[np.arange(len(o)), o], logits.std()


# ------------------------------------------------------------------ #
# (1) the model's own forward, (2) the engine, against the reference
# ------------------------------------------------------------------ #


def test_the_whole_forward_agrees_with_the_reference(params, reference):
    """mixers.forward (no cache) is the program's own statement of the
    model: the logits of the last pass at all positions and columns, and
    the exit gate of EVERY pass. atol 3e-5 on logits that spread 1.6 and
    1e-5 on gates in (0, 1): float32 sums in another order."""
    cfg = adapter.model_config(TOY)
    (p,) = prompts((60,), seed=5)
    got, lam = mixers.forward(cfg, params, jnp.asarray([p], jnp.int32),
                              gates=True)
    want, want_lam = reference.run(params, p + [0], 1)
    assert got.shape == (1, 60, VOCAB) and lam.shape == (T, 1, 60)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=3e-5)
    np.testing.assert_allclose(np.asarray(lam[:, 0]), np.asarray(want_lam),
                               atol=1e-5)
    # the gates differ by pass and position: a constant would pass nothing
    assert float(jnp.std(want_lam)) > 0.05
    # the distribution over the pass a token would leave after sums to 1
    p_exit = mixers.exit_distribution(lam[:, 0])
    np.testing.assert_allclose(np.asarray(p_exit.sum(0)), 1.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(p_exit),
                               ref.exit_distribution(want_lam), atol=1e-5)


@pytest.mark.parametrize("lengths,new", [
    # chunk 8: 21 = 2 chunks and 5 (ragged), 5 inside one chunk, 13 ragged
    pytest.param(LENGTHS, NEW, id="three_slots_of_unequal_length"),
    pytest.param((16, 8, 1), 9, id="whole_chunks_and_a_prompt_of_one_token"),
    pytest.param((40, 7), 30, id="a_long_decode_run_beside_a_short_one")])
def test_prefill_chunks_and_decode_agree_with_the_reference(
        params, reference, lengths, new):
    """Chunked prefill, then decode through the pages of 9 cache layers,
    several slots of unequal length at once: at every served position the
    served token's reference LOGIT is the reference's best to within 1e-4
    of the logits' spread (both sides float32, sums in another order).
    One lowering of each program for every length, slot and offset."""
    eng, ps, outs = served(params, lengths, new)
    for p, o in zip(ps, outs):
        gap, spread = gaps(reference, params, p, o)
        assert len(o) == new and gap.max() <= 1e-4 * spread, (len(p), gap.max())
    assert eng.decode_compile_count == 1
    assert eng._chunk_step._cache_size() == 1
    assert eng.prefill_compile_count == 0
    assert eng.kv.allocator.num_allocated == 0


def chunked(eng, p, slot=1):
    """The chunk program driven by hand through a fresh slot's table: the
    last chunk's logits and exit distribution."""
    scfg, kv = eng.scfg, eng.kv
    table = np.zeros(scfg.blocks_per_slot, np.int32)
    n_pages = scfg.pages_needed(len(p) + 1)
    table[:n_pages] = kv.allocator.alloc(n_pages)
    C = prefill_chunk_for(eng.cfg, scfg)
    for lo in range(0, len(p), C):
        toks = np.zeros((1, C), np.int32)
        n = min(C, len(p) - lo)
        toks[0, :n] = p[lo:lo + n]
        (logits, exits), kv.k, kv.v, kv.kc, kv.state = eng._chunk_step(
            eng.params, kv.k, kv.v, kv.kc, kv.state, jnp.asarray(toks),
            jnp.asarray(table), np.int32(slot), np.int32(lo), np.int32(n))
    return np.asarray(logits), np.asarray(exits), table


@pytest.mark.parametrize("length", [3, 8, 9, 21])
def test_first_token_logits_and_gates_of_a_chunked_prompt(params, reference,
                                                          length):
    """LOGITS and GATES, not tokens: the chunk program's own at the
    prompt's last position against the reference's. atol 3e-5 on logits
    that spread 1.6, 1e-5 on the exit distribution."""
    (p,) = prompts((length,), seed=3)
    got, exits, _ = chunked(engine_for(params), p)
    want, lam = reference.run(params, p + [0], len(p))
    assert got.shape == (VOCAB,) and exits.shape == (T,)
    np.testing.assert_allclose(got, np.asarray(want)[0], atol=3e-5)
    np.testing.assert_allclose(exits, ref.exit_distribution(lam)[:, 0],
                               atol=1e-5)


# ------------------------------------------------------------------ #
# (3) a cache layer for every (pass, layer) pair
# ------------------------------------------------------------------ #


def reference_keys(params, tokens):
    """The reference's rotated keys and its values of every (pass,
    layer), by hand: [pass][layer] -> (k, v) each (S, Hkv, Dh)."""
    model = ref.make(TOY)
    outer = {k: v for k, v in params.items() if k != "full_attn"}
    with jax.default_matmul_precision("highest"):
        x = model.embed(outer, jnp.asarray(tokens, jnp.int32))
        out = []
        for t in range(T):
            out.append([])
            for l in range(L):
                x, kv = model.layer(
                    jax.tree.map(lambda a: a[l], params["full_attn"]), x)
                out[t].append(kv)
            x, _ = model.end_pass(outer, x, t == T - 1)
    return out


def test_pass_t_layer_l_lands_in_cache_layer_t_times_L_plus_l(params):
    """A prompt by chunks, then decode steps: every position's key and
    value of pass t, layer l lie in cache layer t * L + l of the slot's
    pages, and two passes' keys of one token and layer differ (a cache
    shared between passes would pass every other test at T = 1 only)."""
    eng = engine_for(params)
    (p,) = prompts((13,), seed=11)
    eng.submit(p, max_new_tokens=8, request_id="r")
    req = eng.get("r")
    with jax.default_matmul_precision("highest"):
        while len(req.generated) < 6:
            eng.step()
        eng._settle()
    blocks = list(eng.sched.slot_blocks[req.slot])
    n = req.cached_len                   # positions whose rows are written
    toks = (p + req.generated)[:n]
    want = reference_keys(params, toks)
    pool_k, pool_v = np.asarray(eng.kv.k), np.asarray(eng.kv.v)
    assert pool_k.shape[0] == T * L == 9
    for t in range(T):
        for l in range(L):
            k, v = (np.asarray(a) for a in want[t][l])
            for i in range(n):
                row = (t * L + l, blocks[i // BS], slice(None), i % BS)
                np.testing.assert_allclose(pool_k[row], k[i], atol=2e-5)
                np.testing.assert_allclose(pool_v[row], v[i], atol=2e-5)
    k0, k1 = (np.asarray(want[t][1][0]) for t in (0, 1))
    assert np.abs(k0 - k1).max() > 0.1


def test_a_position_costs_a_row_of_every_cache_layer(params):
    """The pool's shape, the bytes a position costs and the metrics come
    from the model's own counts, nowhere from a constant."""
    eng = engine_for(params)
    cfg = eng.cfg
    assert (cfg.loop_steps, cfg.count("full_attn"),
            cfg.cache_layers("full_attn")) == (T, L, T * L)
    assert eng.kv.k.shape == (T * L, 73, 4, BS, 16) == eng.kv.v.shape
    # K and V, 9 cache layers, 4 key heads of 16, float32
    assert position_bytes(eng.kv) == 2 * T * L * 4 * 16 * 4 == 4608
    s = eng.metrics.summary()
    assert (s["loop_steps"], s["kv_bytes_per_position"]) == (T, 4608)
    # pages are counted as for any cache whose pages follow the length
    assert eng.scfg.page_rule == page_rule_for(cfg) and \
        eng.scfg.pages_needed(21) == 6
    assert behind_tokens(cfg) == T


# ------------------------------------------------------------------ #
# (5) preemption, prefix reuse, the counters and the spans
# ------------------------------------------------------------------ #


def test_preempt_and_readmit_gives_identical_tokens(params, reference, roomy):
    """A pool too small for three live slots: the youngest is preempted
    while it decodes, its pages of all 9 cache layers are rebuilt by
    re-prefilling prompt + generated, and every request's tokens are those
    of the roomy pool (which are the reference's best)."""
    _, ps, want = roomy
    eng, _, tight = served(params, LENGTHS, NEW, num_blocks=14)
    assert eng.metrics.summary()["preemptions"] >= 1
    assert tight == want
    assert eng.kv.allocator.num_allocated == 0


def test_a_cached_prefix_gives_the_logits_a_cold_prompt_gives(params,
                                                              reference):
    """Prefix caching over a looped stack (a prefix's pages hold all 9
    cache layers): a second prompt that shares 16 tokens of the first
    skips the chunks that lie wholly inside the shared pages and is
    served the tokens a cold engine serves, which are the reference's
    best."""
    a, b = prompts((24, 9), seed=21)
    second = a[:16] + b
    eng = engine_for(params, prefix_caching=True)
    with jax.default_matmul_precision("highest"):
        eng.submit(a, max_new_tokens=6, request_id="first")
        eng.run()
        eng.submit(second, max_new_tokens=10, request_id="second")
        warm = eng.run()["second"]
    reuse = eng.metrics.summary()["prefix_reuse"]
    assert reuse["reuse_hits"] == 1 and reuse["tokens_saved"] == 16
    # 3 chunks of the first, 4 of the second less the 2 inside the prefix
    assert reuse["prefill_chunks"] == 3 + 2
    cold_eng = engine_for(params)
    with jax.default_matmul_precision("highest"):
        cold_eng.submit(second, max_new_tokens=10, request_id="second")
        cold = cold_eng.run()["second"]
    assert warm == cold
    gap, spread = gaps(reference, params, second, warm)
    assert gap.max() <= 1e-4 * spread


def test_the_exit_gate_is_read_out_and_not_acted_on(params, reference, roomy):
    """Every token runs every pass (the tokens are the reference's, which
    never leaves early); the gate's distribution comes back behind the
    tokens, one read-back a step, and its mean over the served tokens is
    the reference's."""
    eng, ps, outs = roomy
    s = eng.metrics.summary()
    assert len(s["exit_p"]) == T and abs(sum(s["exit_p"]) - 1.0) < 1e-5
    assert eng.metrics.exit_tokens == len(LENGTHS) * NEW
    want = np.zeros(T)
    for p, o in zip(ps, outs):
        _, lam = reference.run(params, p + o, len(p))
        want += ref.exit_distribution(lam).sum(1)
    np.testing.assert_allclose(s["exit_p"], want / (len(LENGTHS) * NEW),
                               atol=1e-5)
    assert s["exit_step_expected"] == pytest.approx(
        1.0 + float(np.dot(np.arange(T), s["exit_p"])))
    assert 1.0 < s["exit_step_expected"] < T
    assert eng._prev.shape == (SERVING["num_slots"] + T,)
    # float32 bits behind int32 tokens, summed over the live lanes alone
    lam = jnp.asarray([[0.5, 0.25], [0.5, 0.5], [0.1, 0.9]])
    bits = exits_behind(lam, jnp.asarray([True, False]))
    np.testing.assert_allclose(
        np.asarray(bits).view(np.float32), [0.5, 0.25, 0.25], atol=1e-7)


def test_the_dispatch_span_says_the_pages_and_the_passes(params):
    from deeperspeed_tpu.monitor.tracer import Tracer, set_tracer

    tracer = Tracer()
    set_tracer(tracer)
    try:
        eng, _, _ = served(params, (9,), 4)
    finally:
        set_tracer(None)
    spans = [e for e in tracer.events()
             if e["name"] == "serving/decode/dispatch"]
    assert len(spans) == 3      # the first token is the chunk's
    assert {e["args"]["passes"] for e in spans} == {str(T)}
    # 9 prompt positions and the new token: 3 pages of 4 rows, each read
    # by all 9 cache layers
    assert [e["args"]["full_pages"] for e in spans] == ["3", "3", "3"]
    assert eng.metrics.kv_full_pages == 9 and eng.metrics.kv_held_rows == 3


# ------------------------------------------------------------------ #
# (6) a stack that is not looped is as it was
# ------------------------------------------------------------------ #


def test_loop_steps_one_leaves_the_layer_loop_the_pool_and_the_pages():
    """``scan_passes`` with ``loop_steps == 1`` traces to the equations of
    ``scan_runs`` alone (so every existing program lowers to the text it
    lowered to: ``scripts/lowering_hashes.py`` compares the text itself),
    the pool is as deep as the stack and a position costs what it cost."""
    toy = mf.load_json(os.path.join(DATA, "toy-mellum.json"))
    cfg = mellum_adapter.model_config(toy)
    assert cfg.loop_steps == 1 and not cfg.gqa.sandwich
    for kind in ("full_attn", "window_attn"):
        assert cfg.cache_layers(kind) == cfg.count(kind)
    params = mixers.init_params(jax.random.PRNGKey(0), cfg)
    assert "exit_gate" not in params and "ln1_post" not in params["full_attn"]
    x = jnp.zeros((1, 8, cfg.d_model))

    def body(kind, carry, p, layer, at=None):
        assert at is None or at is layer    # the SAME traced value
        return (carry[0] + p["ln1"][None, None] * layer, carry[1]), layer

    direct = jax.make_jaxpr(lambda prm, x: mixers.scan_runs(
        cfg, prm, (x, None), body))(params, x)
    looped = jax.make_jaxpr(lambda prm, x: mixers.scan_passes(
        cfg, prm, x, None, body, lambda x: x)[:3])(params, x)
    assert str(direct.jaxpr.eqns) == str(looped.jaxpr.eqns)
    scfg = ServingConfig.from_dict(
        {**SERVING, "num_blocks": 73}).for_cache(page_rule_for(cfg))
    kv = PagedKVCache(cfg, scfg)
    assert kv.k[0].shape[0] == cfg.count("full_attn") == 2
    assert kv.k[1].shape[0] == cfg.count("window_attn") == 6
    # K and V: 2 full layers and 6 ring layers, 2 key heads of 16, float32
    assert position_bytes(kv) == 2 * (2 + 6) * 2 * 16 * 4


def test_a_looped_stack_is_one_of_full_attn_layers_and_is_served_only():
    gqa = GroupedAttnConfig(qk_norm=False, sandwich=True)
    kw = dict(vocab_size=96, n_layer=2, n_head=4, d_model=64, head_size=16,
              d_ff=128, gqa=gqa)
    cfg = GPTConfig(mixer_types=("full_attn",) * 2, loop_steps=3, **kw)
    assert cfg.cache_layers("full_attn") == 6
    with pytest.raises(ValueError, match="full_attn layers alone"):
        GPTConfig(mixer_types=("full_attn", "window_attn"), loop_steps=2,
                  **kw)
    with pytest.raises(ValueError, match="loop_steps"):
        GPTConfig(loop_steps=2, **{**kw, "gqa": None})     # a classic stack
    with pytest.raises(ValueError, match="loop_steps"):
        GPTConfig(mixer_types=("full_attn",) * 2, loop_steps=0, **kw)


def test_a_deep_pool_is_written_part_by_part_to_the_same_rows():
    """``write_rows`` cuts a pool deeper than ``WRITE_DEPTH`` layers into
    parts (the TPU compiler would copy slices of the pool otherwise:
    tests/test_tpu_compile.py); the rows written are the same."""
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(150, 5, 2, 4, 8)), jnp.float32)
    new = jnp.asarray(rng.normal(size=(150, 3, 2, 8)), jnp.float32)
    page, row = jnp.asarray([1, 4, 2]), jnp.asarray([0, 3, 1])
    got = np.asarray(write_rows(pool, page, row, new))
    want = np.array(pool)
    for n in range(3):
        want[:, int(page[n]), :, int(row[n])] = np.asarray(new[:, n])
    np.testing.assert_array_equal(got, want)
