"""A stack of ``full_attn`` and ``kda`` layers whose feed-forward is ONE
CHIP'S SHARE of routed experts beside a shared one (Solar Open 2's: one
gated grouped-query layer without rotary that keeps pages, then three
Kimi Delta Attention layers that keep a float32 state row and three
convolution tails a slot, a period) through ``ServingEngine``, at small
widths on the CPU: G K K K G K K K, hidden 64, 4 heads over 2 key heads of
16, 4 delta-rule heads of 16 x 16, 16 experts 2 a token of which the 4
from expert 4 on are held, pages of 4 rows, ``prefill_chunk`` 8, float32.
The chunkwise rule and both kernels (interpreted) against the token
recurrence; what the engine serves against the plain reference
``benchmark/refs/solar_open2.py`` on seeded weights."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest as mf
from benchmark.adapters import solar_open2 as adapter
from benchmark.refs import init as rinit
from benchmark.refs import solar_open2 as ref
from deeperspeed_tpu.models import mixers
from deeperspeed_tpu.models.gpt import (GPTConfig, GroupedAttnConfig,
                                        KdaConfig)
from deeperspeed_tpu.ops.pallas import kda_chunk as chunk_kernel
from deeperspeed_tpu.ops.pallas import kda_row_update as rows_kernel
from deeperspeed_tpu.serving import ServingConfig
from deeperspeed_tpu.serving.config import PageRule
from deeperspeed_tpu.serving.engine import prefill_chunk_for
from deeperspeed_tpu.serving.kv_cache import PagedKVCache, page_rule_for

TOY = mf.load_json(os.path.join(mf.ROOT, "tests", "bench", "data", "configs",
                                "toy-solar.json"))
SERVING = {"num_slots": 3, "block_size": 4, "num_blocks": 73,
           "max_seq_len": 96, "prefill_chunk": 8,
           "prefill_token_budget": 8, "max_new_tokens": 32}
VOCAB = TOY["vocab_size"]


# ------------------------------------------------------------------ #
# (a) the chunkwise rule and the kernels against the token recurrence
# ------------------------------------------------------------------ #


def rule_inputs(T, H=2, dk=128, dv=128, seed=0, decay=None, beta_shift=0.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (T, H, dk))) / np.sqrt(dk)
    # keys behind a SiLU are far from orthogonal, as the model's are
    k = unit(jax.nn.silu(jax.random.normal(ks[1], (T, H, dk))))
    v = jax.random.normal(ks[2], (T, H, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (T, H, dk)) - 2.0)
    if decay is not None:       # one channel that forgets at once
        g = g.at[:, :, 5].set(np.log(decay))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)) + beta_shift)
    return q, k, v, g, beta, 0.1 * jax.random.normal(ks[5], (H, dk, dv))


def close(got, want, what):
    scale = float(jnp.max(jnp.abs(want)))
    err = float(jnp.max(jnp.abs(got - want)))
    assert np.isfinite(err) and err <= 2e-5 * max(scale, 1.0), (what, err, scale)


@pytest.mark.parametrize("T,kw", [
    pytest.param(64, {}, id="one_block"),
    pytest.param(100, {}, id="not_a_multiple_of_the_block"),
    pytest.param(37, {"dk": 16, "dv": 16, "H": 4}, id="the_toys_heads"),
    pytest.param(128, {"beta_shift": 3.0}, id="beta_above_one"),
    pytest.param(192, {"decay": 1e-4},
                 id="a_channel_that_decays_to_1e-4_a_token")])
def test_the_chunkwise_rule_is_the_recurrence(T, kw):
    """``exp(-G_j)`` of the strong channel would be ``1e4^64``: only
    differences ``<= 0`` may be exponentiated."""
    a = rule_inputs(T, **kw)
    if kw.get("beta_shift"):
        assert float(jnp.mean(a[4])) > 1.8
    o, S = mixers.kda_recurrence(*a)
    ox, Sx = jax.jit(mixers.kda_chunk_xla)(*a)
    close(ox, o, "o")
    close(Sx, S, "S")


@pytest.mark.parametrize("kw", [
    pytest.param({}, id="plain"),
    pytest.param({"decay": 1e-4, "beta_shift": 3.0},
                 id="strong_decay_and_beta_near_two")])
def test_the_chunk_kernel_interpreted_is_the_recurrence(kw):
    a = rule_inputs(128, **kw)
    o, S = mixers.kda_recurrence(*a)
    ok, Sk = chunk_kernel.kda_chunk(*a, interpret=True)
    close(ok, o, "o")
    close(Sk, S, "S")


def test_state_tails_and_n_valid_across_three_chunks():
    """Three chunks of 24 positions through ``mixers.kda_chunk``, the last
    with 13 real ones: the state and the convolutions' tails carried from
    chunk to chunk give what ONE pass over the 61 real positions gives,
    and the padding moves neither."""
    kc = KdaConfig(n_heads=2, head_k=16, head_v=16, low_rank=4)
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    T, n_real = 72, 61
    p = {"conv_w": 0.5 * jax.random.normal(ks[0], (kc.d_conv, kc.conv_dim))}
    qkv = jax.random.normal(ks[1], (T, kc.conv_dim))
    g = -jax.nn.softplus(jax.random.normal(ks[2], (T, 2, 16)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (T, 2)))
    zero_tail = jnp.zeros((kc.d_conv - 1, kc.conv_dim))
    S0 = jnp.zeros((2, 16, 16))
    chunk = jax.jit(lambda *a: mixers.kda_chunk(kc, p, *a))
    o_all, tail_all, S_all = chunk(qkv[:n_real], g[:n_real], beta[:n_real],
                                   zero_tail, S0, n_real)
    tail, S, outs = zero_tail, S0, []
    for lo in range(0, T, 24):
        o, tail, S = chunk(
            qkv[lo:lo + 24], g[lo:lo + 24], beta[lo:lo + 24], tail, S,
            jnp.int32(min(24, n_real - lo)))
        outs.append(o)
    close(jnp.concatenate(outs)[:n_real], o_all, "o")
    close(S, S_all, "S")
    np.testing.assert_array_equal(np.asarray(tail), np.asarray(tail_all))
    np.testing.assert_array_equal(np.asarray(tail), np.asarray(qkv[58:61]))


def test_the_row_kernel_interpreted_is_the_recurrence():
    """One token a slot on the stacked rows, in place at ``layer``; a slot
    that is not live keeps its row bit for bit."""
    N, H, dk, dv = 3, 2, 128, 128
    q, k, v, g, beta, _ = rule_inputs(N, H=H, seed=3, beta_shift=2.0)
    rows = jax.random.normal(jax.random.PRNGKey(9), (2, N, H, dk, dv))
    live = jnp.array([True, False, True])
    rx, ox = mixers.kda_rows_xla(rows, 1, q, k, v, g, beta, live)
    rk, ok = rows_kernel.kda_row_update(rows, jnp.int32(1), q, k, v, g, beta,
                                        live, interpret=True)
    for n in (0, 2):
        o, S = mixers.kda_recurrence(q[n][None], k[n][None], v[n][None],
                                     g[n][None], beta[n][None], rows[1, n])
        for got_r, got_o in ((rx, ox), (rk, ok)):
            close(got_o[n], o[0], "o")
            close(got_r[1, n], S, "S")
    for got in (rx, rk):
        np.testing.assert_array_equal(np.asarray(got[1, 1]),
                                      np.asarray(rows[1, 1]))
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(rows[0]))


# ------------------------------------------------------------------ #
# (b) the engine against the plain reference
# ------------------------------------------------------------------ #


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
    logits = np.asarray(reference.logits(params, p + o, len(p)))
    return logits.max(-1) - logits[np.arange(len(o)), o], logits.std()


LENGTHS, NEW = (16, 15, 17, 40, 9, 2), 12


@pytest.fixture(scope="module")
def window(params):
    """Six requests through three slots, traced: every slot is entered
    twice, so the second occupant finds the first's rows and tails and
    must clear them; prompts of whole chunks, ragged ones, and one shorter
    than the convolution's taps."""
    from deeperspeed_tpu.monitor.tracer import Tracer, set_tracer

    tracer = Tracer()
    set_tracer(tracer)
    try:
        eng, ps, outs = served(params, LENGTHS, NEW)
    finally:
        set_tracer(None)
    return eng, ps, outs, tracer.events()


def test_prefill_chunks_and_decode_agree_with_the_reference(
        params, reference, window):
    """Chunked prefill, then decode through the pages and the state rows:
    every served (greedy) token is the reference's best at its position.
    Tolerance 1e-4 of the logits' spread: both sides are float32 and
    differ in the order of their sums alone."""
    eng, ps, outs, _ = window
    for p, o in zip(ps, outs):
        gap, spread = gaps(reference, params, p, o)
        assert len(o) == NEW and gap.max() <= 1e-4 * spread, (len(p), gap.max())
    assert eng.decode_compile_count == 1
    assert eng._chunk_step._cache_size() == 1       # one lowering, every chunk
    assert eng.prefill_compile_count == 0           # no bucketed prefill
    assert eng.kv.allocator.num_allocated == 0
    assert eng.metrics.summary()["state_resets"] == len(LENGTHS)


def chunked_logits(eng, p, slot=1):
    """The chunk program driven by hand through a slot's table: the last
    chunk's logits and the chunks' counts of their experts."""
    cfg, scfg, kv = eng.cfg, eng.scfg, eng.kv
    table = np.zeros(scfg.blocks_per_slot, np.int32)
    pages = kv.allocator.alloc(scfg.pages_needed(len(p) + 1))
    table[:len(pages)] = pages
    Cp = prefill_chunk_for(cfg, scfg)
    counts = []
    for lo in range(0, len(p), Cp):
        toks = np.zeros((1, Cp), np.int32)
        n = min(Cp, len(p) - lo)
        toks[0, :n] = p[lo:lo + n]
        (logits, c), kv.k, kv.v, kv.kc, kv.state = eng._chunk_step(
            eng.params, kv.k, kv.v, kv.kc, kv.state, jnp.asarray(toks),
            jnp.asarray(table), np.int32(slot), np.int32(lo), np.int32(n))
        counts.append(np.asarray(c))
    kv.allocator.free(pages)
    return np.asarray(logits), counts


@pytest.fixture(scope="module")
def by_hand(params):
    return engine_for(params)


@pytest.mark.parametrize("length", [
    pytest.param(2, id="shorter_than_the_convolution"),
    pytest.param(8, id="one_whole_chunk"),
    pytest.param(21, id="two_chunks_and_a_ragged_third"),
    pytest.param(64, id="eight_whole_chunks")])
def test_first_token_logits_of_a_chunked_prompt(params, reference, by_hand,
                                                length):
    """LOGITS, not tokens: the chunk program's own at the prompt's last
    position against the reference's, every case through the SAME slot of
    one engine (a prompt finds the rows and tails the one before it left:
    cleared rows, cleared tails). atol 3e-5 on logits that spread 1.6. A
    chunk routes its real tokens alone: the assignments computed here and
    those that left make 8 layers x 2 a REAL token."""
    (p,) = prompts((length,), seed=3)
    got, counts = chunked_logits(by_hand, p)
    want = np.asarray(reference.logits(params, p + [0], len(p)))[0]
    assert got.shape == want.shape == (VOCAB,) and got.dtype == np.float32
    np.testing.assert_allclose(got, want, atol=3e-5)
    assert all(c.shape == (4,) for c in counts)
    assert sum(int(c[1] + c[3]) for c in counts) == 8 * 2 * length


def test_the_whole_forward_agrees_with_the_reference(params, reference):
    """mixers.forward (no cache) is the program's own statement of the
    model. All positions, all columns."""
    cfg = adapter.model_config(TOY)
    (p,) = prompts((100,), seed=5)
    got = mixers.forward(cfg, params, jnp.asarray([p], jnp.int32))[0]
    want = reference.logits(params, p + [0], 1)
    assert got.shape == (100, VOCAB) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


def test_preempt_and_readmit_gives_identical_tokens(params, window):
    """A pool too small for three live slots: the youngest is preempted
    while it decodes, its rows and tails are rebuilt by re-prefilling
    prompt + generated (never resumed), and every request's tokens are
    those of the roomy pool (which are the reference's best)."""
    _, _, roomy, _ = window
    eng, _, tight = served(params, LENGTHS, NEW, num_blocks=20)
    assert eng.metrics.summary()["preemptions"] >= 1
    assert tight == roomy
    assert eng.kv.allocator.num_allocated == 0


# ------------------------------------------------------------------ #
# (f) the cache, what it refuses, the counters and the spans
# ------------------------------------------------------------------ #


def test_counters_and_cache_shapes_of_a_served_window(window):
    eng = window[0]
    s = eng.metrics.summary()
    kc, N = eng.cfg.kda, 3
    rows = 6 * N * kc.n_heads * kc.head_k * kc.head_v * 4
    tails = 6 * N * (kc.d_conv - 1) * kc.conv_dim * 4
    assert s["state_bytes"] == rows + tails
    assert s["state_bytes_per_step"] == 2 * (rows + tails)
    assert eng.metrics.state_bytes_moved == s["decode_steps"] * 2 * (rows + tails)
    kv = eng.kv
    assert kv.kc is None and set(kv.state) == {"kda", "conv"}
    # ONE pool, as deep as the full_attn layers alone
    assert kv.k.shape == kv.v.shape == (2, 73, 2, 4, 16)
    assert kv.state["kda"].shape == (6, N, 4, 16, 16)
    assert kv.state["conv"].shape == (6, N, 3, 3 * 4 * 16)
    assert eng.scfg.page_rule == page_rule_for(eng.cfg) == PageRule()
    # a slot's pages follow the length, for the GQA layers alone
    assert eng.scfg.pages_needed(64 + 1) == 17
    assert s["kv_pages_per_slot"]["full"] > 1.0
    assert s["kda_chunks_kernel"] == 0              # the XLA form, on the CPU
    moe = s["moe"]["decode"]
    assert moe["assignments"] + moe["assignments_away"] \
        == 8 * 2 * eng.metrics.kv_held_rows
    assert eng._prev.shape == (N + 4,)


def test_spans_carry_the_rows_the_pages_and_the_experts_counts(window):
    eng, _, _, events = window
    named = lambda n: [e for e in events if e["name"] == n]
    dispatch, emit = named("serving/decode/dispatch"), named("serving/decode/emit")
    assert dispatch and len(dispatch) == len(emit)
    for e in dispatch:
        # every slot's rows move, a kda layer each: 3 slots x 6 layers
        assert e["args"]["state_rows"] == "18"
        assert e["args"]["full_pages"].isdigit()
    for e in emit:
        assert {"experts", "assignments", "max_load", "away"} <= set(e["args"])
    m = eng.metrics
    assert m.moe_assignments_away["decode"] == sum(
        int(e["args"]["away"]) for e in emit)
    chunks = named("serving/prefill_chunk")
    assert len(chunks) == sum(-(-n // 8) for n in LENGTHS)
    assert {e["args"]["scan"] for e in chunks} == {"xla"}
    assert {e["args"]["attn"] for e in chunks} == {"xla"}
    assert {e["args"]["offset"] for e in chunks} == {0, 8, 16, 24, 32}


def test_the_named_scopes_are_in_the_programs(params):
    from deeperspeed_tpu.serving.engine import idle_slots

    eng = engine_for(params)
    N, bps = eng.scfg.num_slots, eng.scfg.blocks_per_slot
    text = eng._decode_step.lower(
        eng.params, eng.kv.k, eng.kv.v, jnp.asarray(idle_slots(N, bps)),
        jnp.zeros(N + 4, jnp.int32), None, eng.kv.state).as_text(
            debug_info=True)
    for scope in ("ds.kda.proj", "ds.kda.rule", "ds.kda.out", "ds.moe.route",
                  "ds.moe.experts", "ds.moe.shared", "ds.attn"):
        assert scope in text, scope


def test_what_cannot_be_served_is_refused_with_its_reason(params):
    with pytest.raises(ValueError, match="recurrent state"):
        engine_for(params, prefix_caching=True)
    with pytest.raises(ValueError, match="need cfg.kda"):
        GPTConfig(n_layer=1, mixer_types=("kda",))
    with pytest.raises(ValueError, match="moe_rule"):
        GPTConfig(moe_rule="hash")
    with pytest.raises(ValueError, match="moe_held"):
        GPTConfig(moe_num_experts=8, moe_held=(6, 4))
    scfg = ServingConfig.from_dict(SERVING)
    base = dict(n_head=2, d_model=32, gqa=GroupedAttnConfig(window=8),
                kda=KdaConfig(n_heads=2, head_k=16, head_v=16, low_rank=4))
    refused = [("kda",), ("kda", "lightning"), ("kda", "window_attn"),
               ("full_attn", "kda", "window_attn"), ("full_attn", "lightning")]
    for kinds in refused:
        with pytest.raises(NotImplementedError, match="share a stack"):
            PagedKVCache(GPTConfig(n_layer=len(kinds), mixer_types=kinds,
                                   **base), scfg)
    kv = PagedKVCache(GPTConfig(n_layer=2, mixer_types=("full_attn", "kda"),
                                **base), scfg)
    assert kv.k.shape[0] == 1 and set(kv.state) == {"kda", "conv"}


def test_the_config_of_the_stack():
    cfg = adapter.model_config(TOY)
    assert cfg.mixer_types == ("full_attn", "kda", "kda", "kda") * 2
    assert cfg.gqa.rotary is False and cfg.gqa.out_gate and not cfg.gqa.qk_norm
    assert cfg.kda.beta_scale == 2.0 and cfg.kda.d_conv == 4
    assert (cfg.moe_num_experts, cfg.moe_held, cfg.moe_shared, cfg.moe_rule) \
        == (16, (4, 4), 1, "sigmoid_bias")
    shapes = jax.eval_shape(
        lambda: mixers.init_params(jax.random.PRNGKey(0), cfg))
    want = jax.tree.map(lambda s: s.shape, ref.leaf_specs(TOY),
                        is_leaf=lambda s: hasattr(s, "std"))
    assert jax.tree.map(lambda a: a.shape, shapes) == want
