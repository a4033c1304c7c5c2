"""A stack of ``mamba_attn`` layers (Falcon-H1's: causal attention over
pages AND a Mamba-2 state-space mixer over a state row and a convolution
tail, side by side in every layer) through ``ServingEngine``, at small
widths on the CPU: 2 layers, hidden 64, 4 query heads over 2 key heads of
16, 4 state-space heads of 16, state 16, 2 groups, conv 4, chunk 8, vocab
320, float32. What the engine serves (chunked prefill, then decode through
pages and rows) is compared with the plain reference
``benchmark/refs/falcon_h1.py`` on seeded weights, and the parts with each
other."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest as mf
from benchmark.adapters import falcon_h1 as adapter
from benchmark.refs import falcon_h1 as ref
from benchmark.refs import init as rinit
from deeperspeed_tpu.models import mixers
from deeperspeed_tpu.models.gpt import (LAYER_KINDS, GPTConfig,
                                        MambaAttnConfig, make_gpt)
from deeperspeed_tpu.serving.engine import prefill_chunk_for
from deeperspeed_tpu.serving.kv_cache import PagedKVCache

import os

TOY = mf.load_json(os.path.join(mf.ROOT, "tests", "bench", "data", "configs",
                                "toy-h1.json"))
SERVING = {"num_slots": 3, "block_size": 8, "num_blocks": 61,
           "max_seq_len": 128, "prefill_chunk": 16,
           "prefill_token_budget": 16, "max_new_tokens": 32}
VOCAB = TOY["vocab_size"]


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
    # the program's float32 matmuls at the reference's precision (on the
    # CPU this is the default; it says what the tolerance below assumes)
    with jax.default_matmul_precision("highest"):
        out = eng.run()
    return eng, ps, [out[f"r{i}"] for i in range(len(ps))]


# ------------------------------------------------------------------ #
# the engine against the plain reference
# ------------------------------------------------------------------ #


@pytest.mark.parametrize("lengths", [
    # 16-token chunks: last chunks of 5, 1 and 2 tokens (1 and 2 lie under
    # the convolution's reach of 3: the tail reaches into the carried one)
    pytest.param((37, 17, 50), id="ragged_last_chunks_of_5_1_2"),
    pytest.param((33, 34, 5), id="last_chunks_of_1_2_and_a_short_prompt"),
    pytest.param((16, 32, 64), id="whole_chunks")])
def test_prefill_chunks_and_decode_agree_with_the_reference(
        params, reference, lengths):
    """Chunked prefill, then decode through pages, state rows and
    convolution tails: every served (greedy) token is the reference's best
    at its position. Tolerance 1e-4 of the logits' spread: both sides are
    float32 and differ in the order of their sums (the chunkwise form, the
    online softmax over pages) alone; bf16 in the program's place reads a
    hundred times that (the last test)."""
    eng, ps, outs = served(params, lengths)
    for p, o in zip(ps, outs):
        logits = np.asarray(reference.logits(params, p + o, len(p)))
        gap = logits.max(-1) - logits[np.arange(len(o)), o]
        assert gap.max() <= 1e-4 * logits.std(), (len(p), gap.max())
    assert eng.decode_compile_count == 1
    assert eng._chunk_step._cache_size() == 1       # one lowering, every chunk
    assert eng.prefill_compile_count == 0           # no bucketed prefill


def chunked_logits(eng, p, slot=1):
    """The chunk program driven by hand: the last chunk's logits."""
    cfg, scfg, kv = eng.cfg, eng.scfg, eng.kv
    blocks = kv.allocator.alloc(-(-(len(p) + 1) // scfg.block_size))
    table = jnp.asarray(blocks + [0] * (scfg.blocks_per_slot - len(blocks)),
                        jnp.int32)
    C = prefill_chunk_for(cfg, scfg)
    for lo in range(0, len(p), C):
        toks = np.zeros((1, C), np.int32)
        n = min(C, len(p) - lo)
        toks[0, :n] = p[lo:lo + n]
        logits, kv.k, kv.v, kv.kc, kv.state = eng._chunk_step(
            eng.params, kv.k, kv.v, kv.kc, kv.state, jnp.asarray(toks), table,
            np.int32(slot), np.int32(lo), np.int32(n))
    return np.asarray(logits)


@pytest.mark.parametrize("length", [49, 50, 53, 64])
def test_first_token_logits_of_a_chunked_prompt(params, reference, length):
    """The chunk program's own logits (the last real position of the last
    chunk: 1, 2, 5 and 16 real positions in it) against the reference's.
    atol 2e-5 on logits that spread 0.4: float32 sums in another order."""
    (p,) = prompts((length,), seed=3)
    got = chunked_logits(engine_for(params), p)
    want = np.asarray(reference.logits(params, p + [0], len(p)))[0]
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_whole_forward_agrees_with_the_reference(params, reference):
    """mixers.forward (no cache) is the program's own statement of the
    model; 37 positions are not a multiple of the SSD block of 8."""
    cfg = adapter.model_config(TOY)
    (p,) = prompts((37,), seed=5)
    got = mixers.forward(cfg, params, jnp.asarray([p], jnp.int32))[0]
    want = reference.logits(params, p + [0], 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("skip", ["ssm", "attn"])
def test_a_reference_without_a_branch_is_far_from_the_program(
        params, reference, skip):
    """The controls of the cell's check see each branch: leaving one out
    moves the logits by more than their spread."""
    (p,) = prompts((40,), seed=6)
    whole = np.asarray(reference.logits(params, p + [0], 1))
    part = np.asarray(ref.Forward(ref.make(TOY, skip=skip)).logits(
        params, p + [0], 1))
    assert np.abs(whole - part).max() > whole.std()


# ------------------------------------------------------------------ #
# recurrence = chunkwise; positions beyond n_valid
# ------------------------------------------------------------------ #


def ssm_parts(seed, T, dtype=jnp.float32):
    m = adapter.model_config(TOY).ssm
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    sp = {"conv_w": jax.random.normal(ks[0], (m.d_conv, m.conv_dim)) * 0.5,
          "conv_b": jax.random.normal(ks[1], (m.conv_dim,)) * 0.1,
          "dt_bias": jax.random.normal(ks[2], (m.n_heads,)) - 2.0,
          "A_log": jnp.log(jnp.arange(1.0, m.n_heads + 1)),
          "D": jnp.ones((m.n_heads,))}
    xbc = jax.random.normal(ks[3], (T, m.conv_dim)).astype(dtype)
    dt = jax.random.normal(ks[4], (T, m.n_heads))
    tail = jax.random.normal(ks[5], (m.d_conv - 1, m.conv_dim)).astype(dtype)
    h = jax.random.normal(ks[6], (m.n_heads, m.head_dim, m.d_state)) * 0.3
    return m, sp, xbc, dt, tail, h


@pytest.mark.parametrize("n_valid", [24, 13, 2, 1])
def test_decode_recurrence_is_the_chunkwise_form(n_valid):
    """``ssm_chunk`` (SSD in blocks of 8) against ``ssm_step`` token by
    token from the same tail and state: outputs, the tail and the state
    after ``n_valid`` positions. atol 1e-5: float32, other order of sums."""
    m, sp, xbc, dt, tail, h = ssm_parts(n_valid, 24)
    y, new_tail, new_h = mixers.ssm_chunk(m, sp, xbc, dt, tail, h, n_valid)
    t_row, h_row, outs = tail[None], h[None], []
    for t in range(n_valid):
        y_t, t_row, h_row = mixers.ssm_step(m, sp, xbc[t][None], dt[t][None],
                                            t_row, h_row)
        outs.append(y_t[0])
    np.testing.assert_allclose(np.asarray(new_h), np.asarray(h_row[0]), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(new_tail), np.asarray(t_row[0]))
    np.testing.assert_allclose(np.asarray(y[:n_valid]),
                               np.asarray(jnp.stack(outs)), atol=1e-5)


@pytest.mark.parametrize("n_valid", [16, 9, 2, 1, 0])
def test_positions_beyond_n_valid_leave_row_and_tail_bit_for_bit(n_valid):
    """Whatever stands at positions >= n_valid, the state and the tail
    that come out are the same BITS; with nothing valid they are the bits
    that went in."""
    m, sp, xbc, dt, tail, h = ssm_parts(3, 16)
    junk = jax.random.normal(jax.random.PRNGKey(9), xbc.shape) * 50.0
    keep = (jnp.arange(16) < n_valid)[:, None]
    a = mixers.ssm_chunk(m, sp, xbc, dt, tail, h, n_valid)
    b = mixers.ssm_chunk(m, sp, jnp.where(keep, xbc, junk),
                         jnp.where(keep, dt, 7.0), tail, h, n_valid)
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))
    np.testing.assert_array_equal(np.asarray(a[2]), np.asarray(b[2]))
    np.testing.assert_array_equal(np.asarray(a[0][:n_valid]),
                                  np.asarray(b[0][:n_valid]))
    if n_valid == 0:
        np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(tail))
        np.testing.assert_array_equal(np.asarray(a[2]), np.asarray(h))


@pytest.mark.parametrize("layer", [0, 1])
def test_row_update_kernel_against_its_oracle(layer):
    """ops/pallas/ssm_row_update, interpreted, against mixers.ssm_rows_xla:
    the layer's rows of live slots updated in place, every other row (the
    other layer's, an idle slot's) bit for bit as it was. atol 1e-5:
    float32, a sum over the state's lanes in another order."""
    from deeperspeed_tpu.ops.pallas.ssm_row_update import ssm_row_update

    L, N, Hs, P, Nst, G = 2, 3, 16, 16, 128, 2
    ks = jax.random.split(jax.random.PRNGKey(layer), 5)
    rows = jax.random.normal(ks[0], (L, N, Hs, P, Nst))
    decay = jax.nn.sigmoid(jax.random.normal(ks[1], (N, Hs)))
    dx = jax.random.normal(ks[2], (N, Hs, P))
    Bm = jax.random.normal(ks[3], (N, G, Nst))
    Cm = jax.random.normal(ks[4], (N, G, Nst))
    live = jnp.asarray([True, False, True])
    args = (rows, jnp.int32(layer), decay, dx, Bm, Cm, live)
    want_rows, want_y = mixers.ssm_rows_xla(*args)
    got_rows, got_y = ssm_row_update(*args, interpret=True)
    np.testing.assert_allclose(np.asarray(got_rows), np.asarray(want_rows),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_y)[[0, 2]],
                               np.asarray(want_y)[[0, 2]], atol=1e-4)
    np.testing.assert_array_equal(np.asarray(got_rows[1 - layer]),
                                  np.asarray(rows[1 - layer]))
    np.testing.assert_array_equal(np.asarray(got_rows[layer, 1]),
                                  np.asarray(rows[layer, 1]))
    assert (np.asarray(got_rows[layer, 0]) != np.asarray(rows[layer, 0])).any()


def test_a_chunk_leaves_other_slots_rows_and_tails_bit_for_bit(params):
    """The chunk program writes ONE slot's row and tail; the decode step
    leaves the rows of idle slots and of slots still being chunked in."""
    eng = engine_for(params)
    before = jax.tree.map(
        lambda a: np.asarray(a) + 0.5, eng.kv.state)
    eng.kv.state = jax.tree.map(jnp.asarray, before)
    (p,) = prompts((21,), seed=8)
    chunked_logits(eng, p, slot=1)
    after = jax.tree.map(np.asarray, eng.kv.state)
    for key in ("ssm", "conv"):
        np.testing.assert_array_equal(after[key][:, 0], before[key][:, 0])
        np.testing.assert_array_equal(after[key][:, 2], before[key][:, 2])
        assert (after[key][:, 1] != before[key][:, 1]).any()


# ------------------------------------------------------------------ #
# the scheduler: pages, a state row and a tail a slot
# ------------------------------------------------------------------ #


def test_a_slot_served_twice_answers_as_a_fresh_engine(params):
    """The second request takes the slot, row and tail the first left
    (one slot): it answers as it does alone in a new engine."""
    ps = prompts((40, 29), seed=4)
    eng = engine_for(params, num_slots=1)
    for i, p in enumerate(ps):
        eng.submit(p, max_new_tokens=8, request_id=f"r{i}")
    out = eng.run()
    fresh = engine_for(params, num_slots=1)
    fresh.submit(ps[1], max_new_tokens=8, request_id="alone")
    assert out["r1"] == fresh.run()["alone"]
    assert eng.metrics.summary()["state_resets"] == 2


def test_preempt_and_readmit_gives_identical_tokens(params):
    """A pool too small for all three: the youngest is preempted while it
    decodes, its row and tail are rebuilt by re-prefilling prompt +
    generated (never resumed), and every request's tokens are those of a
    roomy pool."""
    lengths = (50, 60, 45)
    _, _, roomy = served(params, lengths, new=24)
    eng, _, tight = served(params, lengths, new=24, num_blocks=24)
    assert eng.metrics.summary()["preemptions"] >= 1
    assert tight == roomy
    assert eng.kv.allocator.num_allocated == 0


def test_counters_and_cache_shapes_of_a_served_window(params):
    eng, _, _ = served(params, (37, 17, 50))
    s = eng.metrics.summary()
    L, N, m = 2, 3, eng.cfg.ssm
    rows = L * N * m.n_heads * m.head_dim * m.d_state * 4
    tails = L * N * (m.d_conv - 1) * m.conv_dim * 4
    assert s["state_bytes"] == rows + tails
    assert s["state_bytes_per_step"] == 2 * (rows + tails)
    assert s["state_resets"] == 3 and 0 < s["chunk_gap_share"] < 1
    kv = eng.kv
    assert kv.kc is None and set(kv.state) == {"ssm", "conv"}
    assert kv.k.shape == (L, 61, 2, 8, 16)       # pages in the mixed layout
    assert kv.state["ssm"].shape == (L, N, 4, 16, 16)
    assert kv.state["conv"].shape == (L, N, 3, 64 + 2 * 2 * 16)


def test_refusals_name_what_they_refuse(params):
    with pytest.raises(ValueError, match="a layer that keeps recurrent state"):
        engine_for(params, prefix_caching=True)
    with pytest.raises(ValueError, match="prefill_chunk 12"):
        engine_for(params, prefill_chunk=12)
    with pytest.raises(ValueError) as e:
        GPTConfig(n_layer=2, n_head=2, d_model=32, vocab_size=64,
                  mixer_types=("mamba_attn", "attention"))
    assert all(kind in str(e.value) for kind in LAYER_KINDS)
    with pytest.raises(ValueError, match="mamba_attn layers need cfg.ssm"):
        GPTConfig(n_layer=1, n_head=2, d_model=32, vocab_size=64,
                  mixer_types=("mamba_attn",))
    cfg = dataclasses.replace(adapter.model_config(TOY),
                              mixer_types=("mamba_attn", "lightning"))
    with pytest.raises(NotImplementedError, match="no other kind"):
        PagedKVCache(cfg, engine_for(params).scfg)


def test_the_config_of_the_stack(params):
    cfg = adapter.model_config(TOY)
    assert cfg.head_dim == 16 and cfg.d_model // cfg.n_head == 16
    assert dataclasses.replace(cfg, head_size=0).head_dim == 16
    assert dataclasses.replace(cfg, head_size=8).qkv_dim == (4 + 2 * 2) * 8
    assert mixers.layer_runs(cfg) == [("mamba_attn", 0, 2)]
    assert cfg.ssm == MambaAttnConfig(
        n_heads=4, head_dim=16, d_state=16, n_groups=2, d_conv=4, chunk=8,
        ssm_in=0.25, ssm_mult=(0.35, 0.25, 0.18, 0.5, 0.35), ssm_out=0.3,
        attn_in=1.0, attn_out=0.5, key=0.3, mlp_gate=0.5, mlp_out=0.25)
    assert cfg.ssm.conv_dim == 128 and cfg.ssm.proj_dim == 64 + 128 + 4
    init_fn, _, loss_fn, _ = make_gpt(cfg)
    tree = init_fn(jax.random.PRNGKey(0))
    assert set(tree) == {"embed", "final_norm", "lm_head", "mamba_attn"}
    assert jax.tree.map(jnp.shape, tree["mamba_attn"]) == jax.tree.map(
        jnp.shape, params["mamba_attn"])
    with pytest.raises(NotImplementedError, match="served only"):
        loss_fn(None, None)


def test_bf16_in_the_programs_place_fails_the_float32_tolerance(
        params, reference):
    """The tolerances above are tight enough to see the precision: the
    same program on weights rounded to bfloat16 (the CPU backend has no
    bfloat16 dot to compute in it as well) reads a hundred times over
    them."""
    (p,) = prompts((50,), seed=3)
    rounded = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
    got = chunked_logits(engine_for(rounded), p)
    want = np.asarray(reference.logits(params, p + [0], len(p)))[0]
    assert np.abs(got - want).max() > 100 * 2e-5
