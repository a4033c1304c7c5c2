"""A stack of mixed layers (MiniCPM-SALA's kinds: ``minicpm4`` block-sparse
attention over pages, ``lightning`` linear attention over a state row a
slot) through ``ServingEngine``, at small widths on the CPU: 2 sparse + 6
lightning layers, ``dense_len`` 64, block 8, top-4. What the engine serves
(chunked prefill, then decode through the caches) is compared with the
plain reference ``benchmark/refs/minicpm_sala.py`` on seeded weights, and
the parts with each other."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.adapters import minicpm_sala as adapter
from benchmark.refs import init as rinit
from benchmark.refs import minicpm_sala as ref
from deeperspeed_tpu.models import mixers
from deeperspeed_tpu.models.gpt import GPTConfig, SparseAttnConfig, make_gpt
from deeperspeed_tpu.ops.pallas.lightning_chunk import lightning_chunk
from deeperspeed_tpu.ops.pallas.paged_sparse_attn import paged_sparse_attn
from deeperspeed_tpu.serving.engine import prefill_chunk_for
from deeperspeed_tpu.serving.kv_cache import paged_sparse_attend_xla

PERIOD = ["minicpm4"] + ["lightning-attn"] * 3
TOY = {
    "family": "minicpm_sala", "hidden_size": 64, "intermediate_size": 128,
    "vocab_size": 96, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "num_hidden_layers": 8, "num_layers": 8, "mixer_types": PERIOD * 2,
    "scale_emb": 12, "scale_depth": 1.4, "dim_model_base": 16,
    "max_position_embeddings": 4096, "tie_word_embeddings": False,
    "compute_dtype": "float32",
    "sparse_config": {"block_size": 8, "topk": 4, "kernel_size": 4,
                      "kernel_stride": 2, "init_blocks": 1, "window_size": 16,
                      "dense_len": 64},
    "weights": {"std": 0.05, "sparse_qkv_std": 0.2, "sparse_qk_gain": 1.5},
}
SERVING = {"num_slots": 3, "block_size": 8, "num_blocks": 121,
           "max_seq_len": 256, "prefill_chunk": 16,
           "prefill_token_budget": 16, "max_new_tokens": 32}


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
    return [rng.integers(0, 96, n).tolist() for n in lengths]


def served(params, lengths, new=12, **serving):
    eng = engine_for(params, **serving)
    ps = prompts(lengths)
    for i, p in enumerate(ps):
        eng.submit(p, max_new_tokens=new, request_id=f"r{i}")
    out = eng.run()
    return eng, ps, [out[f"r{i}"] for i in range(len(ps))]


# ------------------------------------------------------------------ #
# the engine against the plain reference
# ------------------------------------------------------------------ #


@pytest.mark.parametrize("lengths", [
    pytest.param((100, 37, 200), id="across_dense_len"),
    pytest.param((5, 16, 63), id="the_dense_rule_alone"),
    pytest.param((64, 65, 129), id="at_the_boundaries")])
def test_prefill_chunks_and_decode_agree_with_the_reference(
        params, reference, lengths):
    """Chunked prefill, then decode through pages, pooled keys and state
    rows: every served (greedy) token is the reference's best at its
    position, within a rounding of the logits' spread."""
    eng, ps, outs = served(params, lengths)
    for p, o in zip(ps, outs):
        logits = np.asarray(reference.logits(params, p + o, len(p)))
        gap = logits.max(-1) - logits[np.arange(len(o)), o]
        assert gap.max() <= 1e-4 * logits.std(), (len(p), gap.max())
    assert eng.decode_compile_count == 1
    assert eng._chunk_step._cache_size() == 1       # one lowering, every chunk
    assert eng.prefill_compile_count == 0           # no bucketed prefill


def test_first_token_logits_of_a_chunked_prompt(params, reference):
    """The chunk program's own logits (the last real position of the last
    chunk, a prompt that ends mid-chunk and beyond dense_len) against the
    reference's."""
    eng = engine_for(params)
    (p,) = prompts((117,), seed=3)
    cfg, scfg, kv = eng.cfg, eng.scfg, eng.kv
    blocks = kv.allocator.alloc(-(-118 // 8))
    table = jnp.asarray(blocks + [0] * (scfg.blocks_per_slot - len(blocks)),
                        jnp.int32)
    C = prefill_chunk_for(cfg, scfg)
    for lo in range(0, len(p), C):
        toks = np.zeros((1, C), np.int32)
        n = min(C, len(p) - lo)
        toks[0, :n] = p[lo:lo + n]
        logits, kv.k, kv.v, kv.kc, kv.state = eng._chunk_step(
            eng.params, kv.k, kv.v, kv.kc, kv.state, jnp.asarray(toks), table,
            np.int32(1), np.int32(lo), np.int32(n))
    want = np.asarray(reference.logits(params, p + [0], len(p)))[0]
    np.testing.assert_allclose(np.asarray(logits), want, atol=2e-5)


def test_the_whole_forward_agrees_with_the_reference(params, reference):
    """mixers.forward (each mixer by its definition, no cache) is the
    program's own statement of the model."""
    cfg = adapter.model_config(TOY)
    (p,) = prompts((136,), seed=5)
    got = mixers.forward(cfg, params, jnp.asarray([p], jnp.int32))[0]
    want = reference.logits(params, p + [0], 1)
    np.testing.assert_allclose(np.asarray(got[:-1]), np.asarray(want)[:-1],
                               atol=2e-5)


# ------------------------------------------------------------------ #
# chunked = unchunked; recurrence = chunkwise
# ------------------------------------------------------------------ #


def test_chunked_and_unchunked_prefill_leave_the_same_state_and_pages(params):
    """One prompt in chunks of 16 and in chunks of 32 (a window of 32 and
    top-8 allow both): the same first token, state rows, pages, pooled
    keys."""
    toy = dict(TOY, sparse_config=dict(TOY["sparse_config"], window_size=32,
                                       topk=8))
    (p,) = prompts((150,), seed=9)
    got = []
    for chunk in (16, 32):
        eng = adapter.serving_engine(toy, params, {
            **SERVING, "prefill_chunk": chunk, "prefill_token_budget": None})
        eng.submit(p, max_new_tokens=3, request_id="r")
        eng.step()                            # every chunk, one decode step
        blocks = list(eng.sched.slot_blocks[0])
        n = len(p) // 8                       # whole pages of the prompt
        got.append((eng.get("r").output, np.asarray(eng.kv.state[:, 0]),
                    np.asarray(eng.kv.k[:, blocks[:n]]),
                    np.asarray(eng.kv.v[:, blocks[:n]]),
                    # a page's last window ends in the next page
                    np.asarray(eng.kv.kc[:, blocks[:n - 1]])))
    assert got[0][0] == got[1][0]
    for a, b in zip(got[0][1:], got[1][1:]):
        np.testing.assert_allclose(a, b, atol=1e-5)
    assert np.abs(got[0][1]).max() > 0 and np.abs(got[0][4]).max() > 0


@pytest.mark.parametrize("n_valid", [64, 37, 0])
def test_decode_recurrence_is_the_chunkwise_form(n_valid):
    C, H, Dh = 64, 4, 128
    ks = jax.random.split(jax.random.PRNGKey(n_valid), 4)
    q, k, v = (jax.random.normal(kk, (C, H, Dh)) for kk in ks[:3])
    s_in = jax.random.normal(ks[3], (H, Dh, Dh)) * 0.1
    slopes = mixers.lightning_slopes(H)
    o, S = mixers.lightning_chunk_xla(q, k, v, s_in, slopes, n_valid, block=16)
    state, outs = s_in[None], []
    for t in range(n_valid):
        o_t, state = mixers.lightning_step(q[t][None], k[t][None], v[t][None],
                                           state, slopes)
        outs.append(o_t[0])
    np.testing.assert_allclose(np.asarray(S), np.asarray(state[0]), atol=1e-4)
    if n_valid:
        np.testing.assert_allclose(np.asarray(o[:n_valid]),
                                   np.asarray(jnp.stack(outs)), atol=1e-4)


# ------------------------------------------------------------------ #
# the kernels, interpreted, against their XLA oracles
# ------------------------------------------------------------------ #


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_selected_pages_kernel_against_its_oracle_at_two_key_heads(dtype):
    L, nb, Hkv, bs, Dh, R, G, P = 2, 20, 2, 16, 128, 6, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    kp = jax.random.normal(ks[0], (L, nb, Hkv, bs, Dh)).astype(dtype)
    vp = jax.random.normal(ks[1], (L, nb, Hkv, bs, Dh)).astype(dtype)
    q = jax.random.normal(ks[2], (R, G, Dh)).astype(dtype)
    pages = jnp.asarray(np.random.default_rng(0).integers(1, nb, (R, P)),
                        jnp.int32)
    # nothing to read, part of a page, whole pages, all of the list
    n_tokens = jnp.asarray([0, 5, 16, 100, 128, 77], jnp.int32)
    heads = jnp.asarray([0, 1, 0, 1, 0, 1], jnp.int32)
    m0 = jax.random.normal(ks[3], (R, G))
    l0 = jnp.full((R, G), 1.5)
    acc0 = jax.random.normal(ks[4], (R, G, Dh))
    args = (kp, vp, jnp.int32(1), q, heads, pages, n_tokens, m0, l0, acc0)
    want = paged_sparse_attend_xla(*args).astype(jnp.float32)
    got = paged_sparse_attn(*args, interpret=True).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # a row with nothing to read returns what it came with
    np.testing.assert_allclose(np.asarray(got[0]),
                               np.asarray((acc0 / l0[..., None])[0].astype(
                                   dtype).astype(jnp.float32)), atol=1e-6)


def _row_world(P, counts, dtype, bs=64, G=4, seed=0, stray=()):
    """Pools of 40 pages of ``bs`` rows at 2 key heads; a row a count of
    ``counts``, its key head by turns, its list ``P`` random pages of the
    pool: what lies past a count names a real page, as a prompt chunk's
    32nd entry does. The entries ``stray`` (row, place) name no page."""
    L, nb, Hkv, Dh, R = 2, 40, 2, 128, len(counts)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    kp = jax.random.normal(ks[0], (L, nb, Hkv, bs, Dh)).astype(dtype)
    vp = jax.random.normal(ks[1], (L, nb, Hkv, bs, Dh)).astype(dtype)
    q = jax.random.normal(ks[2], (R, G, Dh)).astype(dtype)
    pages = np.random.default_rng(seed).integers(1, nb, (R, P))
    for at in stray:
        pages[at] = nb + 3
    return (kp, vp, jnp.int32(1), q, jnp.arange(R, dtype=jnp.int32) % Hkv,
            jnp.asarray(pages, jnp.int32), jnp.asarray(counts, jnp.int32),
            jax.random.normal(ks[3], (R, G)), jnp.full((R, G), 1.5),
            jax.random.normal(ks[4], (R, G, Dh)))


# ``pp``: the pages of a copy-chunk in bf16 and in float32
ROW_CASES = {
    # a prompt chunk's rows: ONE copy-chunk a row, the 32nd page not counted
    "a_chunk_row_is_one_copy_chunk": dict(P=32, counts=(1984,) * 5, pp=(32, 16)),
    # a decode step's width: four copy-chunks of 32 pages; counts that end
    # inside a page, on a copy-chunk's edge, one row past it, with the list
    "a_decode_row_is_up_to_four": dict(
        P=128, counts=(5000, 2048, 2049, 8192, 7, 6143), pp=(32, 16)),
    # the largest divisor of the width under the cap is not the cap
    "a_width_the_cap_does_not_divide": dict(
        P=48, counts=(3072, 1536, 1537, 100), pp=(24, 16)),
    # the prefetch skips a row with nothing to read, wherever it stands
    "idle_rows_first_inside_and_last": dict(
        P=32, counts=(0, 0, 1984, 0, 70, 0, 0, 2048, 0), pp=(32, 16)),
    # no copy leaves the pool: a page past its end reads as its last, as
    # the oracle's gather clamps it (the kernel runs without Mosaic's own
    # bounds checks)
    "a_page_past_the_pool_is_its_last": dict(
        P=32, counts=(1984, 2048, 640), pp=(32, 16),
        stray=((0, 3), (1, 31), (2, 9), (2, 20))),
    # more rows than one call's lists fit scalar memory (here: room for
    # the lists of 4 rows): ``lax.map`` over 3 calls
    "more_rows_than_a_call_takes": dict(
        P=32, counts=(1984, 0, 517, 2048) * 3, pp=(32, 16), calls=3),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", ROW_CASES)
def test_row_form_keeps_a_rows_list_in_flight_against_the_oracle(
        case, dtype, monkeypatch):
    """``paged_sparse_attn`` (a row a LIST of one key head's pages; a
    copy-chunk as many pages as 2,048 positions and the buffers' room
    allow, its copies waited for at once) against
    ``paged_sparse_attend_xla``."""
    from deeperspeed_tpu.ops.pallas import paged_sparse_attn as kernel

    spec = dict(ROW_CASES[case])
    pp, calls = spec.pop("pp"), spec.pop("calls", 1)
    args = _row_world(dtype=dtype, **spec)
    kp, pages, counts = args[0], args[5], np.asarray(args[6])
    R, P = pages.shape
    if calls > 1:       # no other case has 12 rows: the trace is this one's
        monkeypatch.setattr(kernel, "_SMEM_BUDGET", 4 * P * 4)
    # float32 pages are twice the bytes: half as many fit the buffers
    assert kernel._pages_per_row_chunk(P, kp) == pp[dtype == jnp.float32]
    assert R // kernel.rows_per_call(R, P) == calls
    want = paged_sparse_attend_xla(*args).astype(jnp.float32)
    got = paged_sparse_attn(*args, interpret=True).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5 if dtype == jnp.float32 else 2e-2)
    *_, m0, l0, acc0 = args
    came_with = (acc0 / l0[..., None]).astype(dtype).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got)[counts == 0],
                               np.asarray(came_with)[counts == 0], atol=1e-6)


def test_the_other_forms_copy_chunks_are_what_they_were():
    """The row form's copy-chunk has a rule of its own: the slot form's
    (four cells' decode steps) and the width of a chunk row's list still
    follow ``_CHUNK_TOKENS``. The numbers are the parent's (PR 45)."""
    from deeperspeed_tpu.ops.pallas import paged_sparse_attn as kernel
    from deeperspeed_tpu.serving.kv_cache import chosen_list_width

    pool = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    assert kernel._CHUNK_TOKENS == 512
    for name, k_pool, P, pages in [
            ("the byte cell", pool(8, 1025, 32, 64, 128), 64, 2),
            ("the chat cell", pool(6, 2305, 4, 64, 128), 48, 8),
            ("the code cell, every key", pool(2, 16385, 4, 64, 128), 512, 8),
            ("the code cell, the ring", pool(6, 513, 4, 64, 128), 16, 8),
            ("the reasoning cell", pool(1, 15361, 8, 64, 128), 320, 8),
            ("the long-document cell", pool(4, 6241, 2, 64, 128), 128, 8)]:
        assert kernel._pages_per_slot_chunk(P, k_pool) == pages, name
    assert chosen_list_width(SparseAttnConfig()) == 32


def _slot_world(Hkv, G, dtype, counts, carry="plain", seed=0):
    """Pools of 24 pages of 64 rows, lists 16 wide (two copy-chunks at 4
    and at 2 key heads, eight at 32), slots that hold ``counts`` rows;
    entries past a count name the null page, page 0."""
    L, nb, bs, Dh, P = 2, 24, 64, 128, 16
    N = len(counts)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    kp = jax.random.normal(ks[0], (L, nb, Hkv, bs, Dh)).astype(dtype)
    vp = jax.random.normal(ks[1], (L, nb, Hkv, bs, Dh)).astype(dtype)
    q = jax.random.normal(ks[2], (N, Hkv, G, Dh)).astype(dtype)
    n = np.asarray(counts)
    pages = np.random.default_rng(seed).integers(1, nb, (N, P))
    pages = np.where(np.arange(P)[None] * bs < n[:, None], pages, 0)
    # a score over 128 entries of unit normals is a few units: what was
    # attended over before holds all of the softmax, or none of it
    m0 = jax.random.normal(ks[3], (N, Hkv, G)) + {
        "plain": 0.0, "dominates": 60.0, "vanishes": -60.0}[carry]
    l0 = jnp.full((N, Hkv, G), 1.5)
    acc0 = jax.random.normal(ks[4], (N, Hkv, G, Dh))
    return (kp, vp, jnp.int32(1), q, jnp.asarray(pages, jnp.int32),
            jnp.asarray(n, jnp.int32), m0, l0, acc0)


# ends mid-page in the second copy-chunk, nothing (between two live
# slots), a page boundary that is a copy-chunk's too, the whole list, a
# few rows of one page, nothing
LIVE_AND_IDLE = (64 * 9 + 37, 0, 64 * 8, 64 * 16, 5, 0)
SLOT_CASES = [
    pytest.param(Hkv, G, dtype, LIVE_AND_IDLE, "plain",
                 id=f"{Hkv}x{G}_{jnp.dtype(dtype).name}")
    for Hkv, G in ((32, 1), (4, 5), (2, 16))
    for dtype in (jnp.float32, jnp.bfloat16)
] + [
    pytest.param(Hkv, G, jnp.bfloat16, LIVE_AND_IDLE, carry,
                 id=f"{Hkv}x{G}_what_came_before_{carry}")
    for Hkv, G in ((32, 1), (4, 5), (2, 16))
    for carry in ("dominates", "vanishes")
] + [
    pytest.param(Hkv, G, jnp.float32, (0, 0, 0), "plain",
                 id=f"{Hkv}x{G}_every_slot_idle")
    for Hkv, G in ((32, 1), (4, 5), (2, 16))
]


@pytest.mark.parametrize("Hkv,G,dtype,counts,carry", SLOT_CASES)
def test_slot_form_against_the_oracle_over_the_broadcast_rows(
        Hkv, G, dtype, counts, carry):
    """``paged_sparse_attn_slots`` (a row a SLOT, a copy a whole page) is
    ``paged_sparse_attend_xla`` over rows (slot, key head) that each carry
    the slot's list and count."""
    from deeperspeed_tpu.ops.pallas.paged_sparse_attn import (
        paged_sparse_attn_slots)
    from deeperspeed_tpu.serving.kv_cache import slots_as_rows

    args = _slot_world(Hkv, G, dtype, counts, carry)
    want = slots_as_rows(paged_sparse_attend_xla, *args).astype(jnp.float32)
    got = paged_sparse_attn_slots(*args, interpret=True).astype(jnp.float32)
    assert got.shape == (len(counts), Hkv, G, 128)
    # bf16: an output of a few units rounds to 2 ** -6, where two orders
    # of one float32 sum fall on either side of a rounding
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5 if dtype == jnp.float32 else 2e-2)
    *_, m0, l0, acc0 = args
    came_with = (acc0 / l0[..., None]).astype(dtype).astype(jnp.float32)
    idle = np.asarray(counts) == 0
    # a slot with nothing to read returns what it came with, and so does
    # one whose pages weigh nothing beside it
    keeps = idle | (carry == "dominates")
    np.testing.assert_allclose(np.asarray(got)[keeps],
                               np.asarray(came_with)[keeps], atol=1e-6)
    if carry == "vanishes":
        assert np.abs(np.asarray(got - came_with)[~idle]).max() > 0.1


@pytest.mark.parametrize("kind,Hkv,G", [("eva", 32, 1), ("mamba_attn", 4, 5)])
def test_decode_attend_all_is_the_same_through_either_form(kind, Hkv, G):
    """``decode_attend_all`` hands a slot's list on unbroadcast: through
    the slot form (interpreted) and through the XLA form over the
    broadcast rows it returns one context, the new token's own key
    counted in both."""
    from deeperspeed_tpu.ops.pallas.paged_sparse_attn import (
        paged_sparse_attn_slots)
    from deeperspeed_tpu.serving import kv_cache as kvc

    kp, vp, layer, qg, pages, n, *_ = _slot_world(
        Hkv, G, jnp.bfloat16, LIVE_AND_IDLE, seed=3)
    N, Dh = qg.shape[0], qg.shape[-1]
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    k_row, v_row = (jax.random.normal(k, (N, Hkv, Dh)).astype(kp.dtype)
                    for k in ks)
    q = qg.reshape(N, 1, Hkv * G, Dh)
    shapes = []

    def rows_xla(k_pool, v_pool, layer, q_rows, row_head, pages, *rest):
        shapes.append((q_rows.shape, pages.shape))
        return paged_sparse_attend_xla(k_pool, v_pool, layer, q_rows,
                                       row_head, pages, *rest)

    ctx = [kvc.decode_attend_all(kp, vp, layer, q, k_row, v_row, pages, n,
                                 attend)
           for attend in (
               functools.partial(paged_sparse_attn_slots, interpret=True),
               functools.partial(kvc.slots_as_rows, rows_xla))]
    assert ctx[0].shape == (N, 1, Hkv * G, Dh)
    assert shapes == [((N * Hkv, G, Dh), (N * Hkv, 16))]
    np.testing.assert_allclose(np.asarray(ctx[0].astype(jnp.float32)),
                               np.asarray(ctx[1].astype(jnp.float32)),
                               atol=2e-2)


def test_only_a_list_a_slot_takes_the_slot_form(monkeypatch):
    """On one TPU ``decode_attend_all``'s read is the kernel whose row is
    a slot, and a selecting layer's (``sparse_decode_attend``,
    ``sparse_chunk_attend``: lists that differ by key head) stays the
    kernel whose row is a (position, key head); under a mesh and off the
    TPU both are the XLA form."""
    from jax.sharding import Mesh

    from deeperspeed_tpu.ops import kernel_config
    from deeperspeed_tpu.ops.pallas import paged_sparse_attn as kernel
    from deeperspeed_tpu.serving import kv_cache as kvc

    sds = jax.ShapeDtypeStruct
    eva = sds((8, 1025, 32, 64, 128), jnp.bfloat16)
    h1 = sds((6, 2305, 4, 64, 128), jnp.bfloat16)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))

    def xla_rows(attend):
        return (attend.func is kvc.slots_as_rows
                and attend.args == (paged_sparse_attend_xla,))

    assert xla_rows(kvc.slot_attend_for(eva, 32, (16, 64), None))   # the CPU
    assert not kvc.takes_slot_form(eva, 32, (16, 64), None)
    monkeypatch.setattr(kernel_config, "on_tpu", lambda: True)
    for pool, H, lists in ((eva, 32, (16, 64)), (h1, 20, (48, 48))):
        assert kvc.slot_attend_for(pool, H, lists, None) \
            is kernel.paged_sparse_attn_slots
        assert kvc.sparse_attend_for(pool, H, None) is kernel.paged_sparse_attn
        assert xla_rows(kvc.slot_attend_for(pool, H, lists, mesh))
        assert not kvc.takes_slot_form(pool, H, lists, mesh)
    # a page too large for the buffers (4 MiB of them) keeps a row a
    # (slot, key head), through the row kernel
    wide = sds((2, 65, 128, 64, 128), jnp.bfloat16)
    attend = kvc.slot_attend_for(wide, 128, (16, 64), None)
    assert attend.func is kvc.slots_as_rows
    assert attend.args == (kernel.paged_sparse_attn,)
    sala = sds((8, 6241, 2, 64, 128), jnp.bfloat16)
    assert kvc.sparse_attend_for(sala, 32, None) is kernel.paged_sparse_attn


@pytest.mark.parametrize("n_valid", [64, 37])
def test_lightning_kernel_against_its_oracle(n_valid):
    C, H, Dh = 64, 4, 128
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q, k, v = (jax.random.normal(kk, (C, H, Dh)).astype(jnp.bfloat16)
               for kk in ks[:3])
    s_in = jax.random.normal(ks[3], (H, Dh, Dh)) * 0.1
    slopes = mixers.lightning_slopes(H)
    o1, S1 = mixers.lightning_chunk_xla(q, k, v, s_in, slopes, n_valid, block=16)
    o2, S2 = lightning_chunk(q, k, v, s_in, slopes, n_valid, block=16,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(o2[:n_valid]),
                               np.asarray(o1[:n_valid]), atol=1e-4)
    np.testing.assert_allclose(np.asarray(S2), np.asarray(S1), atol=1e-4)


# ------------------------------------------------------------------ #
# the scheduler: a state row as well as blocks
# ------------------------------------------------------------------ #


def test_preempt_and_readmit_gives_identical_tokens(params):
    """A pool too small for all three: the youngest is preempted while it
    decodes, its state row is rebuilt by re-prefilling prompt + generated
    (never resumed), and every request's tokens are those of a roomy pool."""
    lengths = (90, 100, 80)
    _, _, roomy = served(params, lengths, new=24)
    eng, _, tight = served(params, lengths, new=24, num_blocks=40)
    assert eng.metrics.summary()["preemptions"] >= 1
    assert tight == roomy
    assert eng.kv.allocator.num_allocated == 0


def test_a_slot_is_cleared_by_who_enters_it(params, reference):
    """The second request takes the slot (and state row) the first left."""
    eng = engine_for(params, num_slots=1)
    ps = prompts((70, 90), seed=4)
    for i, p in enumerate(ps):
        eng.submit(p, max_new_tokens=6, request_id=f"r{i}")
    out = eng.run()
    for i, p in enumerate(ps):
        logits = np.asarray(reference.logits(params, p + out[f"r{i}"], len(p)))
        assert (logits.argmax(-1) == np.asarray(out[f"r{i}"])).all()


def test_prefix_caching_refuses_recurrent_layers(params):
    with pytest.raises(ValueError, match="prefix_caching cannot serve"):
        engine_for(params, prefix_caching=True)


def test_a_chunk_size_the_model_cannot_take_is_refused(params):
    with pytest.raises(ValueError, match="prefill_chunk 24"):
        engine_for(params, prefill_chunk=24)
    with pytest.raises(ValueError, match="a page is one selection block"):
        engine_for(params, block_size=16, prefill_chunk=16)


def test_training_this_stack_raises_and_a_classic_one_is_untouched():
    cfg = adapter.model_config(TOY)
    init_fn, apply_fn, loss_fn, specs = make_gpt(cfg)
    assert set(init_fn(jax.random.PRNGKey(0))) == {
        "embed", "final_norm", "lm_head", "sparse", "lightning"}
    with pytest.raises(NotImplementedError, match="served only"):
        loss_fn(None, None)
    classic = GPTConfig(n_layer=2, n_head=2, d_model=32, vocab_size=64)
    assert classic.classic and classic.layer_kinds == ("attention",) * 2
    assert mixers.layer_runs(classic) == [("attention", 0, 2)]
    # attention layers keep their own tree and pool layout: no mixing
    with pytest.raises(ValueError, match="mixer_types must name"):
        GPTConfig(n_layer=2, n_head=2, d_model=32, vocab_size=64,
                  mixer_types=("attention", "lightning"))


def test_layer_runs_and_specs_of_the_published_cut():
    import json
    import os

    from benchmark import manifest as mf

    config = mf.load_json(os.path.join(mf.BENCH_DIR, "configs",
                                       "minicpm-sala.json"))
    cfg = adapter.model_config(config)
    assert mixers.layer_runs(cfg) == [
        ("minicpm4", 0, 1), ("lightning", 0, 6), ("minicpm4", 1, 2),
        ("lightning", 6, 4), ("minicpm4", 3, 1), ("lightning", 10, 2)]
    assert cfg.count("lightning") == 12 and cfg.count("minicpm4") == 4
    assert not cfg.classic and cfg.count("attention") == 0
    assert cfg.residual_scale == pytest.approx(0.2475, abs=1e-4)
    assert cfg.logit_scale == 1 / 16 and cfg.scale_emb == 12
    assert dataclasses.asdict(cfg.sparse) == config["sparse_config"]
    assert json.dumps(config["reduced"]) == '["num_layers", "mixer_types"]'


def test_counters_of_a_served_window(params):
    eng, _, _ = served(params, (100, 37, 200))
    s = eng.metrics.summary()
    assert 0 < s["chunk_gap_share"] < 1
    assert 0 < s["kv_selected_page_frac"] <= 1
    assert s["state_bytes"] == eng.kv.state.nbytes == 6 * 3 * 4 * 16 * 16 * 4
    # beyond dense_len a chunk's selections name topk blocks less its own
    assert eng.chunk_pages_read(0) == eng.chunk_pages_read(48) == 0
    assert eng.chunk_pages_read(64) == 2 * 2 * (16 * 4 - 8 * 3)
    # of those the rows' lists name the chosen ones: top-4 less 3 forced
    assert eng.chunk_pages_listed(0) == eng.chunk_pages_listed(48) == 0
    assert eng.chunk_pages_listed(64) == 2 * 2 * 16 * 1
    sparse_chunks = (100 // 16 - 4 + 1) + (200 // 16 - 4 + 1)
    assert eng.metrics.chunk_named_pages == sparse_chunks * 160
    assert eng.metrics.chunk_listed_pages == sparse_chunks * 64
    assert s["chunk_listed_page_share"] == pytest.approx(0.4)


def test_sparse_config_is_validated():
    with pytest.raises(ValueError, match="topk"):
        SparseAttnConfig(block_size=8, topk=2, kernel_size=4, kernel_stride=2,
                         window_size=16, dense_len=64)
    with pytest.raises(ValueError, match="kernel_size"):
        SparseAttnConfig(block_size=8, kernel_size=6, kernel_stride=2)
    with pytest.raises(ValueError, match="mixer_types"):
        GPTConfig(n_layer=2, mixer_types=("lightning",))


# ------------------------------------------------------------------ #
# the selector: a mask by a threshold, a list by a prefix count, no sort
# ------------------------------------------------------------------ #

SEL = SparseAttnConfig(block_size=8, topk=8, kernel_size=4, kernel_stride=2,
                       init_blocks=1, window_size=16, dense_len=64)


def sorted_selection(b, q_block, sp):
    """The selector as PR 27 wrote it, kept here as the oracle: the forced
    blocks at 1e9, a full ``lax.top_k``, validity from the value."""
    M = b.shape[-1]
    m = jnp.arange(M, dtype=jnp.int32)[None, None, :]
    bt = q_block[:, None, None]
    forced = (m < sp.init_blocks) | ((m <= bt) & (m > bt - sp.local_blocks))
    score = jnp.where(forced, 1e9, jnp.where(m <= bt, b, -1e9))
    if M < sp.topk:
        score = jnp.pad(score, ((0, 0), (0, 0), (0, sp.topk - M)),
                        constant_values=-1e9)
    vals, idx = jax.lax.top_k(score, sp.topk)
    return idx.astype(jnp.int32), vals >= 0.0


def sorted_page_list(blocks, valid, q_pos, sp, width, before=None):
    """PR 27's lists (``page_list`` for a decode step, ``before`` for a
    prompt chunk's private copy): the entries that count moved to the
    front by a stable argsort, the query's own block behind them."""
    bt = (q_pos // sp.block_size)[:, None, None]
    pad = ((0, 0), (0, 0), (0, width - blocks.shape[-1]))
    sel_b, sel_v = jnp.pad(blocks, pad), jnp.pad(valid, pad)
    m = jnp.arange(width, dtype=jnp.int32)[None, None, :]
    dense = (q_pos + 1 <= sp.dense_len)[:, None, None]
    blk = jnp.where(dense, jnp.broadcast_to(m, sel_b.shape), sel_b)
    ok = jnp.where(dense, m <= bt, sel_v)
    if before is not None:
        ok = ok & (blk < before)
    rank = jnp.where(ok, jnp.where(blk == bt, 1, 0), 2)
    order = jnp.argsort(rank, axis=-1, stable=True)
    return jnp.take_along_axis(blk, order, -1), jnp.sum(ok, -1)


def _scores(kind, R, Hkv, M, rng):
    b = rng.random((R, Hkv, M), dtype=np.float32)
    if kind == "ties":          # four values: every cut falls inside a tie
        b = rng.integers(0, 4, (R, Hkv, M)).astype(np.float32) / 4
    elif kind == "equal":
        b = np.full((R, Hkv, M), 0.25, np.float32)
    elif kind == "unseen":      # most blocks have no visible window: -1
        b = np.where(rng.random((R, Hkv, M)) < 0.8, -1.0, b).astype(np.float32)
    elif kind == "tiny":        # zeros, the smallest normals, a huge one
        b = rng.choice(np.array([0.0, 1.2e-38, 1.3e-38, 1e-30, 0.5, 2.5e8],
                                np.float32), (R, Hkv, M))
    return jnp.asarray(b)


# name: (scores, blocks M, the queries' positions, a chunk's first block)
SELECTOR_CASES = {
    "random_scores": ("random", 40, [100, 163, 200, 255, 319], None),
    "ties_at_the_cut": ("ties", 40, [100, 163, 200, 255, 319], None),
    "all_scores_equal": ("equal", 40, [97, 180, 319], None),
    "fewer_blocks_than_topk": ("random", 5, [0, 7, 8, 25, 39], None),
    "fewer_seen_than_topk": ("unseen", 40, [100, 200, 319], None),
    "zeros_and_tiny_scores": ("tiny", 40, [120, 250, 319], None),
    "inside_and_past_dense_len": ("random", 24, [0, 30, 62, 63, 64, 65, 191],
                                  None),
    "first_and_last_position_of_a_block": ("ties", 24, [72, 79, 80, 184, 191],
                                           None),
    "a_prompt_chunk": ("random", 24, list(range(128, 144)), 16),
    "a_chunk_with_ties": ("ties", 40, list(range(304, 320)), 38),
    "a_chunk_whose_initial_block_is_local": ("random", 24,
                                             list(range(8, 24)), 1),
    "a_chunk_that_sees_few_windows": ("unseen", 40, list(range(304, 320)),
                                      38),
}


@pytest.mark.parametrize("case", SELECTOR_CASES)
def test_the_selector_names_the_set_a_sort_would(case):
    kind, M, positions, before = SELECTOR_CASES[case]
    sp, Hkv = SEL, 2
    rng = np.random.default_rng(sum(map(ord, case)))
    q_pos = jnp.asarray(positions, jnp.int32)
    R, bt = len(positions), np.asarray(positions) // sp.block_size
    b = _scores(kind, R, Hkv, M, rng)

    def as_sets(blocks, n_or_valid):
        blocks, v = np.asarray(blocks), np.asarray(n_or_valid)
        if v.dtype != bool:                     # a count: the first n
            v = np.arange(blocks.shape[-1]) < v[..., None]
        return [[sorted(blocks[r, h][v[r, h]]) for h in range(Hkv)]
                for r in range(R)]

    want_b, want_v = sorted_selection(b, q_pos // sp.block_size, sp)
    blocks, valid = mixers.select_blocks(b, q_pos // sp.block_size, sp)
    assert blocks.shape == valid.shape == (R, Hkv, sp.topk)
    want = as_sets(want_b, want_v)
    assert as_sets(blocks, valid) == want
    mask = np.asarray(mixers.selection_mask(b, q_pos // sp.block_size, sp))
    assert [[list(np.flatnonzero(mask[r, h])) for h in range(Hkv)]
            for r in range(R)] == want
    # the entries that count are a prefix, ascending; no set has a double
    n_sel = np.asarray(valid).sum(-1)
    assert (np.asarray(valid) == (np.arange(sp.topk) < n_sel[..., None])).all()
    assert all(len(set(s)) == len(s) for row in want for s in row)

    width = sp.topk if before is not None else sp.list_blocks
    want_blk, want_n = sorted_page_list(want_b, want_v, q_pos, sp, width,
                                        before)
    blk, n = mixers.page_list(blocks, valid, q_pos, sp, width, before)
    assert blk.shape == (R, Hkv, width) and n.dtype == jnp.int32
    assert (np.asarray(n) == np.asarray(want_n)).all()
    assert as_sets(blk, n) == as_sets(want_blk, want_n)
    blk, n = np.asarray(blk), np.asarray(n)
    for r in range(R):
        for h in range(Hkv):
            counted = blk[r, h, :n[r, h]]
            assert (np.diff(counted) > 0).all()             # ascending
            if before is None:      # its own block, and last of them
                assert counted[-1] == bt[r] and n[r, h] >= 1
            else:                   # whole blocks before the chunk only
                assert (counted < before).all()
    # an entry past the count: the callers clamp it from above only
    assert (blk >= 0).all()


@pytest.mark.parametrize("case", SELECTOR_CASES)
def test_forced_and_chosen_blocks_are_the_selection_split_in_two(case):
    """``select_chosen`` lists what of ``selection_mask`` is not forced;
    for a prompt chunk ``forced_past`` names the forced blocks before it,
    the chunk's own blocks are the rest, and the three are the mask."""
    kind, M, positions, before = SELECTOR_CASES[case]
    sp, Hkv = SEL, 2
    rng = np.random.default_rng(sum(map(ord, case)))
    bt = np.asarray(positions) // sp.block_size
    b = _scores(kind, len(positions), Hkv, M, rng)
    width = sp.topk - sp.init_blocks - sp.local_blocks

    @jax.jit
    def split(b, bt):
        return (mixers.selection_mask(b, bt, sp),
                mixers.forced_mask(M, bt, sp)[:, 0],
                mixers.select_chosen(b, bt, sp, width),
                mixers.forced_past(0 if before is None else before, bt, sp))

    mask, forced, (blocks, n), (fb, sees) = jax.tree.map(
        np.asarray, split(b, jnp.asarray(bt)))
    assert blocks.shape == (len(bt), Hkv, width) and n.dtype == np.int32
    assert fb.shape == (sp.init_blocks + sp.local_blocks - 1,)
    for r in range(len(bt)):
        own = set(range(before, bt[r] + 1)) if before is not None else set()
        for h in range(Hkv):
            chosen = blocks[r, h, :n[r, h]]
            assert (np.diff(chosen) > 0).all()              # ascending
            assert not forced[r, chosen].any()              # disjoint
            assert (blocks[r, h, n[r, h]:] == M).all()      # the null page
            assert set(chosen) | set(np.flatnonzero(forced[r] & mask[r, h])) \
                == set(np.flatnonzero(mask[r, h]))
            assert n[r, h] == mask[r, h].sum() - (forced[r] & mask[r, h]).sum()
            # every forced block that exists is selected, whatever it scores
            assert (mask[r, h] | ~forced[r])[:bt[r] + 1].all()
            if kind != "unseen" and bt[r] + 1 >= sp.topk + sp.local_blocks:
                assert n[r, h] == width
            if before is not None:
                assert (chosen <= bt[r] - sp.local_blocks).all()
                past = fb[sees[r]]
                assert len(set(past)) == len(past) and (past >= 0).all()
                assert set(past) | own == set(np.flatnonzero(
                    forced[r, :bt[r] + 1]))
                assert set(past) | own | set(chosen) == set(np.flatnonzero(
                    mask[r, h, :bt[r] + 1]))
    assert mixers.chosen_width(sp) == width == 5
    assert mixers.chosen_width(WIDE) == sp.topk - 3      # blocks 0..2, once


WIDE = dataclasses.replace(SEL, window_size=32, dense_len=16)
# a chunk of 512 tokens is two tiles of queries to the forced pages' pass
TILED = dataclasses.replace(SEL, topk=72, window_size=512, dense_len=512)


@functools.lru_cache(maxsize=None)
def _chunk_world(sp, C, S, Hkv=2, H=4, Dh=16):
    """A sequence's q, k, v, its attention by the definition, and the
    chunk program's part at a traced offset over pools that hold EVERY
    page and pooled key of the sequence on shuffled pages (what lies at
    or after the chunk must not be read: it would count twice)."""
    from deeperspeed_tpu.serving import kv_cache as kvc

    bs, w, n = sp.block_size, sp.windows_per_block, S // sp.block_size
    nb, L = n + 9, 2
    table = jnp.asarray(np.random.default_rng(0).permutation(
        np.arange(1, nb))[:n + 3], jnp.int32)

    @jax.jit
    def world(key):
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (S, H, Dh)) * 2.0
        k = jax.random.normal(ks[1], (S, Hkv, Dh))
        v = jax.random.normal(ks[2], (S, Hkv, Dh))
        pages = lambda t: jnp.swapaxes(t.reshape(n, bs, Hkv, Dh), 1, 2)
        pool = jnp.zeros((L, nb, Hkv, bs, Dh))
        kbar = jnp.pad(mixers.pool_windows(k, sp), ((0, 1), (0, 0), (0, 0)))
        kc = jnp.zeros((L, nb, Hkv * w, Dh)).at[1, table[:n]].set(jnp.swapaxes(
            kbar.reshape(n, w, Hkv, Dh), 1, 2).reshape(n, -1, Dh))
        return (q, k, v, pool.at[1, table[:n]].set(pages(k)),
                pool.at[1, table[:n]].set(pages(v)), kc,
                mixers.dense_sparse_attention(q, k, v, sp))

    q, k, v, kp, vp, kc, want = world(jax.random.PRNGKey(0))
    widths = []

    def attend_pages(k_pool, v_pool, layer, q, row_head, pages, *rest):
        widths.append(pages.shape)
        return paged_sparse_attend_xla(k_pool, v_pool, layer, q, row_head,
                                       pages, *rest)

    @jax.jit
    def chunk(offset):
        cut = lambda t: jax.lax.dynamic_slice_in_dim(t, offset, C)
        return kvc.sparse_chunk_attend(sp, kp, vp, kc, 1, cut(q), cut(k),
                                       cut(v), table, offset, attend_pages)

    return chunk, widths, k, want


@pytest.mark.parametrize("sp,C,S,offset", [
    pytest.param(SEL, 16, 192, 64, id="the_first_chunk_beyond_dense_len"),
    pytest.param(SEL, 16, 192, 160, id="a_later_chunk"),
    pytest.param(WIDE, 16, 192, 16, id="an_initial_block_that_is_local"),
    pytest.param(WIDE, 16, 192, 96, id="a_wide_window_later"),
    pytest.param(TILED, 512, 1536, 1024, id="two_tiles_of_queries")])
def test_a_sparse_chunk_is_the_whole_sequence_form(sp, C, S, offset):
    """``sparse_chunk_attend`` beyond ``dense_len`` (forced pages and own
    keys densely, chosen pages through the lists) against the definition
    over the whole sequence, and the width of the lists it hands on."""
    from deeperspeed_tpu.serving.kv_cache import chosen_list_width

    chunk, widths, k, want = _chunk_world(sp, C, S)
    ctx, kbar_new = chunk(jnp.int32(offset))
    np.testing.assert_allclose(np.asarray(ctx),
                               np.asarray(want[offset:offset + C]), atol=2e-5)
    st = sp.kernel_stride
    np.testing.assert_allclose(
        np.asarray(kbar_new),
        np.asarray(mixers.pool_windows(k[offset - st:offset + C], sp)),
        atol=1e-6)
    assert widths == [(C * 2, chosen_list_width(sp))]       # one lowering
    assert chosen_list_width(sp) == mixers.chosen_width(sp)


def test_a_list_of_31_chosen_pages_is_32_wide():
    from deeperspeed_tpu.ops.pallas.paged_sparse_attn import (
        _pages_per_row_chunk)
    from deeperspeed_tpu.serving.kv_cache import chosen_list_width

    sp = SparseAttnConfig()
    assert mixers.chosen_width(sp) == 31 and chosen_list_width(sp) == 32
    pool = jax.ShapeDtypeStruct((4, 6241, 2, sp.block_size, 128), jnp.bfloat16)
    # one copy-chunk either way since PR 46, but only 32 pages of 64 are
    # whole lanes of scores
    assert _pages_per_row_chunk(32, pool) == 32
    assert _pages_per_row_chunk(31, pool) == 31
    assert (32 * sp.block_size) % 128 == 0 and (31 * sp.block_size) % 128


def test_pages_of_is_the_table_lookup_and_reads_the_null_page_past_it():
    from deeperspeed_tpu.serving.kv_cache import NULL_BLOCK, pages_of

    rng = np.random.default_rng(3)
    tables = jnp.asarray(rng.permutation(60)[:36].reshape(3, 12) + 1, jnp.int32)
    blocks = jnp.asarray(rng.integers(0, 15, (3, 2, 8)), jnp.int32)
    want = np.where(np.asarray(blocks) < 12, np.take_along_axis(
        np.asarray(tables)[:, None, :], np.minimum(blocks, 11), 2), NULL_BLOCK)
    got = pages_of(tables[:, None, None, :], blocks)        # a table a slot
    assert got.dtype == tables.dtype and (np.asarray(got) == want).all()
    got = pages_of(tables[1], blocks[1])                    # one table
    assert (np.asarray(got) == want[1]).all()


@pytest.mark.parametrize("program", ["chunk", "decode"])
def test_no_sort_is_left_in_the_sparse_programs(program):
    """``sparse_chunk_attend`` sorted 536 scores for each of 1,024 queries
    (a ``lax.top_k`` is a full sort to XLA) and argsorted every list; a
    fifth of a chunk step went there (PERF.md, PR 28)."""
    from deeperspeed_tpu.analysis.hlo import iter_eqns
    from deeperspeed_tpu.serving import kv_cache as kvc

    sp, Hkv, H, Dh, L, nb, bps = SEL, 2, 4, 16, 2, 40, 24
    w = sp.windows_per_block
    pool = jnp.zeros((L, nb, Hkv, sp.block_size, Dh))
    kc = jnp.zeros((L, nb, Hkv * w, Dh))
    if program == "chunk":
        C = 16
        fn = lambda q, k, v, table, offset: kvc.sparse_chunk_attend(
            sp, pool, pool, kc, 1, q, k, v, table, offset,
            paged_sparse_attend_xla)
        jaxpr = jax.make_jaxpr(fn)(
            jnp.zeros((C, H, Dh)), jnp.zeros((C, Hkv, Dh)),
            jnp.zeros((C, Hkv, Dh)), jnp.arange(bps), jnp.int32(128))
    else:
        N = 3
        tables = jnp.tile(jnp.arange(bps), (N, 1))

        def fn(q, k_row, v_row, lengths):
            at = kvc.decode_write_indices(sp, tables, lengths)
            return kvc.sparse_decode_attend(
                sp, pool, pool, kc, 1, q, k_row, v_row, tables, lengths, at,
                paged_sparse_attend_xla)

        jaxpr = jax.make_jaxpr(fn)(
            jnp.zeros((N, 1, H, Dh)), jnp.zeros((N, Hkv, Dh)),
            jnp.zeros((N, Hkv, Dh)), jnp.asarray([5, 70, 150], jnp.int32))
    names = {eqn.primitive.name for eqn in iter_eqns(jaxpr.jaxpr)}
    assert "cumsum" in names                    # the walk reaches the selector
    assert not [p for p in names if "sort" in p or "top_k" in p]


def test_a_neox_decode_step_never_meets_the_selector(monkeypatch):
    """The lowered ``ds_decode_step`` of a stack of attention layers is the
    same text whatever the selector is: none of it is reached."""
    from deeperspeed_tpu.models.gpt import init_params
    from deeperspeed_tpu.serving import ServingConfig
    from deeperspeed_tpu.serving.engine import idle_slots, make_decode_step

    cfg = GPTConfig(vocab_size=96, n_layer=2, n_head=4, d_model=64,
                    max_seq=64, rotary=True)
    scfg = ServingConfig(num_slots=3, block_size=8, num_blocks=25,
                         max_seq_len=64)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    N = scfg.num_slots
    sds = jax.ShapeDtypeStruct
    pool = sds((cfg.n_layer, scfg.num_blocks, scfg.block_size, cfg.kv_heads,
                cfg.head_dim), cfg.dtype)
    slots = idle_slots(N, scfg.blocks_per_slot)
    args = (params, pool, pool, sds(slots.shape, slots.dtype),
            sds((N,), jnp.int32))
    text = make_decode_step(cfg, scfg).lower(*args).as_text()

    def unreachable(*a, **k):
        raise AssertionError("a NeoX program reached the sparse selector")

    for name in ("top_mask", "selection_mask", "compact", "select_blocks",
                 "page_list", "block_scores"):
        monkeypatch.setattr(mixers, name, unreachable)
    assert make_decode_step(cfg, scfg).lower(*args).as_text() == text
    assert "ds_decode_step" in text and "stablehlo.sort" not in text
