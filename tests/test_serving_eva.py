"""A stack of ``eva`` layers (EvaByte's: the exact keys of the query's own
window in pages a slot reuses window after window, one pooled summary for
every chunk behind it in pages of their own, one softmax over both)
through ``ServingEngine``, at small widths on the CPU: 2 layers, hidden
64, 4 heads of 16, window 32, chunk 4, block 8 (a window is 4 pages and
its 8 summaries one page), 2 prediction heads, vocabulary 320,
``prefill_chunk`` 16, float32. What the engine serves (chunked prefill,
then decode through the reused pages and the summary pages) is compared
with the plain reference ``benchmark/refs/evabyte.py`` on seeded weights,
and the parts with each other."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest as mf
from benchmark.adapters import evabyte as adapter
from benchmark.refs import evabyte as ref
from benchmark.refs import init as rinit
from deeperspeed_tpu.models import mixers
from deeperspeed_tpu.models.gpt import (LAYER_KINDS, EvaAttnConfig, GPTConfig,
                                        make_gpt)
from deeperspeed_tpu.serving import ServingConfig
from deeperspeed_tpu.serving.config import PageRule
from deeperspeed_tpu.serving.engine import prefill_chunk_for
from deeperspeed_tpu.serving.kv_cache import (BlockAllocator, PagedKVCache,
                                              blocks_needed, page_rule_for)
from deeperspeed_tpu.serving.scheduler import Request, Scheduler

TOY = mf.load_json(os.path.join(mf.ROOT, "tests", "bench", "data", "configs",
                                "toy-eva.json"))
SERVING = {"num_slots": 3, "block_size": 8, "num_blocks": 31,
           "max_seq_len": 128, "prefill_chunk": 16,
           "prefill_token_budget": 16, "max_new_tokens": 32}
VOCAB, W, C, BS = TOY["vocab_size"], TOY["window_size"], TOY["chunk_size"], 8


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


def gaps(reference, params, p, o):
    """How far each served byte lies under the reference's best, in the
    head's first block, and the logits' spread."""
    logits = np.asarray(reference.logits(params, p + o, len(p)))[:, :VOCAB]
    return logits.max(-1) - logits[np.arange(len(o)), o], logits.std()


# ------------------------------------------------------------------ #
# the engine against the plain reference
# ------------------------------------------------------------------ #


@pytest.mark.parametrize("lengths,new", [
    # 70 = 4 chunks of 16 and 6: n_valid 6 leaves the chunk of 4 at 68..71
    # incomplete; the decode steps complete it and write its summary
    pytest.param((70, 37, 21), 12, id="ragged_last_chunks_leave_a_chunk_of_4_open"),
    # 64 ends exactly on a window's edge: the first decoded position is a
    # window's first and sees itself and summaries alone
    pytest.param((64, 32, 96), 12, id="prompts_that_end_on_a_windows_edge"),
    # 50 + 50: the decode run crosses the edges at 64 and 96, the reused
    # pages start over twice
    pytest.param((50, 28), 50, id="a_decode_run_across_two_windows_edges"),
    pytest.param((100, 5), 8, id="three_windows_and_a_prompt_inside_one_chunk")])
def test_prefill_chunks_and_decode_agree_with_the_reference(
        params, reference, lengths, new):
    """Chunked prefill, then decode through the reused pages and the
    summary pages: every served (greedy) byte is the reference's best at
    its position. Tolerance 1e-4 of the logits' spread: both sides are
    float32 and differ in the order of their sums alone (the online
    softmax over a page list, the chunk's own keys apart from the listed
    past); bf16 in the program's place reads a hundred times that (the
    last test). The hidden state a byte is picked from feeds every
    prediction head through one matmul, whose columns the chunk
    program's test below compares one by one."""
    eng, ps, outs = served(params, lengths, new)
    for p, o in zip(ps, outs):
        gap, spread = gaps(reference, params, p, o)
        assert len(o) == new and gap.max() <= 1e-4 * spread, (len(p), gap.max())
    assert eng.decode_compile_count == 1
    assert eng._chunk_step._cache_size() == 1       # one lowering, every chunk
    assert eng.prefill_compile_count == 0           # no bucketed prefill


def chunked_logits(eng, p):
    """The chunk program driven by hand through a fresh slot's table: the
    last chunk's logits, all ``n_pred x vocab`` of them."""
    cfg, scfg, kv = eng.cfg, eng.scfg, eng.kv
    want = scfg.pages_by_role(len(p) + 1)
    blocks = kv.allocator.alloc(sum(want))
    table = np.zeros(scfg.blocks_per_slot, np.int32)
    table[:want[0]] = blocks[:want[0]]
    table[scfg.table_widths[0]:scfg.table_widths[0] + want[1]] = blocks[want[0]:]
    Cp = prefill_chunk_for(cfg, scfg)
    for lo in range(0, len(p), Cp):
        toks = np.zeros((1, Cp), np.int32)
        n = min(Cp, len(p) - lo)
        toks[0, :n] = p[lo:lo + n]
        logits, kv.k, kv.v, kv.kc, kv.state = eng._chunk_step(
            eng.params, kv.k, kv.v, kv.kc, kv.state, jnp.asarray(toks),
            jnp.asarray(table), np.int32(1), np.int32(lo), np.int32(n))
    return np.asarray(logits)


@pytest.mark.parametrize("length", [
    pytest.param(21, id="inside_the_first_window_no_summary_seen"),
    pytest.param(32, id="the_first_windows_last_position"),
    pytest.param(33, id="a_windows_first_position_sees_itself_and_summaries"),
    pytest.param(49, id="the_second_half_of_a_window_reads_its_first_half"),
    pytest.param(70, id="two_windows_behind_a_ragged_chunk"),
    pytest.param(100, id="three_windows_behind")])
def test_first_byte_logits_of_a_chunked_prompt_on_every_prediction_head(
        params, reference, length):
    """The chunk program's own logits at the prompt's last position, all
    ``2 x 320`` columns, against the reference's. atol 3e-5 on logits
    that spread 1.6: float32 sums in another order."""
    (p,) = prompts((length,), seed=3)
    got = chunked_logits(engine_for(params), p)
    want = np.asarray(reference.logits(params, p + [0], len(p)))[0]
    assert got.shape == want.shape == (2 * VOCAB,)
    np.testing.assert_allclose(got, want, atol=3e-5)


def test_the_whole_forward_agrees_with_the_reference(params, reference):
    """mixers.forward (no cache) is the program's own statement of the
    model: 100 positions reach into a fourth window, and the last chunk
    of 4 is whole (a sequence's trailing chunk is pooled only if all its
    positions exist). All positions, all columns."""
    cfg = adapter.model_config(TOY)
    (p,) = prompts((100,), seed=5)
    got = mixers.forward(cfg, params, jnp.asarray([p], jnp.int32))[0]
    want = reference.logits(params, p + [0], 1)
    assert got.shape == (100, 2 * VOCAB) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


@pytest.mark.parametrize("control", ["nosum", "flatpool"])
def test_a_reference_without_its_summaries_is_far_from_the_program(
        params, reference, control):
    """The controls of the cell's check at the toy size: a forward that
    forgot its summaries, or pooled by plain means, puts other bytes
    first than the program served."""
    (p,) = prompts((90,), seed=2)
    want = np.asarray(reference.logits(params, p + [0], 33))
    low = np.asarray(ref.Forward(ref.make(TOY, control=control)).logits(
        params, p + [0], 33))
    # inside the first window nothing is pooled: the controls agree there
    got = np.asarray(reference.logits(params, p[:32] + [0], 1))
    ctl = np.asarray(ref.Forward(ref.make(TOY, control=control)).logits(
        params, p[:32] + [0], 1))
    np.testing.assert_allclose(ctl, got, atol=3e-5)
    assert np.abs(low - want).max() > 1000 * 3e-5


# ------------------------------------------------------------------ #
# the pages: two roles, reused rows, rows written and not yet seen
# ------------------------------------------------------------------ #


def slot_rows(eng, slot, n):
    """What ``n`` cached positions of a slot leave a query to see: the
    summary rows of whole windows behind, then the window's rows, keys
    and values of both layers (rows, 2 layers, heads, Dh) each."""
    table = np.asarray(eng.sched.slot_table_row(slot))
    ring = eng.scfg.table_widths[0]
    w, r = divmod(n, W)
    out = []
    for pool in (eng.kv.k, eng.kv.v):
        pool = np.asarray(pool)
        rows = lambda pages: np.concatenate(
            [np.moveaxis(pool[:, pg], 2, 0) for pg in pages])   # (rows, L, H, Dh)
        summ = rows(table[ring:])[:(W // C) * w]
        out.append(np.concatenate([summ, rows(table[:ring])[:r]]))
    return out


def test_rows_a_decode_step_writes_are_the_rows_a_prompt_chunk_writes(params):
    """50 prompt bytes and 39 decode steps leave 89 cached positions; the
    same 89 bytes entered as ONE prompt leave the same rows to see: the
    window's reused rows and the summary rows, those a decode step wrote
    when it completed a chunk of 4 among them. 3e-5 on entries up to 4:
    float32, the same sums in another order (a decode step's projections
    run at batch 1, a chunk's at 16)."""
    (p,) = prompts((50,))
    a = engine_for(params, num_slots=1)
    a.submit(p, max_new_tokens=41, request_id="r")
    with jax.default_matmul_precision("highest"):
        while len(a.get("r").generated) < 40:
            a.step()
        o = a.get("r").generated[:40]
        # the last byte is picked and not yet cached
        b = engine_for(params, num_slots=1)
        b.submit(p + o[:39], max_new_tokens=3, request_id="whole")
        while not b.get("whole").generated:
            b.step()
    n = 89
    assert a.get("r").cached_len - a.get("r").in_flight in (n, n + 1)
    for got, want in zip(slot_rows(a, 0, n), slot_rows(b, 0, n)):
        assert got.shape == want.shape == ((W // C) * 2 + n % W, 2, 4, 16)
        np.testing.assert_allclose(got, want, atol=3e-5)


def test_stale_rows_and_the_unfinished_windows_summaries_stay_unseen(params):
    """A slot at 70 positions holds, beyond what counts, the rows 6..31 of
    the window before in its reused pages and the summary rows of the
    window being filled. Laid over with 1e3, they move nothing: the
    served bytes are those of an untouched engine (a decode step that
    completes a chunk reads that chunk's rows alone)."""
    _, (p,), (want,) = served(params, (70,), new=30, num_slots=1)
    eng = engine_for(params, num_slots=1)
    eng.submit(p, max_new_tokens=30, request_id="r")
    with jax.default_matmul_precision("highest"):
        while eng._chunking or not eng.get("r").generated:
            eng.step()
        eng._settle()
        n = eng.get("r").cached_len
        table = np.asarray(eng.sched.slot_table_row(0))
        ring = eng.scfg.table_widths[0]
        w, r = divmod(n, W)
        k, v = np.array(eng.kv.k), np.array(eng.kv.v)
        for i in range(r, W):                   # the window before
            for pool in (k, v):
                pool[:, table[i // BS], :, i % BS] = 1e3
        for s in range(n // C + 1, (w + 1) * (W // C)):   # not yet written
            if table[ring + s // BS]:
                for pool in (k, v):
                    pool[:, table[ring + s // BS], :, s % BS] = 1e3
        eng.kv.k, eng.kv.v = jnp.asarray(k), jnp.asarray(v)
        out = eng.run()["r"]
    assert out == want


def test_a_slot_served_twice_answers_as_a_fresh_engine(params):
    """The second request of a slot finds the first one's rows in pages
    it is handed again (a longer request's: every reused row and summary
    row was written); it answers as a fresh engine does."""
    eng = engine_for(params, num_slots=1)
    (long, short) = prompts((100, 37), seed=4)
    eng.submit(long, max_new_tokens=20, request_id="long")
    eng.submit(short, max_new_tokens=30, request_id="short")
    with jax.default_matmul_precision("highest"):
        out = eng.run()
        fresh = engine_for(params, num_slots=1)
        fresh.submit(short, max_new_tokens=30, request_id="short")
        assert out["short"] == fresh.run()["short"]


def test_preempt_and_readmit_gives_identical_tokens(params):
    """A pool too small for two long requests preempts the younger; it
    re-enters by the chunk program with what it had generated and goes
    on to the same bytes."""
    lengths = (60, 60)
    _, ps, want = served(params, lengths, new=40)
    # a request at 100 positions holds 4 + 4 pages: 13 usable pages hold
    # one whole and most of another
    eng, _, got = served(params, lengths, new=40, num_blocks=14)
    assert eng.metrics.preemptions >= 1
    assert got == want


# ------------------------------------------------------------------ #
# the page rule
# ------------------------------------------------------------------ #


def eva_rule(n, bs=BS):
    return min(-(-n // bs), W // bs) + -(-(n // C) // bs)


def test_the_page_rule_of_the_cache():
    """min(ceil(n / bs), W / bs) pages of exact keys, reused, and a page
    of summaries for every ``chunk * bs`` positions, the rows of the
    window being filled among them (ISSUE 35's ``(W / C / bs) (n // W)``
    would leave those rows, which it has written as chunks complete, no
    page; CHANGES.md). For an attention stack ``ceil(n / bs)`` to the
    page."""
    rule = page_rule_for(adapter.model_config(TOY))
    assert rule == PageRule(window=W, chunk=C)
    assert page_rule_for(GPTConfig(n_layer=2, n_head=2, d_model=32)) == PageRule()
    for n in range(0, 4 * W + 1):
        assert sum(rule.counts(n, BS)) == eva_rule(n) if n else True
        assert PageRule().counts(n, 16) == (blocks_needed(n, 16),)
        assert PageRule().live(n, 16) == blocks_needed(n, 16)
    assert rule.counts(0, BS) == (0, 0)
    # at the published sizes a slot at 32,768 positions holds 64 pages
    # where a page for every 64 positions is 512
    big = PageRule(window=2048, chunk=16)
    assert big.counts(32768, 64) == (32, 32)
    assert big.counts(14000, 64) == (32, 14)
    assert big.counts(2048, 64) == (32, 2) and big.counts(100, 64) == (2, 1)
    # the pages a query reads: 2 summary pages a window behind, the
    # window's so far
    assert big.live(14001, 64) == 12 + -(-(14000 % 2048 + 1) // 64)
    scfg = ServingConfig(num_slots=16, block_size=64, num_blocks=1025,
                         max_seq_len=32768).for_cache(big)
    assert scfg.blocks_per_slot == 64 and scfg.table_widths == (32, 32)
    assert ServingConfig(block_size=64, max_seq_len=32768).blocks_per_slot == 512
    # the rule is derived from the model, never configured: no caller can
    # hand it in, as an argument or as a key of the "serving" block
    with pytest.raises(TypeError, match="page_rule"):
        ServingConfig(block_size=64, page_rule=big)
    with pytest.raises(ValueError, match="page_rule"):
        ServingConfig.from_dict({"block_size": 64, "page_rule": {"window": 2048}})


def test_the_scheduler_admits_grows_and_frees_by_the_caches_rule():
    scfg = ServingConfig.from_dict(SERVING).for_cache(PageRule(W, C))
    alloc = BlockAllocator(scfg.num_blocks)
    sched = Scheduler(scfg, alloc)
    assert scfg.blocks_per_slot == 8 and scfg.table_widths == (4, 4)
    sched.submit(Request("a", list(range(70)), 40))
    slot, req, blocks = sched.pop_admissible()
    blocks = list(blocks)
    # 71 positions: 4 pages of the window, ceil(17 / 8) = 3 of summaries
    assert len(blocks) == eva_rule(71) == 7 and sched.slot_roles[slot] == [4, 3]
    row = sched.slot_table_row(slot)
    assert row[:4] == blocks[:4] and row[4:7] == blocks[4:] and row[7] == 0
    held = []
    for _ in range(40):     # a decode step a position
        sched.ensure_decode_capacity(1)
        held.append(len(sched.slot_blocks[slot]))
        assert held[-1] == eva_rule(req.cached_len + 1)
        assert alloc.num_allocated == held[-1]
        req.cached_len += 1
    assert held[0] == 7 and held[-1] == 8       # the count stopped following
    assert sched.slot_table_row(slot)[:4] == blocks[:4]     # the same 4, reused
    sched.finish(req, "length")
    assert alloc.num_allocated == 0 and sched.slot_roles[slot] == []
    # the worst case: what 128 positions hold is 8 pages, not 16
    sched.submit(Request("b", list(range(100)), 28))
    small = Scheduler(ServingConfig.from_dict({**SERVING, "num_blocks": 8})
                      .for_cache(PageRule(W, C)), BlockAllocator(8))
    with pytest.raises(ValueError, match="worst-case footprint"):
        small.submit(Request("c", list(range(100)), 28))


def test_an_attention_stack_is_held_by_the_length_to_the_page():
    scfg = ServingConfig(num_slots=2, block_size=16, num_blocks=33,
                         max_seq_len=256)
    assert scfg.page_rule == PageRule() and scfg.blocks_per_slot == 16
    alloc = BlockAllocator(scfg.num_blocks)
    sched = Scheduler(scfg, alloc)
    sched.submit(Request("a", list(range(40)), 100))
    slot, req, blocks = sched.pop_admissible()
    blocks = list(blocks)
    assert len(blocks) == blocks_needed(41, 16) == 3
    for _ in range(100):
        sched.ensure_decode_capacity(1)
        assert len(sched.slot_blocks[slot]) == blocks_needed(
            req.cached_len + 1, 16)
        req.cached_len += 1
    assert sched.slot_table_row(slot)[:3] == blocks
    assert sched.slot_table_row(slot)[9:] == [0] * 7


# ------------------------------------------------------------------ #
# counters, shapes, refusals
# ------------------------------------------------------------------ #


def test_counters_and_cache_shapes_of_a_served_window(params):
    eng, ps, outs = served(params, (70, 37), new=30, num_slots=2)
    assert eng.kv.k.shape == eng.kv.v.shape == (2, 31, 4, 8, 16)
    assert eng.kv.kc is None and eng.kv.state is None
    assert eng.scfg.blocks_per_slot == 8 and eng.scfg.table_widths == (4, 4)
    m = eng.metrics
    # chunks write a summary for every whole chunk of 4 they hold
    assert m.summary_rows_chunk == sum(
        (min(16, n - lo)) // 4 for n in (70, 37) for lo in range(0, n, 16))
    # decode steps write one whenever a position completes a chunk
    assert m.summary_rows_decode == sum(
        sum(1 for t in range(n, n + 29) if (t + 1) % 4 == 0) for n in (70, 37))
    # 70 + 29 crosses 96; 37 + 29 crosses 64
    assert m.window_wraps == 2
    s = m.summary()
    assert s["kv_pages_per_slot"]["window"] == 4.0
    assert 1.0 < s["kv_pages_per_slot"]["summary"] <= 4.0
    assert s["summary_rows"] == {"decode": m.summary_rows_decode,
                                 "chunk": m.summary_rows_chunk}
    assert m.kv_held_rows == m.gaps == 58
    # a stack whose pages follow the length counts none of this
    assert "kv_window_pages" in vars(m)


def chunk_spans(params, reference, lengths=(70, 37), new=6):
    """A served window under a tracer: the engine, its
    ``serving/prefill_chunk`` spans, and how far the served bytes lie
    under the reference's best, in units of the logits' spread."""
    from deeperspeed_tpu.monitor.tracer import Tracer, set_tracer

    tracer = Tracer()
    set_tracer(tracer)
    try:
        eng, ps, outs = served(params, lengths, new)
    finally:
        set_tracer(None)
    worst = 0.0
    for p, o in zip(ps, outs):
        gap, spread = gaps(reference, params, p, o)
        worst = max(worst, gap.max() / spread)
    return eng, [e for e in tracer.events()
                 if e["name"] == "serving/prefill_chunk"], worst


def test_the_span_and_the_count_say_the_xla_form_on_the_cpu(params,
                                                            reference):
    """Off the TPU an eva stack's chunk attends through
    ``kv_cache.chunk_attend_all``: the engine, the span's ``attn`` and the
    count of kernel chunks say so."""
    eng, chunks, worst = chunk_spans(params, reference)
    assert eng._chunk_attn == "xla"
    assert [e["args"]["attn"] for e in chunks] == ["xla"] * 8   # 5 + 3
    reuse = eng.metrics.summary()["prefix_reuse"]
    assert reuse["prefill_chunks"] == 8
    assert reuse["prefill_chunks_kernel_attn"] == 0
    assert worst <= 1e-4


def test_the_span_and_the_count_say_when_the_chunks_kernel_engaged(
        params, reference, monkeypatch):
    """On one TPU the chunk of an eva stack attends over its list of two
    roles in ops/pallas/chunk_past_attn. The toy pool is no shape the
    compiled kernel tiles, so the chooser is made to hand out the kernel's
    forms here, interpreted: every chunk is counted, its span says
    ``kernel``, and the served bytes are still the reference's best."""
    from deeperspeed_tpu.ops.pallas import chunk_past_attn as kernel
    from deeperspeed_tpu.serving import kv_cache as kvc

    compiled, asked, lists = kernel.chunk_past_attn, [], []
    chooser = kvc.chunk_attend_for

    def interpreted(*a, **kw):
        lists.append(a[6].shape)
        return compiled(*a, **kw, q_tile=8, interpret=True)

    monkeypatch.setattr(kernel, "chunk_past_attn", interpreted)

    def as_on_one_tpu(pool, *args):
        asked.append(args)
        return chooser(pool, *args)._replace(
            name="kernel", listed=kvc.chunk_attend_all_kernel)

    monkeypatch.setattr(kvc, "chunk_attend_for", as_on_one_tpu)
    eng, chunks, worst = chunk_spans(params, reference)
    assert eng._chunk_attn == "kernel"
    assert [e["args"]["attn"] for e in chunks] == ["kernel"] * 8
    reuse = eng.metrics.summary()["prefix_reuse"]
    assert reuse["prefill_chunks_kernel_attn"] == reuse["prefill_chunks"] == 8
    assert worst <= 1e-4
    # asked with the heads, the chunk's length and no mesh, by the engine
    # and by the program it built
    assert set(asked) == {(4, 16, None)}
    # the program's layers (one trace for both) handed the kernel the list
    # of ``eva_chunk_past``: 4 summary pages and the window's 2 before a chunk
    assert set(lists) == {(6,)}


def test_refusals_name_what_they_refuse(params):
    with pytest.raises(ValueError, match="recurrent state, or pages that are "
                                         "overwritten behind a window"):
        engine_for(params, prefix_caching=True)
    with pytest.raises(ValueError, match="divide the window"):
        engine_for(params, prefill_chunk=24, prefill_token_budget=24)
    with pytest.raises(NotImplementedError, match="without speculation"):
        engine_for(params, speculative={"draft_k": 2})
    with pytest.raises(ValueError, match="block_size .* must divide the window"):
        engine_for(params, block_size=12, prefill_chunk=24,
                   prefill_token_budget=24)
    with pytest.raises(ValueError, match="eva layers need cfg.eva"):
        GPTConfig(n_layer=1, mixer_types=("eva",))
    with pytest.raises(ValueError, match="multiple of chunk"):
        EvaAttnConfig(window=30, chunk=4)
    cfg = adapter.model_config(TOY)
    with pytest.raises(ValueError, match="page rule"):
        PagedKVCache(cfg, ServingConfig.from_dict(SERVING))
    with pytest.raises(NotImplementedError, match="is served only"):
        make_gpt(cfg)[2](None, None)


def test_the_config_of_the_stack(params):
    cfg = adapter.model_config(TOY)
    assert "eva" in LAYER_KINDS
    assert cfg.mixer_types == ("eva", "eva") and not cfg.classic
    assert cfg.eva == EvaAttnConfig(window=32, chunk=4) and cfg.eva.summaries == 8
    assert (cfg.norm_offset, cfg.fp32_stream, cfg.n_pred) == (1.0, True, 2)
    assert mixers.layer_runs(cfg) == [("eva", 0, 2)]
    # the values default to what every other configuration runs
    plain = GPTConfig()
    assert (plain.norm_offset, plain.fp32_stream, plain.n_pred,
            plain.eva) == (0.0, False, 1, None)
    mine = mixers.init_params(jax.random.PRNGKey(0), cfg)
    want = jax.tree.map(lambda s: s.shape, ref.leaf_specs(TOY),
                        is_leaf=lambda s: isinstance(s, rinit.Spec))
    assert jax.tree.map(lambda a: a.shape, mine) == want
    assert mine["lm_head"].shape == (64, 2 * VOCAB)
    # the norms start at a scale of 1 through the unit offset
    assert float(mine["eva"]["ln1"][0, 0]) + cfg.norm_offset == 1.0


def test_bf16_in_the_programs_place_fails_the_float32_tolerance(
        params, reference):
    """The tolerances above are tight enough to see the precision: the
    same program on weights rounded to bfloat16 (the CPU backend has no
    bfloat16 dot to compute in it as well) reads a hundred times over
    them."""
    (p,) = prompts((70,), seed=3)
    rounded = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
    got = chunked_logits(engine_for(rounded), p)
    want = np.asarray(reference.logits(params, p + [0], len(p)))[0]
    assert np.abs(got - want).max() > 100 * 3e-5
