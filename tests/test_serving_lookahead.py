"""The decode loop one step ahead (serving/engine.py ``_launch`` /
``_collect``): step n+1 is launched from step n's tokens where they lie on
the device, and step n is read while it runs. Everything here is counts and
tokens at toy sizes on the CPU: the same tokens as the synchronous order
(launch and collect back to back on the same engine class) for the three
kinds of stack, no row launched beyond a request's length, exactly one row
dropped after an EOS, every reader of the host's truth collecting first,
nothing left in flight by ``run()`` and ``drain()``, one compiled program."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest as mf
from benchmark.adapters import falcon_h1, minicpm_sala
from benchmark.refs import falcon_h1 as h1_ref
from benchmark.refs import init as rinit
from benchmark.refs import minicpm_sala as sala_ref
from deeperspeed_tpu.models.gpt import GPTConfig, make_gpt
from deeperspeed_tpu.monitor import compile_account
from deeperspeed_tpu.monitor.tracer import Tracer, set_tracer
from deeperspeed_tpu.serving import (FINISH_EOS, FINISH_LENGTH,
                                     FINISH_TIMEOUT, ServingConfig,
                                     ServingEngine)
from deeperspeed_tpu.serving.engine import TAKE_PREV, pack_slots
from deeperspeed_tpu.sharding import from_config

CONFIGS = os.path.join(mf.ROOT, "tests", "bench", "data", "configs")
CHUNKED = {"block_size": 8, "prefill_chunk": 16, "prefill_token_budget": 16}


@pytest.fixture(scope="module", autouse=True)
def _compile_cache(tmp_path_factory):
    """As in test_serving_spec.py: the pairs of engines here compile the
    same toy programs, so the persistent cache keeps them affordable."""
    d = tmp_path_factory.mktemp("xla_cache")
    jax.config.update("jax_compilation_cache_dir", str(d))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    yield
    jax.config.update("jax_compilation_cache_dir", None)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)


def _attention(**serving):
    cfg = GPTConfig(vocab_size=97, n_layer=2, n_head=2, d_model=32,
                    max_seq=128, remat=False, dtype=jnp.float32,
                    attn_impl="xla")
    params = make_gpt(cfg)[0](jax.random.PRNGKey(0))
    scfg = {"num_slots": 3, "block_size": 4, "num_blocks": 96,
            "max_seq_len": 96, "top_k": 8, **serving}
    mesh, clock = scfg.pop("mesh", None), scfg.pop("clock", FakeClock())
    return ServingEngine(cfg, params, ServingConfig.from_dict(scfg),
                         clock=clock, mesh=mesh)


def _toy(adapter, ref, name):
    def build(**serving):
        config = mf.load_json(os.path.join(CONFIGS, name))
        params = rinit.init_tree(7, ref.leaf_specs(config), jnp.float32)
        return adapter.serving_engine(
            config, params, {"num_slots": 3, "num_blocks": 61,
                             "max_seq_len": 128, "top_k": 8, **CHUNKED,
                             **serving})
    return build


STACKS = {
    "attention": _attention,
    "minicpm4_lightning": _toy(minicpm_sala, sala_ref, "toy-sala.json"),
    "mamba_attn": _toy(falcon_h1, h1_ref, "toy-h1.json"),
}


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def synchronous(eng):
    """The same engine reading every step before it goes on: launch and
    collect back to back, the order before the loop ran ahead."""
    launch = eng._launch

    def launch_and_collect(lanes):
        launch(lanes)
        eng._settle()

    eng._launch = launch_and_collect
    return eng


# requests by the step() call they arrive before: (prompt tokens, new
# tokens). More than the slots hold, so a queue forms; one ends at its
# prefill, others mid-run, while prompts of several chunks come in
ARRIVALS = {0: [(9, 6), (5, 9)], 2: [(37, 4)], 3: [(7, 1), (20, 7)],
            6: [(6, 5)], 9: [(33, 3), (4, 2)]}


def drive(eng, temperature=0.0, arrivals=ARRIVALS, vocab=96):
    rs = np.random.RandomState(11)
    rids, step = [], 0
    while step <= max(arrivals) or eng.has_work():
        for n, new in arrivals.get(step, ()):
            rids.append(eng.submit(
                rs.randint(0, vocab, (n,)).tolist(), max_new_tokens=new,
                temperature=temperature, request_id=f"r{len(rids)}"))
        eng.step()
        step += 1
    assert not eng._inflight
    return [eng.get(r) for r in rids]


def outputs(reqs):
    return [(r.output, r.finish_reason) for r in reqs]


# ------------------------------------------------------------------ #
# the same tokens as the synchronous order
# ------------------------------------------------------------------ #


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
@pytest.mark.parametrize("stack", sorted(STACKS))
def test_tokens_are_the_synchronous_orders(stack, temperature):
    want = drive(synchronous(STACKS[stack]()), temperature)
    eng = STACKS[stack]()
    got = drive(eng, temperature)
    assert outputs(got) == outputs(want)
    assert [len(r.output) for r in got] == [6, 9, 4, 1, 7, 5, 3, 2]
    assert {r.finish_reason for r in got} == {FINISH_LENGTH}
    s = eng.metrics.summary()
    assert eng.decode_compile_count == 1
    assert s["decode_rows_discarded"] == 0
    assert s["decode_placements_per_step"] == 1.0
    assert s["decode_ahead_share"] > 0.5


@pytest.mark.parametrize("serving", [
    pytest.param({"prefill_chunk": 8, "prefill_token_budget": 8},
                 id="chunked_prefill"),
    pytest.param({"prefix_caching": True, "prefill_chunk": 8},
                 id="prefix_caching"),
    pytest.param({"num_blocks": 14, "max_seq_len": 48}, id="preemption"),
    pytest.param({"mesh": {"dp": 4, "tp": 2}, "num_slots": 4},
                 id="dp4_tp2")])
def test_attention_stack_parity_under(serving):
    """Chunked prefill (a first token picked a step later while a step is
    in flight), a shared prefix, a pool so tight that requests are
    preempted, and a dp x tp mesh (``prev`` comes back laid out as the
    first step's zeros were placed: one entry in the jit's cache)."""
    serving = dict(serving)
    if "mesh" in serving:
        serving["mesh"] = from_config(serving["mesh"])
    shared = list(range(1, 14))
    arrivals = {0: [(9, 6), (5, 9)], 1: [(13, 5)], 2: [(13, 4), (21, 7)],
                5: [(6, 8)]}

    def run(eng):
        rs = np.random.RandomState(5)
        rids, step = [], 0
        while step <= max(arrivals) or eng.has_work():
            for n, new in arrivals.get(step, ()):
                p = shared[:n] if n == 13 else rs.randint(0, 97, n).tolist()
                rids.append(eng.submit(p + [len(rids)], max_new_tokens=new,
                                       temperature=0.7 * (len(rids) % 2)))
            eng.step()
            step += 1
        return eng, outputs(eng.get(r) for r in rids)

    _, want = run(synchronous(_attention(**serving)))
    eng, got = run(_attention(**serving))
    assert got == want
    assert eng.decode_compile_count == 1 and not eng._inflight
    assert eng.metrics.decode_rows_discarded == 0
    if "num_blocks" in serving:
        assert eng.metrics.preemptions > 0
    if serving.get("prefix_caching"):
        assert eng.metrics.summary()["prefix_reuse"]["reuse_hits"] > 0


def test_the_program_takes_a_token_from_prev_only_where_the_flag_says():
    """One program: ``TAKE_PREV`` in the token column reads the slot's
    entry of ``prev``; any other token is the host's, whatever ``prev``
    holds."""
    eng = _attention()
    N, bps = eng.scfg.num_slots, eng.scfg.blocks_per_slot

    def step_with(tokens, prev):
        tables = np.zeros((N, bps), np.int32)
        tables[:, 0] = [1, 2, 3]
        zeros = np.zeros(N, np.int32)
        slots = pack_slots(tables, zeros, tokens, np.zeros(N, np.float32),
                           zeros, zeros)
        nxt, eng.kv.k, eng.kv.v, _, _ = eng._decode_step(
            eng.params, eng.kv.k, eng.kv.v, jnp.asarray(slots),
            jnp.asarray(prev, jnp.int32))
        return np.asarray(nxt).tolist()

    host = step_with(np.array([5, 6, 7], np.int32), [0, 0, 0])
    assert step_with(np.array([5, 6, 7], np.int32), [9, 9, 9]) == host
    assert step_with(np.array([TAKE_PREV, 6, TAKE_PREV], np.int32),
                     [5, 9, 7]) == host
    assert eng.decode_compile_count == 1


# ------------------------------------------------------------------ #
# how a request ends
# ------------------------------------------------------------------ #


def test_a_request_ending_by_length_launches_no_row_beyond_it():
    eng = _attention()
    launched = []
    launch = eng._launch
    eng._launch = lambda lanes: (launched.append(
        [r.rid for _, r in lanes]), launch(lanes))[1]
    a = eng.submit([1, 2, 3, 4], max_new_tokens=5, request_id="a")
    b = eng.submit([5, 6, 7], max_new_tokens=3, request_id="b")
    eng.run()
    # a prefill yields the first token: m - 1 rows a request of m tokens
    assert sum(l.count("a") for l in launched) == 4
    assert sum(l.count("b") for l in launched) == 2
    s = eng.metrics.summary()
    assert s["decode_rows_discarded"] == 0
    assert s["decode_steps"] == len(launched) == 4
    assert s["tokens_generated"] == 8
    assert len(eng.get(a).output) == 5 and len(eng.get(b).output) == 3
    assert eng.get(a).cached_len == 4 + 4      # prompt + rows written


@pytest.mark.parametrize("stack", ["attention", "mamba_attn"])
def test_eos_discards_one_row_and_the_slot_serves_the_next_as_fresh(stack):
    """An end of sequence is seen a step late: exactly one row too many.
    It writes into a page the slot still holds (blocks go back at the
    collect that reads the EOS, never earlier), its token is dropped, and
    whoever enters the slot next (pages reused, the state row cleared by
    its first chunk) serves what a fresh engine serves."""
    build = STACKS[stack]
    rs = np.random.RandomState(4)
    first, second = (rs.randint(0, 96, n).tolist() for n in (11, 19))
    ref = synchronous(build(num_slots=1))
    ref.submit(first, max_new_tokens=12, request_id="first")
    full = ref.run()["first"]
    eos = full[4]
    expected = full[:full.index(eos) + 1]
    fresh = synchronous(build(num_slots=1, eos_token_id=eos))
    fresh.submit(second, max_new_tokens=6, request_id="second")
    want_second = fresh.run()["second"]

    eng = build(num_slots=1, eos_token_id=eos)
    bs = eng.scfg.block_size
    req = eng.get(eng.submit(first, max_new_tokens=12, request_id="first"))
    while req.state != "finished":
        eng.step()
        if req.state == "active":
            # every row launched writes inside the blocks the slot holds
            assert len(eng.sched.slot_blocks[0]) * bs >= req.cached_len
    assert req.output == expected and req.finish_reason == FINISH_EOS
    # the row launched before the EOS was read is still in flight; its
    # request has left, and the loop has work until it is collected
    assert req.in_flight == 1 and len(eng._inflight) == 1
    assert eng.kv.allocator.num_allocated == 0
    assert not eng.sched.has_work() and eng.has_work()
    eng.submit(second, max_new_tokens=6, request_id="second")
    out = eng.run()
    assert out["second"] == want_second and out["first"] == expected
    assert eng.metrics.summary()["decode_rows_discarded"] == 1
    assert req.in_flight == 0 and not eng.has_work()
    # the discarded row is no token
    assert eng.metrics.total_generated == len(expected) + len(want_second)


# ------------------------------------------------------------------ #
# whatever needs the host's truth collects first
# ------------------------------------------------------------------ #


def _two_requests(**serving):
    """(engine, the two requests, what the synchronous order serves)."""
    rs = np.random.RandomState(9)
    prompts = [rs.randint(0, 97, n).tolist() for n in (6, 9)]

    def submit(eng):
        return [eng.get(eng.submit(p, max_new_tokens=10, request_id=f"r{i}"))
                for i, p in enumerate(prompts)]

    ref = synchronous(_attention())
    submit(ref)
    want = ref.run()
    eng = _attention(**serving)
    return eng, submit(eng), [want["r0"], want["r1"]]


def test_cancel_collects_first_and_loses_no_token():
    eng, (a, b), (want_a, want_b) = _two_requests()
    for _ in range(3):
        eng.step()
    assert a.in_flight == 1 and len(eng._inflight) == 1
    held = len(a.output)
    assert eng.cancel(a.rid, reason="timeout")
    assert not eng._inflight and a.in_flight == b.in_flight == 0
    assert a.output == want_a[:held + 1] and len(b.output) == held + 1
    assert eng.run()["r1"] == want_b
    assert eng.metrics.decode_rows_discarded == 0
    assert eng.kv.allocator.num_allocated == 0


def test_cancel_of_a_request_its_last_token_in_flight_finds_it_finished():
    eng = _attention()
    req = eng.get(eng.submit([1, 2, 3], max_new_tokens=2))
    eng.step()
    assert req.state == "active" and req.in_flight == 1
    assert not eng.cancel(req.rid)          # the collect ended it by length
    assert req.finish_reason == FINISH_LENGTH and len(req.output) == 2


def test_a_timeout_collects_first_and_loses_no_token():
    clock = FakeClock()
    eng, (a, b), (want_a, want_b) = _two_requests(clock=clock,
                                                  request_timeout_s=5.0)
    for _ in range(3):
        eng.step()
    held = len(a.output)
    assert a.in_flight == b.in_flight == 1
    clock.t = 6.0
    done = eng.step()
    assert {r.rid for r in done} == {"r0", "r1"} and not eng.has_work()
    assert a.finish_reason == b.finish_reason == FINISH_TIMEOUT
    assert a.output == want_a[:held + 1] and b.output == want_b[:held + 1]
    assert eng.kv.allocator.num_allocated == 0


def test_a_preemption_collects_first_and_requeues_generated_whole():
    rs = np.random.RandomState(2)
    prompts = [rs.randint(0, 97, n).tolist() for n in (7, 6, 5, 4)]
    news = [10, 9, 11, 8]
    serving = {"num_slots": 4, "num_blocks": 8, "max_seq_len": 20}

    def run(eng):
        rids = [eng.submit(p, max_new_tokens=m)
                for p, m in zip(prompts, news)]
        out = eng.run()
        return [out[r] for r in rids]

    want = run(synchronous(_attention(**serving)))
    eng = _attention(**serving)
    seen = []
    preempt = eng.sched._preempt

    def checked(slot):
        req = eng.sched.slots[slot]
        seen.append((len(eng._inflight), req.in_flight))
        return preempt(slot)

    eng.sched._preempt = checked
    assert run(eng) == want
    assert seen and set(seen) == {(0, 0)}
    assert eng.metrics.preemptions == len(seen)
    assert eng.metrics.decode_rows_discarded == 0


# ------------------------------------------------------------------ #
# run(), drain(), has_work()
# ------------------------------------------------------------------ #


def test_run_and_drain_leave_nothing_in_flight():
    eng, (a, b), (want_a, want_b) = _two_requests()
    eng.run(max_steps=3)                    # cut short: still settled
    assert not eng._inflight and a.in_flight == b.in_flight == 0
    assert a.output == want_a[:len(a.output)] and 1 < len(a.output) < 10
    eng.step()
    assert len(eng._inflight) == 1
    queued = eng.submit([3, 1, 4], max_new_tokens=2)
    late = eng.submit([1, 5, 9, 2], max_new_tokens=2)
    assert eng.drain() == [queued, late]    # draining admits nothing new
    assert not eng._inflight and eng.sched.num_active == 0
    assert a.output == want_a and b.output == want_b
    assert a.finish_reason == b.finish_reason == FINISH_LENGTH


def test_has_work_while_a_launched_step_is_unread():
    eng = _attention()
    req = eng.get(eng.submit([1, 2, 3], max_new_tokens=3))
    eng.step()                              # prefill, launch; nothing read
    assert len(req.output) == 1 and len(eng._inflight) == 1
    assert eng.has_work()
    eng.step()                              # launch the last row, read one
    assert len(req.output) == 2 and req.in_flight == 1
    done = eng.step()                       # nothing to launch: read it
    assert [r.rid for r in done] == [req.rid] and len(req.output) == 3
    assert not eng._inflight and not eng.has_work()


# ------------------------------------------------------------------ #
# one program, and how often the loop runs ahead
# ------------------------------------------------------------------ #


def test_one_lowering_of_the_decode_program_over_a_whole_run():
    # a width no other test here uses: nothing is found already lowered
    cfg = GPTConfig(vocab_size=83, n_layer=2, n_head=2, d_model=40,
                    max_seq=64, remat=False, dtype=jnp.float32,
                    attn_impl="xla")
    params = make_gpt(cfg)[0](jax.random.PRNGKey(1))

    def lowered(name="ds_decode_step"):
        return compile_account().get(name, {}).get("lower", {}) \
            .get("count", 0)

    eng = ServingEngine(cfg, params, ServingConfig(
        num_slots=3, block_size=4, num_blocks=64, max_seq_len=64))
    before = lowered()
    drive(eng, temperature=0.5, vocab=83,
          arrivals={0: [(9, 6), (5, 9)], 2: [(12, 4)], 3: [(7, 1), (20, 7)],
                    40: [(6, 5)]})    # the loop idles, then starts again
    assert lowered() - before == 1
    assert eng.decode_compile_count == 1
    s = eng.metrics.summary()
    assert s["decode_steps"] >= 12 and s["decode_rows_discarded"] == 0


def test_ahead_share_on_a_busy_loop_and_zero_when_synchronous():
    arrivals = {0: [(9, 30), (5, 40)], 4: [(12, 25)], 20: [(7, 12)]}
    t = Tracer()
    set_tracer(t)
    try:
        eng = _attention()
        drive(eng, arrivals=arrivals)
    finally:
        set_tracer(None)
    s = eng.metrics.summary()
    assert s["decode_ahead_share"] >= 0.9
    assert eng.metrics.decode_steps_ahead == round(
        s["decode_ahead_share"] * s["decode_steps"])
    # the dispatch span says it too, as a STRING (the profiler's reader
    # keeps no other kind of argument); the four names stand
    spans = [e for e in t.events() if e["name"].startswith("serving/decode/")]
    assert {e["name"] for e in spans} == {
        "serving/decode/pack", "serving/decode/dispatch",
        "serving/decode/wait", "serving/decode/emit"}
    ahead = [e["args"]["ahead"] for e in spans
             if e["name"] == "serving/decode/dispatch"]
    assert ahead.count("1") == eng.metrics.decode_steps_ahead
    assert set(ahead) == {"0", "1"} and len(ahead) == s["decode_steps"]
    sync = synchronous(_attention())
    drive(sync, arrivals=arrivals)
    z = sync.metrics.summary()
    assert z["decode_ahead_share"] == 0.0 and z["decode_steps"] >= 39


def test_speculation_on_reads_every_round_and_serves_the_same_tokens():
    arrivals = {0: [(9, 12), (5, 9)], 2: [(12, 14)], 5: [(7, 1), (20, 7)]}
    want = drive(synchronous(_attention()), arrivals=arrivals, vocab=97)
    eng = _attention(speculative={"draft_k": 3, "drafter": {"n_layer": 1}},
                     prefill_buckets=(4, 8, 16, 32, 64, 96))
    inflight_after = []
    step = eng.step
    eng.step = lambda: (step(), inflight_after.append(len(eng._inflight)))[0]
    got = drive(eng, arrivals=arrivals, vocab=97)
    assert outputs(got) == outputs(want)
    assert set(inflight_after) == {0}
    s = eng.metrics.summary()
    assert s["speculative"]["rounds"] > 0
    assert s["decode_ahead_share"] == 0.0 and s["decode_rows_discarded"] == 0
