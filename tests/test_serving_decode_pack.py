"""The decode step's slot inputs cross to the device once (PR 30): the
six per-slot arrays ride ONE packed int32 array, the program takes it
apart itself, and the engine counts the placements it makes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeperspeed_tpu.analysis import count_alias_pairs
from deeperspeed_tpu.models.gpt import GPTConfig, make_gpt
from deeperspeed_tpu.monitor import Tracer, set_tracer
from deeperspeed_tpu.serving import (ServingConfig, ServingEngine, idle_slots,
                                     pack_slots, unpack_slots)
from deeperspeed_tpu.serving import engine as engine_mod
from deeperspeed_tpu.serving.engine import make_decode_step

I32 = np.iinfo(np.int32)
SCFG = ServingConfig(num_slots=4, block_size=4, num_blocks=33, max_seq_len=32,
                     max_new_tokens=8, top_k=20, seed=11)


def _model(**kw):
    cfg = GPTConfig(vocab_size=97, n_layer=2, n_head=2, d_model=32, max_seq=64,
                    remat=False, dtype=jnp.float32, attn_impl="xla", **kw)
    return cfg, make_gpt(cfg)[0](jax.random.PRNGKey(0))


# ------------------------------------------------------------------ #
# (a) the pair of functions
# ------------------------------------------------------------------ #


def _six(temp, table):
    """Six slot arrays with seeds and counts at int32's edges."""
    N, bps = 4, 6
    tables = {"empty": np.zeros((N, bps), np.int32),
              "full": np.arange(1, 1 + N * bps, dtype=np.int32).reshape(N, bps)
              }[table]
    return (tables, np.asarray([0, 1, 23, I32.max], np.int32),
            np.asarray([0, 96, I32.max, 5], np.int32),
            np.asarray([temp, 0.0, -temp, temp], np.float32),
            np.asarray([I32.min, -1, 0, I32.max], np.int32),
            np.asarray([I32.max, 0, 1, I32.min], np.int32))


@pytest.mark.parametrize("table", ["empty", "full"])
@pytest.mark.parametrize("temp", [0.0, -1.0, 0.7, 1e-6])
def test_every_column_round_trips_bit_for_bit(temp, table):
    six = _six(temp, table)
    slots = pack_slots(*six)
    bps = six[0].shape[1]
    assert slots.dtype == np.int32 and slots.shape == (4, bps + 5)
    for unpack in (unpack_slots, jax.jit(unpack_slots, static_argnums=1)):
        got = unpack(jnp.asarray(slots), bps)
        for g, want in zip(got, six):
            g = np.asarray(g)
            assert g.dtype == want.dtype and g.shape == want.shape
            # bits, not values: -0.0 and 0.0 are different temperatures here
            np.testing.assert_array_equal(g.view(np.int32),
                                          want.view(np.int32))
    # the greedy rule reads the same slots before and after the crossing
    np.testing.assert_array_equal(np.asarray(got[3]) <= 0, six[3] <= 0)


def test_a_step_never_gets_the_last_steps_buffer():
    six = _six(0.7, "full")
    first, second = pack_slots(*six), pack_slots(*six)
    assert not np.shares_memory(first, second)
    assert not any(np.shares_memory(first, a) for a in six)


# ------------------------------------------------------------------ #
# (b) the engine against the same program body fed six arrays
# ------------------------------------------------------------------ #


def _six_array_engine(cfg, params, monkeypatch):
    """An engine whose decode program is the same body traced with its six
    slot inputs as six arguments (the form before PR 30), each built and
    placed by hand here."""
    eng = ServingEngine(cfg, params, SCFG)

    def launch(lanes):
        N = SCFG.num_slots
        tables = np.zeros((N, SCFG.blocks_per_slot), np.int32)
        lengths, tokens, seeds, counts = (np.zeros(N, np.int32)
                                          for _ in range(4))
        temps = np.zeros(N, np.float32)
        for s, req in lanes:
            tables[s] = eng.sched.slot_table_row(s)
            lengths[s] = req.cached_len
            tokens[s] = (engine_mod.TAKE_PREV if req.in_flight
                         else req.pending_token)
            temps[s], seeds[s] = req.temperature, req.seed
            counts[s] = len(req.generated) + req.in_flight
            req.cached_len += 1
            req.in_flight += 1
        six = tuple(map(jnp.asarray,
                        (tables, lengths, tokens, temps, seeds, counts)))
        with monkeypatch.context() as m:
            # the program's first line hands the six on as they came
            m.setattr(engine_mod, "unpack_slots", lambda six, bps: six)
            nxt, eng.kv.k, eng.kv.v, _, _ = eng._decode_step(
                eng.params, eng.kv.k, eng.kv.v, six, eng._prev)
        eng._inflight.append(engine_mod._Launched(
            nxt, lanes, False, bool(eng._inflight)))
        eng._prev = nxt

    eng._launch = launch
    return eng


def _serve(eng, temperature):
    rs = np.random.RandomState(7)
    rids = [eng.submit(rs.randint(0, 97, (n,)).tolist(), max_new_tokens=m,
                       temperature=temperature, request_id=f"r{n}")
            for n, m in ((6, 8), (5, 7), (9, 8))]
    outs = eng.run()
    return [outs[r] for r in rids]


@pytest.mark.parametrize("temperature", [0.0, 0.7], ids=["greedy", "sampled"])
def test_served_tokens_are_the_six_array_programs(temperature, monkeypatch):
    cfg, params = _model()
    want = _serve(_six_array_engine(cfg, params, monkeypatch), temperature)
    eng = ServingEngine(cfg, params, SCFG)
    got = _serve(eng, temperature)
    assert got == want and [len(o) for o in got] == [8, 7, 8]
    assert eng.decode_compile_count == 1


# ------------------------------------------------------------------ #
# (c) one placement a step, counted where it is made
# ------------------------------------------------------------------ #


def _count_placements(eng, monkeypatch):
    """Count what ``_launch`` places on the device by itself: calls
    of ``jnp.asarray`` and ``jax.device_put`` on a host array, by step."""
    calls, inside = [], []

    def counting(fn):
        def wrapped(x, *a, **k):
            if inside and isinstance(x, np.ndarray):
                calls[-1] += 1
            return fn(x, *a, **k)
        return wrapped

    monkeypatch.setattr(jnp, "asarray", counting(jnp.asarray))
    monkeypatch.setattr(jax, "device_put", counting(jax.device_put))
    plain = eng._launch

    def launch(lanes):
        calls.append(0)
        inside.append(True)
        try:
            return plain(lanes)
        finally:
            inside.pop()

    eng._launch = launch
    return calls


@pytest.mark.parametrize("mesh_shape", [None, {"dp": 4, "tp": 2}],
                         ids=["one_device", "dp4_tp2"])
def test_a_decode_step_makes_exactly_one_placement(mesh_shape, monkeypatch):
    from deeperspeed_tpu.sharding import from_config

    cfg, params = _model()
    mesh = None if mesh_shape is None else from_config(mesh_shape)
    eng = ServingEngine(cfg, params, SCFG, mesh=mesh)
    calls = _count_placements(eng, monkeypatch)
    _serve(eng, 0.0)
    assert len(calls) == eng.metrics.decode_steps >= 7
    assert set(calls) == {1}
    assert eng.metrics.summary()["decode_placements_per_step"] == 1.0
    # and nothing is left to cross inside the call: the last step's
    # tokens go in as the device handed them back
    assert isinstance(eng._prev, jax.Array)
    assert eng.decode_compile_count == 1


def test_six_placements_would_read_six():
    from deeperspeed_tpu.serving import ServingMetrics

    m = ServingMetrics(num_slots=4)
    assert m.summary()["decode_placements_per_step"] == 0.0
    for _ in range(3):
        m.record_decode_placements(6)
    assert m.summary()["decode_placements_per_step"] == 6.0


def test_the_pack_span_says_one_placement_and_the_four_names_stand():
    t = Tracer()
    set_tracer(t)
    try:
        cfg, params = _model()
        eng = ServingEngine(cfg, params, SCFG)
        _serve(eng, 0.0)
    finally:
        set_tracer(None)
    under = [e for e in t.events() if e["name"].startswith("serving/decode/")]
    assert {e["name"] for e in under} == {
        "serving/decode/pack", "serving/decode/dispatch",
        "serving/decode/wait", "serving/decode/emit"}
    packs = [e["args"] for e in under if e["name"] == "serving/decode/pack"]
    assert len(packs) == eng.metrics.decode_steps
    # a STRING: the profiler's reader keeps no other kind of argument
    assert all(a["placements"] == "1" for a in packs)


# ------------------------------------------------------------------ #
# (d) still one program, and the same one
# ------------------------------------------------------------------ #


def test_one_lowering_over_admissions_and_finishes():
    cfg, params = _model()
    eng = ServingEngine(cfg, params, SCFG)
    rs = np.random.RandomState(3)
    for wave in range(3):       # slots fill, drain and fill again
        for i in range(5):      # one more than the slots: a queue forms
            eng.submit(rs.randint(0, 97, (3 + i,)).tolist(),
                       max_new_tokens=2 + (i + wave) % 4,
                       temperature=0.5 * (i % 2))
        for _ in range(2 + wave):
            eng.step()
    eng.run()
    s = eng.metrics.summary()
    assert s["requests_finished"] == 15 and s["decode_steps"] >= 10
    assert eng.decode_compile_count == 1
    assert s["decode_placements_per_step"] == 1.0


def test_neox_decode_step_still_aliases_both_pools_and_never_sorts():
    cfg, params = _model(rotary=True, rotary_pct=1.0)
    N, bps = SCFG.num_slots, SCFG.blocks_per_slot
    pool = jnp.zeros((cfg.n_layer, SCFG.num_blocks, SCFG.block_size,
                      cfg.kv_heads, cfg.head_dim), cfg.dtype)
    lowered = make_decode_step(cfg, SCFG).lower(params, pool, pool,
                                                idle_slots(N, bps),
                                                np.zeros(N, np.int32))
    text = lowered.as_text()
    assert "ds_decode_step" in text and "stablehlo.sort" not in text
    # ONE slot argument, and the last step's tokens, beside the
    # parameters and the two pools
    n_args = len(jax.tree.leaves(params)) + 4
    assert len(jax.tree.leaves(lowered.args_info)) == n_args
    assert f"tensor<{N}x{bps + 5}xi32>" in text
    assert count_alias_pairs(lowered.compile().as_text()) == 2
