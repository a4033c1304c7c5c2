"""What the decode step reads and writes of the paged pool (PR 25): the
pool stays in place through the layer loop, the kernel reads each slot's
live pages and agrees with the XLA form, and the engine counts how far
the live-pages read engages."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeperspeed_tpu.analysis import count_alias_pairs
from deeperspeed_tpu.models.gpt import GPTConfig, make_gpt
from deeperspeed_tpu.monitor import Tracer, set_tracer
from deeperspeed_tpu.ops.pallas import paged_decode_attn as kernel
from deeperspeed_tpu.serving import ServingConfig, ServingEngine
from deeperspeed_tpu.serving.engine import (_paged_block, make_decode_step,
                                            pack_slots)
from deeperspeed_tpu.serving.kv_cache import (
    blocks_needed,
    paged_attend,
    paged_attend_rows,
)

# ------------------------------------------------------------------ #
# (a) the kernel, interpreted, against the XLA mathematics
# ------------------------------------------------------------------ #

BS, BPS = 16, 16        # a view of 256 positions: two chunks of 8 pages
# idle slots first, between and last (null table); one position; a page
# boundary and one past it; a chunk boundary and one past it; a full view
LENGTHS = (0, 1, BS, BS + 1, 0, 128, 129, BS * BPS - 1, 100, 0)


def _ragged_case(H, Hkv, Dh, dtype, seed):
    N, L = len(LENGTHS), 2
    rng = np.random.default_rng(seed)
    nb = 1 + N * BPS

    def arr(*shape):
        return jnp.asarray(rng.normal(size=shape), dtype)

    tables = np.zeros((N, BPS), np.int32)
    perm = rng.permutation(np.arange(1, nb))
    for i, n in enumerate(LENGTHS):
        used = blocks_needed(n + 1, BS) if n else 0
        tables[i, :used] = perm[i * BPS:i * BPS + used]
    return (arr(L, nb, BS, Hkv, Dh), arr(L, nb, BS, Hkv, Dh), arr(N, 1, H, Dh),
            arr(N, Hkv, Dh), arr(N, Hkv, Dh), jnp.asarray(tables),
            jnp.asarray(LENGTHS, jnp.int32))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("Dh", [64, 128])
@pytest.mark.parametrize("H,Hkv", [(4, 4), (8, 2)])
def test_kernel_matches_the_xla_form_on_ragged_lengths(H, Hkv, Dh, dtype, tol):
    kp, vp, q, kr, vr, tables, lengths = _ragged_case(H, Hkv, Dh, dtype,
                                                      seed=H + Dh)
    assert kernel._pages_per_chunk(BPS, BS * Hkv * Dh * 4, BS) == 8
    for layer in range(kp.shape[0]):
        want = paged_attend_rows(kp, vp, layer, q, kr, vr, tables, lengths)
        got = kernel.paged_decode_attn(kp, vp, jnp.int32(layer), q, kr, vr,
                                       tables, lengths, interpret=True)
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=tol, rtol=tol)
    # an idle slot is its new token's value, whatever block 0 holds
    idle = np.asarray(LENGTHS) == 0
    np.testing.assert_array_equal(
        np.asarray(got, np.float32)[idle, 0],
        np.asarray(jnp.repeat(vr, H // Hkv, axis=1), np.float32)[idle])


@pytest.mark.parametrize("bps,bs,page_bytes,want", [
    (128, 16, 65536, 8),        # the serving cell: 8 pages of 16 positions
    (128, 8, 32768, 16), (64, 32, 131072, 4), (6, 16, 65536, 6),
    (7, 16, 65536, 7), (14, 16, 65536, 7),      # a divisor of the table row
    (128, 16, 4 * 2 ** 20, 0),                  # a page VMEM cannot hold
])
def test_chunk_is_whole_pages_that_divide_a_table_row(bps, bs, page_bytes, want):
    assert kernel._pages_per_chunk(bps, page_bytes, bs) == want


def test_kernel_is_not_taken_off_the_tpu_or_for_shapes_it_cannot_tile(
        monkeypatch):
    from deeperspeed_tpu.ops import kernel_config
    from deeperspeed_tpu.serving.kv_cache import decode_attend_for

    def pool(Hkv, Dh, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((2, 9, 16, Hkv, Dh), dtype)

    tables = jax.ShapeDtypeStruct((4, 8), jnp.int32)
    assert decode_attend_for(pool(16, 128), tables, 16, None) \
        is paged_attend_rows                        # the CPU: the XLA form
    monkeypatch.setattr(kernel_config, "on_tpu", lambda: True)
    assert decode_attend_for(pool(16, 128), tables, 16, None) \
        is kernel.paged_decode_attn
    assert decode_attend_for(pool(16, 128), tables, 32, None) \
        is kernel.paged_decode_attn                 # grouped queries
    assert decode_attend_for(pool(8, 128, jnp.float32), tables, 8, None) \
        is kernel.paged_decode_attn
    for refused in (pool(16, 64), pool(4, 128), pool(8, 128)):
        assert decode_attend_for(refused, tables, 16, None) \
            is paged_attend_rows
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    assert decode_attend_for(pool(16, 128), tables, 16, mesh) \
        is paged_attend_rows                        # XLA partitions no kernel


# ------------------------------------------------------------------ #
# (b) (c) the decode program and the pool
# ------------------------------------------------------------------ #

SCFG = ServingConfig(num_slots=4, block_size=4, num_blocks=33, max_seq_len=32,
                     max_new_tokens=8)


def _model(dtype=jnp.float32, **kw):
    cfg = GPTConfig(vocab_size=97, n_layer=3, n_head=2, d_model=32, max_seq=64,
                    remat=False, dtype=dtype, attn_impl="xla", **kw)
    init_fn, _, _, _ = make_gpt(cfg)
    params = jax.tree.map(lambda a: a.astype(dtype),
                          init_fn(jax.random.PRNGKey(0)))
    return cfg, params


def _step_args(cfg, params, lengths, seed=3):
    """Pools of noise and one private run of blocks a live slot."""
    N, bps = SCFG.num_slots, SCFG.blocks_per_slot
    rng = np.random.default_rng(seed)
    shape = (cfg.n_layer, SCFG.num_blocks, SCFG.block_size, cfg.kv_heads,
             cfg.head_dim)
    tables = np.zeros((N, bps), np.int32)
    for i, n in enumerate(lengths):
        if n:
            tables[i] = 1 + i * bps + np.arange(bps)

    def i32(x):
        return jnp.asarray(x, jnp.int32)

    return (params, jnp.asarray(rng.normal(size=shape), cfg.dtype),
            jnp.asarray(rng.normal(size=shape), cfg.dtype), i32(tables),
            i32(lengths), i32(rng.integers(0, cfg.vocab_size, N)),
            jnp.zeros(N, jnp.float32), i32(np.arange(N)), i32(np.ones(N)))


def _packed(args):
    """The decode step's own arguments from the nine of ``_step_args``:
    the six per-slot arrays as the one packed array, and no token left
    on the device (``prev`` zeros)."""
    return args[:3] + (jnp.asarray(pack_slots(*args[3:])),
                       jnp.zeros(SCFG.num_slots, jnp.int32))


def test_decode_step_slices_no_layer_out_of_the_pool_and_aliases_both():
    cfg, params = _model()
    args = _step_args(cfg, params, [5, 0, 8, 3])
    lowered = make_decode_step(cfg, SCFG).lower(*_packed(args))
    nb, bs = SCFG.num_blocks, SCFG.block_size
    layer = f"{nb}x{bs}x{cfg.kv_heads}x{cfg.head_dim}x"
    moved = [ln.strip() for ln in lowered.as_text().splitlines()
             if ("dynamic_slice" in ln or "dynamic_update_slice" in ln)
             and layer in ln]
    assert moved == []
    # both donated pools are outputs of the program, in place
    assert count_alias_pairs(lowered.compile().as_text()) == 2


def _old_form_step(cfg):
    """The decode step as it stood before PR 25: the pools go through the
    layer scan as ``xs`` and each layer writes its row before it reads."""

    @jax.jit
    def step(params, k_pool, v_pool, tables, lengths, tokens):
        N = tokens.shape[0]
        x = jnp.take(params["embed"]["wte"].astype(cfg.dtype), tokens,
                     axis=0)[:, None, :]
        positions = lengths[:, None]
        wblk = tables[jnp.arange(N), lengths // SCFG.block_size]
        woff = lengths % SCFG.block_size

        def body(x, xs):
            layer_params, k_l, v_l = xs

            def attend(q, k, v):
                ctx, k2, v2 = paged_attend(k_l, v_l, q, k, v, tables, lengths,
                                           wblk, woff)
                return ctx, (k2, v2)

            return _paged_block(cfg, x, layer_params, positions, attend)

        _, (k_pool, v_pool) = jax.lax.scan(
            body, x, (params["layers"], k_pool, v_pool))
        return k_pool, v_pool

    return step


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_rows_written_are_the_old_forms_rows_bit_for_bit(dtype):
    cfg, params = _model(dtype, rotary=True)
    bs = SCFG.block_size
    # the new row at the first and at the last offset of a page
    lengths = [2 * bs, 3 * bs - 1, 0, bs]
    args = _step_args(cfg, params, lengths)
    want_k, want_v = _old_form_step(cfg)(*args[:6])
    _, got_k, got_v, _, _ = make_decode_step(cfg, SCFG)(*_packed(args))
    tables = np.asarray(args[3])
    for got, want in ((got_k, want_k), (got_v, want_v)):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        for i, n in enumerate(lengths):
            blk, off = tables[i, n // bs], n % bs
            np.testing.assert_array_equal(got[:, blk, off], want[:, blk, off])
        # and nothing else of the pool moved
        np.testing.assert_array_equal(got, want)


# ------------------------------------------------------------------ #
# (d) the counter that says how far the live-pages read engages
# ------------------------------------------------------------------ #


def test_live_pages_are_counted_from_the_slots_lengths():
    t = Tracer()
    set_tracer(t)
    try:
        cfg, params = _model()
        eng = ServingEngine(cfg, params, SCFG)
        eng.submit(list(range(1, 6)), max_new_tokens=3, request_id="a")   # 5
        eng.submit(list(range(1, 9)), max_new_tokens=2, request_id="b")   # 8
        eng.run()
    finally:
        set_tracer(None)
    bs, view = SCFG.block_size, SCFG.num_slots * SCFG.blocks_per_slot
    # a prefill emits a request's first token; decode step j then runs with
    # prompt + j positions cached: "a" takes two steps, "b" one
    by_step = [[5, 8], [6]]
    want = [sum(blocks_needed(n + 1, bs) for n in step) for step in by_step]
    assert want == [2 + 3, 2]
    spans = [e["args"] for e in t.events()
             if e["name"] == "serving/decode/dispatch"]
    assert [(a["live_pages"], a["view_pages"]) for a in spans] \
        == [(w, view) for w in want]
    assert eng.metrics.summary()["kv_live_page_frac"] \
        == pytest.approx(sum(want) / (len(want) * view))
