"""``ops/pallas/chunk_past_attn`` interpreted on the CPU against the
three XLA forms it replaces on one TPU (``kv_cache.chunk_attend_past`` for
a layer that keeps every key, ``kv_cache.ring_chunk_attend`` for one that
keeps a ring, ``kv_cache.chunk_attend_all`` for an eva layer's list of
two roles and its count), on a toy pool: pages of 16 rows, chunks of 64
queries, tiles small enough that a chunk is several query tiles and a walk
several steps. And the chooser between them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeperspeed_tpu.ops import kernel_config
from deeperspeed_tpu.ops.pallas import chunk_past_attn as kernel
from deeperspeed_tpu.serving import kv_cache as kvc

BS, C, DH, NB, LAYERS = 16, 64, 32, 40, 3


def world(Hkv, G, dtype, seed=0):
    """Pools of ``NB`` pages of unit normals (page 0 the null page: what
    it holds is as finite as any), a chunk's queries, keys and values."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    pool = lambda key: jax.random.normal(
        key, (LAYERS, NB, Hkv, BS, DH)).astype(dtype)
    rows = lambda key, h: jax.random.normal(key, (C, h, DH)).astype(dtype)
    return (pool(ks[0]), pool(ks[1]), rows(ks[2], Hkv * G), rows(ks[3], Hkv),
            rows(ks[4], Hkv))


def table(n, seed=0):
    """``n`` distinct pages of the pool, none the null page."""
    return jnp.asarray(
        np.random.default_rng(seed).permutation(np.arange(1, NB))[:n],
        jnp.int32)


def close(got, want, dtype):
    # the same sums in another order of tiles: float32 rounding, and for
    # bfloat16 the last bit of the probabilities and of the result
    atol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


TILES = [pytest.param(16, 32, id="four_tiles_two_pages_a_step"),
         pytest.param(32, 16, id="two_tiles_a_page_a_step"),
         pytest.param(None, None, id="one_tile_the_chunk_a_step")]


@pytest.mark.parametrize("q_tile,step_keys", TILES)
@pytest.mark.parametrize("offset", [0, C, 5 * C])
@pytest.mark.parametrize("Hkv,G", [(2, 1), (4, 2), (2, 8)])
def test_every_key_against_chunk_attend_past(Hkv, G, offset, q_tile,
                                             step_keys):
    dtype = jnp.float32
    kp, vp, q, k, v = world(Hkv, G, dtype)
    # the slot's pages, then null pages where the chunk itself will lie
    row = jnp.concatenate([table(offset // BS),
                           jnp.zeros(C // BS + 3, jnp.int32)])
    layer, off = jnp.int32(1), jnp.int32(offset)
    want = kvc.chunk_attend_past(kp, vp, layer, q, k, v, row, off)
    got = kernel.chunk_past_attn(kp, vp, layer, q, k, v, row, 0, off,
                                 q_tile=q_tile, step_keys=step_keys,
                                 interpret=True)
    assert got.shape == q.shape and got.dtype == q.dtype
    close(got, want, dtype)


@pytest.mark.parametrize("q_tile,step_keys", TILES[:2])
@pytest.mark.parametrize("window,offset", [
    pytest.param(64, 0, id="ring64_nothing_held"),
    pytest.param(128, 0, id="ring128_nothing_held"),
    pytest.param(128, 64, id="ring128_half_held"),
    pytest.param(64, 64, id="ring64_exactly_full"),
    pytest.param(128, 128, id="ring128_exactly_full"),
    pytest.param(64, 7 * 64, id="ring64_wrapped_seven_times"),
    pytest.param(128, 5 * 64, id="ring128_starts_mid_ring"),
    pytest.param(128, 11 * 64, id="ring128_wrapped_five_times")])
@pytest.mark.parametrize("Hkv,G", [(2, 2), (4, 8)])
def test_the_band_against_ring_chunk_attend(Hkv, G, window, offset, q_tile,
                                            step_keys):
    dtype = jnp.float32
    kp, vp, q, k, v = world(Hkv, G, dtype, seed=1)
    ring_row = table(window // BS, seed=2)
    layer, off = jnp.int32(2), jnp.int32(offset)
    want = kvc.ring_chunk_attend(window, kp, vp, layer, q, k, v, ring_row,
                                 off)
    ring_list = kvc.ring_oldest_first(window, ring_row, off)
    # the list's first page is the one the chunk will be written over
    assert int(ring_list[0]) == int(ring_row[offset % window // BS])
    got = kernel.chunk_past_attn(
        kp, vp, layer, q, k, v, ring_list, jnp.maximum(window - off, 0),
        window, band=True, q_tile=q_tile, step_keys=step_keys,
        interpret=True)
    close(got, want, dtype)


# the byte cell's list at a quarter of its page and a sixteenth of its
# chunk: a window left behind leaves 2 pages of summaries (128 rows of 64
# there, 32 of 16 here), the window's pages before the chunk are none or
# one chunk's (1,024 rows there, 64 here), 48 entries in all
SUMMARIES, LISTED = 2 * BS, 48


def two_roles(w, r, seed=0):
    """An eva layer's list before a chunk (``kv_cache.eva_page_list``):
    the summary pages of ``w`` windows, then the window's pages holding
    ``r`` rows; what lies beyond the count names the null page."""
    count = SUMMARIES * w + r
    row = jnp.concatenate([table(-(-count // BS), seed),
                           jnp.zeros(LISTED, jnp.int32)])[:LISTED]
    return row, jnp.int32(count)


@pytest.mark.parametrize("q_tile,step_keys", TILES)
@pytest.mark.parametrize("r", [0, C])
@pytest.mark.parametrize("w", [0, 1, 7, 15])
def test_a_list_of_two_roles_against_chunk_attend_all(w, r, q_tile,
                                                      step_keys):
    """ONE query a key head (``Hkv = H``), a list and a count of rows
    ``2 pages x w + r``, no band: what ``make_chunk_step``'s eva layers
    ask of the kernel on one TPU, against the XLA form they run
    elsewhere, which computes all 48 pages under a mask."""
    dtype = jnp.float32
    kp, vp, q, k, v = world(4, 1, dtype, seed=11)
    row, count = two_roles(w, r, seed=12)
    layer = jnp.int32(2)
    want = kvc.chunk_attend_all(kp, vp, layer, q, k, v, row, count, LISTED)
    got = kernel.chunk_past_attn(kp, vp, layer, q, k, v, row, 0, count,
                                 q_tile=q_tile, step_keys=step_keys,
                                 interpret=True)
    assert got.shape == q.shape and got.dtype == q.dtype
    close(got, want, dtype)


@pytest.mark.parametrize("Hkv,G,band,first,count", [
    pytest.param(4, 8, False, 0, 3 * C, id="every_key"),
    pytest.param(4, 8, True, C // 2, C, id="the_band"),
    pytest.param(4, 1, False, 0, 0, id="two_roles_at_offset_0"),
    pytest.param(4, 1, False, 0, SUMMARIES + C,
                 id="two_roles_one_window_behind"),
    pytest.param(4, 1, False, 0, 7 * SUMMARIES,
                 id="two_roles_seven_windows_behind")])
def test_bfloat16_as_the_cell_runs_it(Hkv, G, band, first, count):
    """The pool's dtype all through: bfloat16 operands, float32 scores,
    probabilities cast to bfloat16 before they meet V. The CPU has no
    bfloat16 product for the XLA forms, so the oracle is the contract
    written out, in float32 on the same bfloat16 values."""
    dtype, f32 = jnp.bfloat16, jnp.float32
    kp, vp, q, k, v = world(Hkv, G, dtype, seed=3)
    layer = jnp.int32(0)
    # 15 pages hold the longest count; what lies beyond one is not seen
    pages = jnp.concatenate([table(15, seed=4), jnp.zeros(4, jnp.int32)])
    pages = pages[:C // BS] if band else pages
    want = plain(*(a.astype(f32) for a in (kp, vp)), layer,
                 *(a.astype(f32) for a in (q, k, v)), pages, first, count,
                 band)
    got = kernel.chunk_past_attn(kp, vp, layer, q, k, v, pages, first, count,
                                 band=band, q_tile=16, step_keys=32,
                                 interpret=True)
    assert got.dtype == dtype
    close(got, want, dtype)


def plain(kp, vp, layer, q, k, v, pages, first, count, band):
    """The kernel's contract, written out: every query against every list
    position and every own key, one softmax."""
    Hkv = kp.shape[2]
    G = q.shape[1] // Hkv
    past = lambda pool: jnp.swapaxes(pool[layer, pages], 0, 1).reshape(
        Hkv, -1, DH)
    keys = jnp.concatenate([past(kp), jnp.swapaxes(k, 0, 1)], 1)
    vals = jnp.concatenate([past(vp), jnp.swapaxes(v, 0, 1)], 1)
    n = pages.shape[0] * BS
    i, p = jnp.arange(C)[:, None], jnp.arange(n)[None, :]
    sees_past = (p >= first) & (p < count) & ((p > i) | (not band))
    sees = jnp.concatenate([sees_past, i >= jnp.arange(C)[None, :]], 1)
    s = jnp.einsum("qhgd,hkd->qhgk", q.reshape(C, Hkv, G, DH), keys,
                   preferred_element_type=jnp.float32) / np.sqrt(DH)
    pr = jax.nn.softmax(jnp.where(sees[:, None, None, :], s, -1e30), -1)
    return jnp.einsum("qhgk,hkd->qhgd", pr, vals).reshape(C, Hkv * G, DH)


@pytest.mark.parametrize("q_tile,step_keys", TILES[:2])
@pytest.mark.parametrize("first,count,band", [
    pytest.param(0, 70, False, id="a_count_inside_a_page"),
    pytest.param(0, 33, False, id="a_count_inside_a_step"),
    pytest.param(21, 90, False, id="a_first_and_a_count_inside_pages"),
    pytest.param(40, 128, True, id="the_band_over_a_first_inside_a_page"),
    pytest.param(0, 1, False, id="one_position")])
def test_a_ragged_count_against_the_contract(first, count, band, q_tile,
                                             step_keys):
    """Edges the two callers never make (their counts are whole chunks):
    the walk still ends at the count's step and masks inside it, and a
    list's tail that names the null page is never seen."""
    dtype = jnp.float32
    kp, vp, q, k, v = world(2, 2, dtype, seed=5)
    pages = jnp.concatenate([table(6, seed=6), jnp.zeros(2, jnp.int32)])
    layer = jnp.int32(1)
    want = plain(kp, vp, layer, q, k, v, pages, first, count, band)
    got = kernel.chunk_past_attn(
        kp, vp, layer, q, k, v, pages, jnp.int32(first), jnp.int32(count),
        band=band, q_tile=q_tile, step_keys=step_keys, interpret=True)
    close(got, want, dtype)


def test_a_list_that_is_no_multiple_of_a_step_is_padded_with_the_null_page():
    dtype = jnp.float32
    kp, vp, q, k, v = world(2, 2, dtype, seed=7)
    pages = table(5, seed=8)                 # 5 pages, steps of 2
    want = plain(kp, vp, jnp.int32(0), q, k, v, pages, 0, 80, False)
    got = kernel.chunk_past_attn(kp, vp, jnp.int32(0), q, k, v, pages, 0, 80,
                                 q_tile=32, step_keys=32, interpret=True)
    close(got, want, dtype)


def test_the_forms_the_chooser_returns(monkeypatch):
    """The kernel on one TPU at the code cell's geometry; the XLA forms
    off the TPU and under a mesh; both kernel forms give what their XLA
    twins give for the same arguments (the ring's pages as ``ring_pages``
    hands them)."""
    from jax.sharding import Mesh

    sds = jax.ShapeDtypeStruct
    full = sds((2, 16385, 4, 64, 128), jnp.bfloat16)
    rings = sds((6, 513, 4, 64, 128), jnp.bfloat16)
    roles = sds((8, 1025, 32, 64, 128), jnp.bfloat16)     # the byte cell's
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))

    def is_xla(attend):
        row = jnp.arange(4)
        return (attend.name == "xla" and attend.past is kvc.chunk_attend_past
                and attend.ring is kvc.ring_chunk_attend
                and attend.ring_pages(64, row, 128) is row
                and attend.listed is kvc.chunk_attend_all)

    for pool in (full, roles):                                    # the CPU
        assert is_xla(kvc.chunk_attend_for(pool, 32, 1024, None))
    monkeypatch.setattr(kernel_config, "on_tpu", lambda: True)
    for pool in (full, rings, roles):
        attend = kvc.chunk_attend_for(pool, 32, 1024, None)
        assert attend.name == "kernel"
        assert attend.past is kvc.chunk_attend_past_kernel
        assert attend.ring is kvc.ring_chunk_attend_kernel
        assert attend.ring_pages is kvc.ring_oldest_first
        assert attend.listed is kvc.chunk_attend_all_kernel
        assert is_xla(kvc.chunk_attend_for(pool, 32, 1024, mesh))


@pytest.mark.parametrize("offset", [0, 64, 192])
def test_the_kernel_forms_give_what_their_xla_twins_give(monkeypatch, offset):
    """``chunk_attend_past_kernel``, ``ring_chunk_attend_kernel`` and
    ``chunk_attend_all_kernel`` with the arguments ``make_chunk_step``
    hands them."""
    window = 128
    kp, vp, q, k, v = world(2, 4, jnp.float32, seed=9)
    compiled = kernel.chunk_past_attn
    monkeypatch.setattr(
        kernel, "chunk_past_attn",
        lambda *a, **kw: compiled(*a, **kw, q_tile=16, step_keys=32,
                                  interpret=True))
    row = jnp.concatenate([table(offset // BS),
                           jnp.zeros(C // BS, jnp.int32)])
    ring_row = table(window // BS, seed=10)
    layer, off = jnp.int32(1), jnp.int32(offset)
    close(kvc.chunk_attend_past_kernel(kp, vp, layer, q, k, v, row, off),
          kvc.chunk_attend_past(kp, vp, layer, q, k, v, row, off),
          jnp.float32)
    close(kvc.ring_chunk_attend_kernel(
        window, kp, vp, layer, q, k, v,
        kvc.ring_oldest_first(window, ring_row, off), off),
        kvc.ring_chunk_attend(window, kp, vp, layer, q, k, v, ring_row, off),
        jnp.float32)
    # a list of two roles wider than ``n_past``: one window behind, then
    # ``offset`` rows of the window's own pages
    listed, count = two_roles(1, offset, seed=13)
    listed = jnp.concatenate([listed, table(4, seed=14)])
    close(kvc.chunk_attend_all_kernel(kp, vp, layer, q, k, v, listed, count,
                                      LISTED),
          kvc.chunk_attend_all(kp, vp, layer, q, k, v, listed, count, LISTED),
          jnp.float32)


@pytest.mark.parametrize("pool,n_head,C_,why", [
    pytest.param((2, 9, 4, 64, 64), 32, 1024, "Dh is half a lane tile",
                 id="a_narrow_head"),
    pytest.param((2, 9, 4, 8, 128), 32, 1024, "a page is half a bf16 tile",
                 id="a_short_page"),
    pytest.param((2, 9, 4, 64, 128), 30, 1024, "30 heads over 4",
                 id="heads_that_do_not_group"),
    pytest.param((2, 9, 4, 64, 128), 32, 1000, "a chunk of part pages",
                 id="a_chunk_of_part_pages"),
    pytest.param((2, 9, 4, 16, 128), 4, 72, "a tile of 72 rows: half a "
                 "bf16 tile over", id="a_tile_of_part_tiles"),
    pytest.param((2, 9, 64, 64, 128), 1024, 1024, "a tile of 4,096 rows a "
                 "key head at 64 key heads: more than VMEM",
                 id="more_than_vmem")])
def test_is_available_refuses_what_the_kernel_cannot_tile(
        monkeypatch, pool, n_head, C_, why):
    sds = jax.ShapeDtypeStruct
    assert not kernel.is_available(sds((2, 9, 4, 64, 128), jnp.bfloat16),
                                   32, 1024)             # off the TPU
    monkeypatch.setattr(kernel_config, "on_tpu", lambda: True)
    assert kernel.is_available(sds((2, 9, 4, 64, 128), jnp.bfloat16), 32,
                               1024)
    assert kernel.is_available(sds((2, 9, 2, 16, 128), jnp.float32), 4, 64)
    assert not kernel.is_available(sds(pool, jnp.bfloat16), n_head, C_), why
    assert not kernel.is_available(sds((2, 9, 4, 64, 128), jnp.int8), 32,
                                   1024)
