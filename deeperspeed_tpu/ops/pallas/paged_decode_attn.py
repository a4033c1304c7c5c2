"""Paged decode attention: one new token a slot over the slot's LIVE pages.

The serving decode step attends, per layer and slot, over the pool's
logical positions ``< length`` plus the new token's own key and value.
The XLA form (serving/kv_cache.paged_attend_rows, this kernel's oracle)
gathers ``blocks_per_slot`` pages for every slot whatever its length and
materialises the gathered view; XLA cannot express a per-slot ragged
bound. This kernel walks the block table instead:

  * the stacked pools ``(L, num_blocks, bs, Hkv, Dh)`` stay in HBM
    (``pl.ANY``); the layer index, the lengths and the block tables are
    scalar prefetch, so page addresses are computed before a page is
    needed;
  * a slot's work is ``ceil(length / chunk)`` chunks of ``pages`` whole
    pages (one contiguous ``bs * Hkv * Dh`` run each in this layout),
    copied to VMEM by double-buffered DMA; the next chunk — of this slot,
    or the first of the next LIVE slot — is in flight while this one is
    computed; an idle slot (length 0) costs nothing and returns its new
    token's value;
  * K and V are read once, in the pool's dtype, into float32
    accumulations: a running maximum and sum (online softmax), the
    probabilities cast to the pool's dtype before they meet V, as the XLA
    form casts them;
  * a page keeps the pool's layout ``(bs, Hkv, Dh)``, heads interleaved
    position by position, so one MXU product of the slot's ``(H, Dh)``
    queries with a chunk's ``(chunk * Hkv, Dh)`` rows gives every
    (query head, key head) pair and a mask keeps each head's own group:
    ``Hkv`` times the necessary products on a unit that is otherwise
    idle, and no transposed copy of the pool.

Table entries past a slot's live pages point at the null block (finite
rows, masked by the length), so a chunk is always copied whole.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import kernel_config

NEG_INF = -1e30
# positions a chunk aims at: 8 pages of 16, 1 MiB of K and V in flight at
# Hkv 16 x Dh 128 bf16, long enough to hide a DMA's start behind compute
_CHUNK_TOKENS = 128
_VMEM_BUDGET = 8 * 2 ** 20     # both pools, double-buffered
_SMEM_BUDGET = 256 * 2 ** 10   # the flattened block tables


def _pages_per_chunk(blocks_per_slot, page_bytes, block_size):
    """Whole pages a chunk holds: a divisor of ``blocks_per_slot`` (a
    chunk never runs off a table row), near ``_CHUNK_TOKENS`` positions,
    inside the VMEM budget; 0 when not even one page fits."""
    most = min(max(1, _CHUNK_TOKENS // block_size),
               _VMEM_BUDGET // (4 * page_bytes), blocks_per_slot)
    return next((p for p in range(most, 0, -1)
                 if blocks_per_slot % p == 0), 0)


def is_available(k_pool, tables, n_head) -> bool:
    """Whether the compiled kernel can take this decode program, from
    what its shapes say: the key heads of one position must fill whole
    sublane tiles (so a chunk's pages read as one ``(rows, Dh)`` matrix),
    the head size whole lanes (Mosaic slices no page out of a pool whose
    rows are padded to 128 lanes, as head size 64 is), the block tables
    must fit scalar memory, and a page the VMEM budget."""
    if not kernel_config.on_tpu():
        return False
    _, _, bs, Hkv, Dh = k_pool.shape
    item = k_pool.dtype.itemsize
    if item not in (2, 4) or Hkv % (32 // item) or n_head % Hkv or Dh % 128:
        return False
    if tables.size * 4 > _SMEM_BUDGET:
        return False
    return _pages_per_chunk(tables.shape[1], bs * Hkv * Dh * item, bs) > 0


def _kernel(layer_ref, lengths_ref, tables_ref, q_ref, kn_ref, vn_ref,
            k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, *, pages, sm_scale):
    N, H, Dh = q_ref.shape
    bs, Hkv = kbuf.shape[2], kbuf.shape[3]
    bps = tables_ref.shape[0] // N
    chunk = pages * bs
    rows = chunk * Hkv
    rep = H // Hkv
    layer = layer_ref[0]

    def for_each_copy(slot, c, buf, act):
        """``act`` on the DMA of every page of chunk ``c`` of ``slot``
        into buffer ``buf``: K and V, one semaphore a pool and buffer."""
        def page_copies(p, _):
            page = tables_ref[slot * bps + c * pages + p]
            act(pltpu.make_async_copy(
                k_hbm.at[layer, page], kbuf.at[buf, p], sems.at[0, buf]))
            act(pltpu.make_async_copy(
                v_hbm.at[layer, page], vbuf.at[buf, p], sems.at[1, buf]))

        jax.lax.fori_loop(0, pages, page_copies, None)

    def start(slot, c, buf):
        for_each_copy(slot, c, buf, lambda cp: cp.start())

    def next_live(slot):
        """The first slot after ``slot`` with cached positions, else N."""
        return jax.lax.fori_loop(
            slot + 1, N,
            lambda j, r: jnp.where((r == N) & (lengths_ref[j] > 0), j, r),
            jnp.int32(N))

    # column r of a chunk's score matrix is position r // Hkv, key head
    # r % Hkv; query head h keeps the columns of key head h // rep
    col = jax.lax.broadcasted_iota(jnp.int32, (H, rows), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (H, rows), 0)
    own_head = jax.lax.rem(col, Hkv) == jax.lax.div(row, rep)
    col_pos = jax.lax.div(col, Hkv)

    first = next_live(jnp.int32(-1))

    @pl.when(first < N)
    def _():
        start(first, 0, 0)

    def slot_body(slot, g):
        length = lengths_ref[slot]
        n_chunks = pl.cdiv(length, chunk)
        q = q_ref[slot]                                     # (H, Dh)
        # the new token's own key and value: position == length
        s_new = jnp.sum(q.astype(jnp.float32)
                        * kn_ref[slot].astype(jnp.float32),
                        axis=1, keepdims=True) * sm_scale   # (H, 1)

        def chunk_body(c, carry):
            g, m, l, acc = carry
            buf = jax.lax.rem(g, 2)

            # what comes after this chunk goes in flight before it is
            # computed: the slot's next chunk, else the next live slot's
            # first
            nxt_slot, nxt_c = jax.lax.cond(
                c + 1 < n_chunks, lambda: (slot, c + 1),
                lambda: (next_live(slot), jnp.int32(0)))

            @pl.when(nxt_slot < N)
            def _():
                start(nxt_slot, nxt_c, 1 - buf)

            for_each_copy(slot, c, buf, lambda cp: cp.wait())
            k = kbuf[buf].reshape(rows, Dh)
            v = vbuf[buf].reshape(rows, Dh)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale  # (H, rows)
            keep = own_head & (c * chunk + col_pos < length)
            s = jnp.where(keep, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)          # exactly 0 where masked
            l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
            acc = alpha * acc + jnp.dot(p.astype(v.dtype), v,
                                        preferred_element_type=jnp.float32)
            return g + 1, m_new, l, acc

        g, _, l, acc = jax.lax.fori_loop(
            0, n_chunks, chunk_body,
            (g, s_new, jnp.ones_like(s_new),
             vn_ref[slot].astype(jnp.float32)))
        o_ref[slot] = (acc / l).astype(o_ref.dtype)
        return g

    jax.lax.fori_loop(0, N, slot_body, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attn(k_pool, v_pool, layer, q, k_row, v_row, tables,
                      lengths, interpret=False):
    """serving/kv_cache.paged_attend_rows as one kernel (its docstring
    has the contract): ctx (N, 1, H, Dh) of the layer ``layer`` of the
    stacked pools, slot i over positions ``< lengths[i]`` of its pages
    plus its new row ``k_row[i]``/``v_row[i]`` (N, Hkv, Dh)."""
    N, _, H, Dh = q.shape
    _, _, bs, Hkv, _ = k_pool.shape
    rep = H // Hkv
    pages = _pages_per_chunk(tables.shape[1],
                             bs * Hkv * Dh * k_pool.dtype.itemsize, bs)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_kernel, pages=pages,
                          sm_scale=1.0 / math.sqrt(Dh)),
        name="paged_decode_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[vmem, vmem, vmem, hbm, hbm],
            out_specs=vmem,
            scratch_shapes=[
                pltpu.VMEM((2, pages, bs, Hkv, Dh), k_pool.dtype),
                pltpu.VMEM((2, pages, bs, Hkv, Dh), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((N, H, Dh), q.dtype),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      lengths.astype(jnp.int32), tables.reshape(-1).astype(jnp.int32),
      q[:, 0], jnp.repeat(k_row, rep, axis=1), jnp.repeat(v_row, rep, axis=1),
      k_pool, v_pool)
    return out[:, None]
