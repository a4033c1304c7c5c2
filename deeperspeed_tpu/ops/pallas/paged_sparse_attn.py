"""Paged attention over the pages a SELECTION names.

``paged_decode_attn`` reads the pages a length implies: every live page of
a slot, all key heads of a position side by side. A block-sparse layer
(InfLLM-v2, the ``minicpm4`` mixer) names, for every query and key head, a
list of pages that is the same size whatever the context, and has two key
heads, which a pool with the heads last would pad to a tile of sixteen. So
the pools here are ``(L, num_blocks, Hkv, bs, Dh)``, left in HBM
(``pl.ANY``): one key head's page is one contiguous ``(bs, Dh)`` tile run,
and a whole page, every key head of it, one contiguous ``(Hkv, bs, Dh)``
run. A list is ``P`` physical pages, read in order, of which the first
``n_tokens`` positions count; lists and counts are scalar prefetch, so a
page's address is known before the page is needed. Whoever owns a list has
attended over something already (the new token's own key in a decode step,
the chunk's own keys in a prompt chunk) and brings it as a running
maximum, sum and unnormalised output, so the result is one softmax over
both. Two forms, by who owns a list:

  * ``paged_sparse_attn``, a ROW a list: a row is one query position's
    ``G`` heads that share a key head (a decode step of a selecting layer
    has ``slots * Hkv`` rows, a prompt chunk ``tokens * Hkv``). A copy is
    one key head's page, 16 KiB: too small to pay for a descriptor built
    twice and a wait of its own (0.8 us for 8 of them where their bytes
    take 0.3: PERF.md, PR 46), and a row's pages are scattered, so the
    copies cannot be larger: they are made CHEAP and MANY. A copy-chunk
    is as many pages as 2,048 positions and the buffers' room allow
    (``_pages_per_row_chunk``: a prompt chunk's whole list of 32, a
    quarter of a decode step's 128), always whole, so entries past the
    last that counts must still name a page of the pool; its copies are
    started from straight-line code, without Mosaic's bounds check of
    each (the jitted wrapper clamps layer, page and key head into the
    pool), and waited for ONCE a buffer and pool, on the bytes of the
    whole buffer. On a v5e the copies then bind: 1,024 rows of 32 pages
    take 1.58 ms where their copies alone take 1.54 (3.39 before).
  * ``paged_sparse_attn_slots``, a SLOT a list: every key head of the slot
    reads the same pages (``kv_cache.decode_attend_all``: a slot's whole
    table, or its list of two roles), so a copy is a whole page, all key
    heads in one DMA (512 KiB where the row form makes 32 copies of 16
    KiB), and a page past the count is not copied at all. The slot's ``Hkv
    * G`` queries meet a copy-chunk's keys in ONE product, every query
    against every key head's keys, and a query keeps its own key head's
    columns (the others are masked like the positions past the count, so
    their probabilities are exact zeros): the MXU takes a key row a cycle
    whichever query it is for, and at one query a key head this keeps the
    products under the copy (stand-alone on a v5e at 16 slots x 32 key
    heads: the copies alone 386 us a call, with the products 390).

Both: one grid step a row or slot; its work is ``ceil(n_tokens / chunk)``
copy-chunks brought to VMEM by double-buffered DMA, the next one (its own,
or the first of the next row or slot that has any) in flight while this
one is computed, so an idle one costs a grid step and no copy; K and V are
read once, in the pool's dtype, into float32 accumulations (online
softmax), the probabilities cast to the pool's dtype before they meet V,
as the XLA form (serving/kv_cache.paged_sparse_attend_xla, the oracle of
both) casts them.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import kernel_config

NEG_INF = -1e30
# a copy-chunk of the SLOT form: 8 pages of 64 (and what the width of a
# prompt chunk's list of chosen pages is taken up to: kv_cache.
# chosen_list_width)
_CHUNK_TOKENS = 512
# a copy-chunk of the ROW form: a prompt chunk's whole list, 32 pages of 64
# (512 KiB of K, as much of V, two buffers each)
_ROW_TOKENS = 2048
_ROW_VMEM = 2 * 2 ** 20
_SMEM_BUDGET = 192 * 2 ** 10   # one call's page lists
LANES = 128


def _pages_per_row_chunk(P, k_pool):
    """Pages of one key head a copy-chunk of the row form holds: a list's
    pages up to ``_ROW_TOKENS`` positions, what the four buffers' room
    allows (32 pages of 16 KiB, 16 of float32), a divisor of the list."""
    _, _, _, bs, Dh = k_pool.shape
    most = min(max(1, _ROW_TOKENS // bs),
               max(1, _ROW_VMEM // (4 * bs * Dh * k_pool.dtype.itemsize)), P)
    return next(p for p in range(most, 0, -1) if P % p == 0)


def rows_per_call(R, P):
    """Rows one call takes: all of them, or the largest divisor of R whose
    page lists fit scalar memory."""
    most = max(1, _SMEM_BUDGET // (4 * P))
    return next(r for r in range(min(R, most), 0, -1) if R % r == 0)


def is_available(k_pool, n_head) -> bool:
    """Whether the compiled kernel can take this pool: a page must be
    whole tiles (``bs`` rows of the dtype's sublane packing, ``Dh`` whole
    lanes)."""
    if not kernel_config.on_tpu():
        return False
    _, _, Hkv, bs, Dh = k_pool.shape
    item = k_pool.dtype.itemsize
    return (item in (2, 4) and bs % (32 // item) == 0 and Dh % LANES == 0
            and n_head % Hkv == 0)


def _walk_lists(r, ntok_ref, g_ref, start, wait, pos_of, q_ref, m_ref, l_ref,
                acc_ref, o_ref, kbuf, vbuf, chunk, sm_scale):
    """Grid step ``r``: the copy-chunks of row (or slot) ``r`` through the
    online softmax. ``start(row, c, buf)`` starts every copy of row
    ``row``'s copy-chunk ``c`` into buffer ``buf`` and ``wait(row, c,
    buf)`` returns when all of them have landed; ``pos_of(Q)`` is, for
    each of the step's Q queries and each row of a buffer, that row's
    position in the copy-chunk (int32 (Q, rows)). What lies at
    ``n_tokens`` or beyond does not count. ``g_ref`` counts the
    copy-chunks of the whole call: the buffers alternate across steps."""
    R = pl.num_programs(0)
    Dh = kbuf.shape[-1]

    def next_live(row):
        """The first row after ``row`` with anything to read, else R."""
        return jax.lax.while_loop(
            lambda j: (j < R) & (ntok_ref[jnp.minimum(j, R - 1)] == 0),
            lambda j: j + 1, row + 1)

    @pl.when(r == 0)
    def _():
        g_ref[0] = 0
        first = next_live(jnp.int32(-1))

        @pl.when(first < R)
        def _():
            start(first, 0, 0)

    n_tok = ntok_ref[r]
    n_chunks = pl.cdiv(n_tok, chunk)
    q = q_ref[0]                                            # (Q, Dh)
    pos = pos_of(q.shape[0])

    def chunk_body(c, carry):
        m, l, acc = carry
        g = g_ref[0]
        buf = jax.lax.rem(g, 2)
        nxt_row, nxt_c = jax.lax.cond(
            c + 1 < n_chunks, lambda: (r, c + 1),
            lambda: (next_live(r), jnp.int32(0)))

        @pl.when(nxt_row < R)
        def _():
            start(nxt_row, nxt_c, 1 - buf)

        wait(r, c, buf)
        g_ref[0] = g + 1
        k = kbuf[buf].reshape(-1, Dh)
        v = vbuf[buf].reshape(-1, Dh)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # (Q, rows)
        s = jnp.where(c * chunk + pos < n_tok, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = alpha * acc + jnp.dot(p.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc

    _, l, acc = jax.lax.fori_loop(
        0, n_chunks, chunk_body,
        (m_ref[0][:, :1], l_ref[0][:, :1], acc_ref[0]))
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def _kernel(layer_ref, head_ref, ntok_ref, pages_ref, q_ref, m_ref, l_ref,
            acc_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, g_ref, *,
            P, sm_scale):
    _, pp, bs, _ = kbuf.shape
    chunk = pp * bs
    layer = layer_ref[0]

    def start(row, c, buf):
        # straight-line code, the row's key head and place in the lists
        # read once: a page's address arithmetic is scheduled beside its
        # neighbours', where a loop a page ran them one after another. The
        # loop is unrolled when it is LOWERED: traced a page at a time in
        # Python, 64 descriptors cost 0.5 s a trace of the kernel
        head, first = head_ref[row], row * P + c * pp

        def page_copies(p, _):
            page = pages_ref[first + p]
            pltpu.make_async_copy(
                k_hbm.at[layer, page, head], kbuf.at[buf, p],
                sems.at[0, buf]).start()
            pltpu.make_async_copy(
                v_hbm.at[layer, page, head], vbuf.at[buf, p],
                sems.at[1, buf]).start()

        jax.lax.fori_loop(0, pp, page_copies, None, unroll=True)

    def wait(row, c, buf):
        # a DMA semaphore counts bytes: ONE wait for a whole buffer takes
        # what its ``pp`` copies, all of them always started, signalled
        for pages, s in ((kbuf, 0), (vbuf, 1)):
            pltpu.make_async_copy(
                pages.at[buf], pages.at[buf], sems.at[s, buf]).wait()

    _walk_lists(
        pl.program_id(0), ntok_ref, g_ref, start, wait,
        lambda G: jax.lax.broadcasted_iota(jnp.int32, (G, chunk), 1),
        q_ref, m_ref, l_ref, acc_ref, o_ref, kbuf, vbuf, chunk, sm_scale)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_sparse_attn(k_pool, v_pool, layer, q, row_head, pages, n_tokens,
                      m0, l0, acc0, interpret=False):
    """serving/kv_cache.paged_sparse_attend_xla as a kernel (its docstring
    has the contract)."""
    R, G, Dh = q.shape
    L, nb, Hkv, bs, _ = k_pool.shape
    P = pages.shape[1]
    pp = _pages_per_row_chunk(P, k_pool)
    rows = rows_per_call(R, P)
    lanes = lambda a: jnp.broadcast_to(a[..., None], (R, G, LANES))
    # a copy's source is (layer, page, key head) of the pool: each clamped
    # into it, as the XLA form's gather clamps them
    inside = lambda a, n: jnp.clip(a.astype(jnp.int32), 0, n - 1)
    layer = inside(jnp.reshape(layer, (1,)), L)
    args = (inside(row_head, Hkv), inside(pages, nb),
            n_tokens.astype(jnp.int32), q, lanes(m0.astype(jnp.float32)),
            lanes(l0.astype(jnp.float32)), acc0.astype(jnp.float32))

    def call(a):
        row_head, pages, n_tokens, q, m0, l0, acc0 = a
        row = lambda w: pl.BlockSpec((1, G, w), lambda r, *_: (r, 0, 0))
        hbm = pl.BlockSpec(memory_space=pl.ANY)
        return pl.pallas_call(
            functools.partial(_kernel, P=P, sm_scale=1.0 / math.sqrt(Dh)),
            name="paged_sparse_attn",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(rows,),
                in_specs=[row(Dh), row(LANES), row(LANES), row(Dh), hbm, hbm],
                out_specs=row(Dh),
                scratch_shapes=[
                    pltpu.VMEM((2, pp, bs, Dh), k_pool.dtype),
                    pltpu.VMEM((2, pp, bs, Dh), v_pool.dtype),
                    pltpu.SemaphoreType.DMA((2, 2)),
                    pltpu.SMEM((1,), jnp.int32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((rows, G, Dh), q.dtype),
            # Mosaic's own check of each copy's two ends is two thirds of
            # the instructions a descriptor takes; the clamps above do its
            # work
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                disable_bounds_checks=True),
            interpret=interpret,
        )(layer, row_head, n_tokens, pages.reshape(-1), q, m0, l0, acc0,
          k_pool, v_pool)

    if rows == R:
        return call(args)
    out = jax.lax.map(call, jax.tree.map(
        lambda a: a.reshape(R // rows, rows, *a.shape[1:]), args))
    return out.reshape(R, G, Dh)


# ------------------------------------------------------------------ #
# a SLOT a list
# ------------------------------------------------------------------ #

_SLOT_VMEM = 4 * 2 ** 20       # K and V, two buffers each, of whole pages


def _page_bytes(k_pool):
    _, _, Hkv, bs, Dh = k_pool.shape
    return Hkv * bs * Dh * k_pool.dtype.itemsize


def _pages_per_slot_chunk(P, k_pool):
    """Whole pages a copy-chunk of the slot form holds: what the four
    buffers' room allows (2 pages of 512 KiB, 8 of 64 KiB), no more tokens
    than the row form's, a divisor of the list."""
    most = min(max(1, _SLOT_VMEM // (4 * _page_bytes(k_pool))),
               max(1, _CHUNK_TOKENS // k_pool.shape[3]), P)
    return next(p for p in range(most, 0, -1) if P % p == 0)


def slots_available(k_pool, n_head, lists_shape) -> bool:
    """Whether the compiled slot form can take this pool and lists of
    ``lists_shape`` = (N, P): what ``is_available`` asks, a page that fits
    a buffer, and every slot's list in scalar memory."""
    return (is_available(k_pool, n_head)
            and 4 * _page_bytes(k_pool) <= _SLOT_VMEM
            and 4 * math.prod(lists_shape) <= _SMEM_BUDGET)


def _slots_kernel(layer_ref, ntok_ref, pages_ref, q_ref, m_ref, l_ref,
                  acc_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, g_ref, *,
                  P, G, sm_scale):
    _, pp, Hkv, bs, _ = kbuf.shape
    chunk = pp * bs
    layer = layer_ref[0]

    def for_each_copy(slot, c, buf, act):
        for p in range(pp):
            @pl.when((c * pp + p) * bs < ntok_ref[slot])
            def _():
                page = pages_ref[slot * P + c * pp + p]
                act(pltpu.make_async_copy(
                    k_hbm.at[layer, page], kbuf.at[buf, p], sems.at[0, buf]))
                act(pltpu.make_async_copy(
                    v_hbm.at[layer, page], vbuf.at[buf, p], sems.at[1, buf]))

    @pl.when(pl.program_id(0) == 0)
    def _():
        # a page that is not copied leaves what its buffer held: a
        # probability of zero times that must be zero, so no buffer may
        # start out holding what is not a number
        vbuf[...] = jnp.zeros_like(vbuf)

    def pos_of(H):
        """A buffer's rows are (page, key head, row of the page); those
        of another key head than the query's lie past every count."""
        shape = (H, pp * Hkv * bs)
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        head = (col // bs) % Hkv
        own = (row >= head * G) & (row < (head + 1) * G)
        return jnp.where(own, (col // (Hkv * bs)) * bs + col % bs, P * bs)

    _walk_lists(
        pl.program_id(0), ntok_ref, g_ref,
        functools.partial(for_each_copy, act=lambda cp: cp.start()),
        functools.partial(for_each_copy, act=lambda cp: cp.wait()), pos_of,
        q_ref, m_ref, l_ref, acc_ref, o_ref, kbuf, vbuf, chunk, sm_scale)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_sparse_attn_slots(k_pool, v_pool, layer, q, pages, n_tokens,
                            m0, l0, acc0, interpret=False):
    """``paged_sparse_attend_xla`` for N slots whose key heads share the
    slot's list: q (N, Hkv, G, Dh), pages (N, P), n_tokens (N,), m0 and l0
    (N, Hkv, G), acc0 (N, Hkv, G, Dh) -> (N, Hkv, G, Dh), what the row
    form gives for rows (slot, key head) that each carry the slot's list
    and count."""
    N, Hkv, G, Dh = q.shape
    bs = k_pool.shape[3]
    H, P = Hkv * G, pages.shape[1]
    pp = _pages_per_slot_chunk(P, k_pool)
    lanes = lambda a: jnp.broadcast_to(
        a.astype(jnp.float32).reshape(N, H, 1), (N, H, LANES))
    slot = lambda w: pl.BlockSpec((1, H, w), lambda n, *_: (n, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_slots_kernel, P=P, G=G,
                          sm_scale=1.0 / math.sqrt(Dh)),
        name="paged_sparse_attn_slots",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N,),
            in_specs=[slot(Dh), slot(LANES), slot(LANES), slot(Dh), hbm, hbm],
            out_specs=slot(Dh),
            scratch_shapes=[
                pltpu.VMEM((2, pp, Hkv, bs, Dh), k_pool.dtype),
                pltpu.VMEM((2, pp, Hkv, bs, Dh), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((N, H, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), n_tokens.astype(jnp.int32),
      pages.astype(jnp.int32).reshape(-1), q.reshape(N, H, Dh), lanes(m0),
      lanes(l0), acc0.astype(jnp.float32).reshape(N, H, Dh), k_pool, v_pool)
    return out.reshape(N, Hkv, G, Dh)
