"""Paged attention over the pages a SELECTION names.

``paged_decode_attn`` reads the pages a length implies: every live page of
a slot, all key heads of a position side by side. A block-sparse layer
(InfLLM-v2, the ``minicpm4`` mixer) names, for every query and key head, a
list of pages that is the same size whatever the context, and has two key
heads, which a pool with the heads last would pad to a tile of sixteen. So
this kernel takes

  * pools ``(L, num_blocks, Hkv, bs, Dh)``: one key head's page is one
    contiguous ``(bs, Dh)`` tile run in HBM (``pl.ANY``);
  * ROWS, not slots: a row is one query position's ``G`` heads that share
    a key head (a decode step has ``slots * Hkv`` rows, a prompt chunk
    ``tokens * Hkv``), with its own list of ``P`` physical pages, read in
    order, of which the first ``n_tokens`` positions count; lists and
    counts are scalar prefetch, so a page's address is known before the
    page is needed;
  * what each row has attended over already, as a running maximum, sum
    and unnormalised output (the new token's own key in a decode step, the
    chunk's own keys in a prompt chunk), so the result is one softmax over
    both.

One grid step a row; a row's work is ``ceil(n_tokens / chunk)`` chunks of
``pp`` whole pages copied to VMEM by double-buffered DMA, the next chunk
(of this row, or the first of the next row that has any) in flight while
this one is computed; K and V are read once, in the pool's dtype, into
float32 accumulations (online softmax), the probabilities cast to the
pool's dtype before they meet V, as the XLA form
(serving/kv_cache.paged_sparse_attend_xla, this kernel's oracle) casts
them. List entries past the last that counts must still name a page of
the pool (a chunk is always copied whole).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import kernel_config

NEG_INF = -1e30
_CHUNK_TOKENS = 512            # 8 pages of 64: 128 KiB of K, as much of V
_SMEM_BUDGET = 192 * 2 ** 10   # one call's page lists
LANES = 128


def _pages_per_chunk(P, block_size):
    most = min(max(1, _CHUNK_TOKENS // block_size), P)
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


def _kernel(layer_ref, head_ref, ntok_ref, pages_ref, q_ref, m_ref, l_ref,
            acc_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, g_ref, *,
            P, pp, sm_scale):
    r = pl.program_id(0)
    R = pl.num_programs(0)
    bs = kbuf.shape[2]
    chunk = pp * bs
    layer = layer_ref[0]

    def for_each_copy(row, c, buf, act):
        head = head_ref[row]

        def page_copies(p, _):
            page = pages_ref[row * P + c * pp + p]
            act(pltpu.make_async_copy(
                k_hbm.at[layer, page, head], kbuf.at[buf, p], sems.at[0, buf]))
            act(pltpu.make_async_copy(
                v_hbm.at[layer, page, head], vbuf.at[buf, p], sems.at[1, buf]))

        jax.lax.fori_loop(0, pp, page_copies, None)

    def start(row, c, buf):
        for_each_copy(row, c, buf, lambda cp: cp.start())

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
    q = q_ref[0]                                            # (G, Dh)
    G = q.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (G, chunk), 1)

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

        for_each_copy(r, c, buf, lambda cp: cp.wait())
        g_ref[0] = g + 1
        k = kbuf[buf].reshape(chunk, kbuf.shape[3])
        v = vbuf[buf].reshape(chunk, vbuf.shape[3])
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # (G, chunk)
        s = jnp.where(c * chunk + col < n_tok, s, NEG_INF)
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


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_sparse_attn(k_pool, v_pool, layer, q, row_head, pages, n_tokens,
                      m0, l0, acc0, interpret=False):
    """serving/kv_cache.paged_sparse_attend_xla as a kernel (its docstring
    has the contract)."""
    R, G, Dh = q.shape
    _, _, _, bs, _ = k_pool.shape
    P = pages.shape[1]
    pp = _pages_per_chunk(P, bs)
    rows = rows_per_call(R, P)
    lanes = lambda a: jnp.broadcast_to(a[..., None], (R, G, LANES))

    def call(a):
        row_head, pages, n_tokens, q, m0, l0, acc0 = a
        row = lambda w: pl.BlockSpec((1, G, w), lambda r, *_: (r, 0, 0))
        hbm = pl.BlockSpec(memory_space=pl.ANY)
        return pl.pallas_call(
            functools.partial(_kernel, P=P, pp=pp,
                              sm_scale=1.0 / math.sqrt(Dh)),
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
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(jnp.reshape(layer, (1,)).astype(jnp.int32), row_head, n_tokens,
          pages.reshape(-1), q, m0, l0, acc0, k_pool, v_pool)

    args = (row_head.astype(jnp.int32), pages.astype(jnp.int32),
            n_tokens.astype(jnp.int32), q, lanes(m0.astype(jnp.float32)),
            lanes(l0.astype(jnp.float32)), acc0.astype(jnp.float32))
    if rows == R:
        return call(args)
    out = jax.lax.map(call, jax.tree.map(
        lambda a: a.reshape(R // rows, rows, *a.shape[1:]), args))
    return out.reshape(R, G, Dh)
