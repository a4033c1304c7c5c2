"""Attention of a prompt chunk over its past and over itself, one kernel.

A prompt chunk of a stack of two cache rules (``full_attn``: every key, a
page for every ``bs`` positions; ``window_attn``: a ring of the last
``window`` keys) or of pages of two roles (``eva``: the summary pages of
the windows left behind, then the window's own pages, one list and a
count of its rows) has ``C`` queries at positions ``offset + i``. Each
sees some of the slot's past, which lies in the pool's pages, and the
chunk's own keys up to itself, which do not yet. The XLA forms
(serving/kv_cache.chunk_attend_past, ring_chunk_attend, chunk_attend_all:
the oracles of this kernel, and the route under a mesh and off the TPU)
gather the pages and hand float32 scores of ``(C, Hkv, G, keys)`` from
one fusion to the next through HBM. Here the scores of one query tile
against one step of keys live in VMEM and nowhere else:

  * the pools ``(L, num_blocks, Hkv, bs, Dh)`` stay in HBM (``pl.ANY``)
    and ``layer`` is scalar prefetch, as in ``paged_sparse_attn``: nothing
    slices a pool in XLA;
  * the past is a LIST of pages in position order, of which list
    positions ``first <= p < count`` hold a key some query sees (both
    traced, scalar prefetch); with ``band`` query ``i`` sees ``p > i``
    only: the list is then a ring of ``window`` positions listed oldest
    first (``count`` is the window), so list position ``p`` holds position
    ``offset - window + p``, which query ``offset + i`` sees while it is
    above ``offset + i - window``;
  * the chunk's own keys come as ``C / bs`` pages of the pool's layout
    and are walked behind the list's, through the same buffers: own key
    ``j`` is seen by the queries ``i >= j``;
  * one grid step is a tile of ``q_tile`` query positions, all heads: its
    rows are ``(position, g)`` for each key head. It walks the steps of
    ``step_keys`` keys (whole pages, every key head of a page in one
    copy, double-buffered) that hold anything one of its queries sees: a
    chunk at offset 0 walks no past, a window layer's tile not the part
    of the ring behind its band, no tile the own keys after it. A step
    is masked only where it straddles an edge (the band's, the count's,
    the diagonal);
  * float32 scores from the operands' dtype, scaled, float32 running
    maximum, sum and accumulator in VMEM for the whole walk, the
    probabilities cast to the pool's dtype before they meet V, one
    division at the end: what the XLA forms compute, in another order of
    tiles.

The tiles (stand-alone on a v5e at 32 heads over 4 key heads of 128, ms a
1,024 x 1,024 tile and layer, the XLA form 0.65): what a step costs
beside its products is a lane reduction and a lane broadcast for every 8
rows of a key head's block (the maximum, the sum, the accumulator's
rescale), whatever the block's width, so a step is as WIDE as the chunk
allows: steps of 256 keys 0.338, of 512 0.193, of 1,024 0.124 (70% of the
MXU's peak), at 256 query positions a grid step; 128 positions read the
same, 512 at steps of 512 0.176 against 0.193. A step cannot be wider than
the chunk, whose own keys are walked in the same steps. At steps of 1,024
a window layer computes its ring and its own keys whole (two squares of
which the band and the diagonal keep half each: 0.40 ms a layer where
steps of 512, which skip a quarter, read 0.42).

At ONE query a key head (the eva layers': 32 heads over 32 key heads of
128, a list of 48 pages of 512 KiB) a grid step's 8,192 rows would be 32
blocks of 256 where the code cell's are 4 of 2,048, and each query tile
reads the past again, 16 MiB a step: the tile is 512 positions there
(``_HEAD_ROWS``; a grid step's VMEM reckons 77 MiB of the 96 asked for).
Stand-alone on a v5e, ms a layer by the rows that count (0, 1,024, 1,920,
2,944) against chunk_attend_all's 0.77-0.81 (0.16 of each reading is
``q`` and the result crossing HBM transposed, which fuse with their
neighbours in a program): tiles of 256 x 1,024 keys 0.383, 0.545, 0.682,
0.843; 512 x 1,024 0.322, 0.454, 0.577, 0.695; 512 x 512 0.337, 0.552,
0.753, 0.965. In the byte cell's chunk program a call reads 0.161 ms at
count 0, 0.574 at most. A walk costs what its count asks, where the XLA
form computes all 3,072 listed rows under a mask.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import kernel_config

NEG_INF = -1e30
LANES = 128
_Q_TILE = 256                  # query positions a grid step
_HEAD_ROWS = 512               # ... and at least these rows a key head
_STEP_KEYS = 1024              # keys a step of the walk: 16 pages of 64
_VMEM_LIMIT = 96 * 2 ** 20


def tiles(C, bs, G, q_tile=None, step_keys=None):
    """(query positions a grid step, pages a step of the walk): divisors
    of the chunk and of its pages, as near the targets as they come. At
    ``G`` queries a key head the tile is long enough to give a key head's
    block ``_HEAD_ROWS`` rows (512 positions at ONE query a key head)."""
    most_q = min(q_tile or max(_Q_TILE, _HEAD_ROWS // G), C)
    tq = next(t for t in range(most_q, 0, -1) if C % t == 0)
    n_own = C // bs
    most_p = min(max(1, (step_keys or _STEP_KEYS) // bs), n_own)
    pp = next(p for p in range(most_p, 0, -1) if n_own % p == 0)
    return tq, pp


def _vmem_bytes(k_pool, n_head, C):
    """What one grid step holds in VMEM, temporaries of a head's step
    included (scores, probabilities, their cast)."""
    _, _, Hkv, bs, Dh = k_pool.shape
    item = k_pool.dtype.itemsize
    tq, pp = tiles(C, bs, n_head // Hkv)
    rows, kb = tq * n_head // Hkv, pp * bs
    q_and_out = 2 * 2 * Hkv * rows * Dh * item
    buffers = 2 * 2 * pp * Hkv * bs * Dh * item
    running = Hkv * rows * (Dh + 2 * LANES) * 4
    step = rows * kb * (4 + 4 + item) + rows * Dh * 4
    return q_and_out + buffers + running + step


def is_available(k_pool, n_head, C) -> bool:
    """Whether the compiled kernel can take a chunk of ``C`` queries over
    this pool: a page must be whole tiles (``bs`` rows of the dtype's
    sublane packing, ``Dh`` whole lanes), a query tile's rows too, the
    chunk whole pages, and a grid step must fit VMEM."""
    if not kernel_config.on_tpu():
        return False
    _, _, Hkv, bs, Dh = k_pool.shape
    item = k_pool.dtype.itemsize
    if item not in (2, 4) or bs % (32 // item) or Dh % LANES \
            or n_head % Hkv or C % bs:
        return False
    tq, _ = tiles(C, bs, n_head // Hkv)
    return (tq * (n_head // Hkv)) % (32 // item) == 0 \
        and _vmem_bytes(k_pool, n_head, C) <= _VMEM_LIMIT * 7 // 8


def _kernel(layer_ref, pages_ref, lim_ref, q_ref, k_own, v_own, k_hbm, v_hbm,
            o_ref, kbuf, vbuf, sems, m_ref, l_ref, acc_ref, *, band, G,
            sm_scale):
    _, pp, Hkv, bs, Dh = kbuf.shape
    kb = pp * bs
    rows = q_ref.shape[2]
    tq = rows // G
    layer = layer_ref[0]
    first, count = lim_ref[0], lim_ref[1]
    i0 = pl.program_id(0) * tq              # the tile's first query
    # the lowest list position the tile's first query sees, and its last's
    lo_min = jnp.maximum(i0 + 1, first) if band else first
    lo_max = jnp.maximum(i0 + tq, first) if band else first
    s0 = lo_min // kb
    n_past = jnp.maximum(pl.cdiv(count, kb) - s0, 0)
    n = n_past + pl.cdiv(i0 + tq, kb)       # ... and the own keys up to it

    def for_each_copy(t, buf, act):
        def pages(k_src, v_src, page_of):
            def one(p, _):      # a loop, not sixteen copies of it to trace
                page = page_of(p)
                act(pltpu.make_async_copy(k_src(page), kbuf.at[buf, p],
                                          sems.at[0, buf]))
                act(pltpu.make_async_copy(v_src(page), vbuf.at[buf, p],
                                          sems.at[1, buf]))

            jax.lax.fori_loop(0, pp, one, None)

        @pl.when(t < n_past)
        def _():
            pages(lambda page: k_hbm.at[layer, page],
                  lambda page: v_hbm.at[layer, page],
                  lambda p: pages_ref[(s0 + t) * pp + p])

        @pl.when(t >= n_past)
        def _():
            pages(lambda page: k_own.at[page], lambda page: v_own.at[page],
                  lambda p: (t - n_past) * pp + p)

    def wait(buf):
        def one(p, _):          # a wait takes a copy's size, not its source
            pltpu.make_async_copy(k_own.at[0], kbuf.at[buf, p],
                                  sems.at[0, buf]).wait()
            pltpu.make_async_copy(v_own.at[0], vbuf.at[buf, p],
                                  sems.at[1, buf]).wait()

        jax.lax.fori_loop(0, pp, one, None)

    def attend(x0, own, buf, masked):
        """One step's keys, column 0 at coordinate ``x0``: a list position,
        or ``count + j`` for own key ``j``."""
        if masked:
            col = x0 + jax.lax.broadcasted_iota(jnp.int32, (1, kb), 1)
            i = i0 + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // G
            lo = jnp.maximum(i + 1, first) if band else first
            hi = jnp.where(own, count + i, count - 1)
            sees = (col >= lo) & (col <= hi)                 # (rows, kb)

        def head(h, _):
            q = q_ref[h, 0]                                  # (rows, Dh)
            k = kbuf[buf, :, h].reshape(kb, Dh)
            v = vbuf[buf, :, h].reshape(kb, Dh)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            if masked:
                s = jnp.where(sees, s, NEG_INF)
            m0 = m_ref[h]
            m = jnp.maximum(m0, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m0 - m)
            p = jnp.exp(s - m)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[h] = m

        jax.lax.fori_loop(0, Hkv, head, None)

    # a row whose first step is masked whole gathers probabilities of one
    # there; its first real score (its own key at the latest) scales them
    # to exact zeros, since the mask's value is finite
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    for_each_copy(0, 0, lambda cp: cp.start())

    def step(t, _):
        buf = jax.lax.rem(t, 2)

        @pl.when(t + 1 < n)
        def _():
            for_each_copy(t + 1, 1 - buf, lambda cp: cp.start())

        wait(buf)
        own = t >= n_past
        j0 = (t - n_past) * kb
        x0 = jnp.where(own, count + j0, (s0 + t) * kb)
        straddles = jnp.where(own, j0 + kb > i0 + 1,
                              (x0 < lo_max) | (x0 + kb > count))

        @pl.when(straddles)
        def _():
            attend(x0, own, buf, True)

        @pl.when(jnp.logical_not(straddles))
        def _():
            attend(x0, own, buf, False)

    jax.lax.fori_loop(0, n, step, None)

    def result(h, _):           # a loop: 32 key heads are 32 copies to trace
        o_ref[h, 0] = (acc_ref[h] / l_ref[h]).astype(o_ref.dtype)

    jax.lax.fori_loop(0, Hkv, result, None)


@functools.partial(jax.jit, static_argnames=("band", "q_tile", "step_keys",
                                             "interpret"))
def chunk_past_attn(k_pool, v_pool, layer, q, k, v, pages, first, count,
                    band=False, q_tile=None, step_keys=None, interpret=False):
    """q (C, H, Dh) at positions ``offset + i``; k, v (C, Hkv, Dh), the
    chunk's own, in the pool's dtype; pools (L, num_blocks, Hkv, bs, Dh),
    ``layer`` traced; ``pages`` (P,) the past's pages in position order,
    every entry a page of the pool; ``first``, ``count`` traced: query
    ``i`` sees list position ``p`` iff ``first <= p < count`` and, with
    ``band``, ``p > i``; and own key ``j`` iff ``j <= i``. -> (C, H, Dh)
    in q's dtype, one softmax over both. ``q_tile`` and ``step_keys``
    override the tiles (tests)."""
    C, H, Dh = q.shape
    _, _, Hkv, bs, _ = k_pool.shape
    G = H // Hkv
    tq, pp = tiles(C, bs, G, q_tile, step_keys)
    nq, rows = C // tq, tq * G
    P = pages.shape[0]
    pages = jnp.pad(pages.astype(jnp.int32), (0, -P % pp))
    qt = q.reshape(nq, tq, Hkv, G, Dh).transpose(2, 0, 1, 3, 4).reshape(
        Hkv, nq, rows, Dh)
    own = lambda t: jnp.swapaxes(t.reshape(C // bs, bs, Hkv, Dh), 1, 2)
    tile = pl.BlockSpec((Hkv, 1, rows, Dh), lambda i, *_: (0, i, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_kernel, band=band, G=G,
                          sm_scale=1.0 / math.sqrt(Dh)),
        name="chunk_past_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(nq,),
            in_specs=[tile, hbm, hbm, hbm, hbm],
            out_specs=tile,
            scratch_shapes=[
                pltpu.VMEM((2, pp, Hkv, bs, Dh), k_pool.dtype),
                pltpu.VMEM((2, pp, Hkv, bs, Dh), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((Hkv, rows, 1), jnp.float32),
                pltpu.VMEM((Hkv, rows, 1), jnp.float32),
                pltpu.VMEM((Hkv, rows, Dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((Hkv, nq, rows, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), pages,
      jnp.stack([first, count]).astype(jnp.int32), qt, own(k), own(v),
      k_pool, v_pool)
    return out.reshape(Hkv, nq, tq, G, Dh).transpose(1, 2, 0, 3, 4).reshape(
        C, H, Dh)
