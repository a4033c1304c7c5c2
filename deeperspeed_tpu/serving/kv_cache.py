"""Slot-based paged KV cache: block pool, allocator, and the paged
attention/cache-write math for the serving decode step.

Layout: one pool per cache side, stacked over layers —

    k, v: (n_layer, num_blocks, block_size, n_kv_head, head_dim)

A model of mixed layers (``GPTConfig.mixer_types``) keeps, by the kind of
each layer, one of six shapes of cache: pages (``minicpm4``,
``full_attn``), a state row a slot (``lightning``), both for one layer
(``mamba_attn``), pages of two roles (``eva``: the exact keys of the
current window in pages the slot reuses window after window, and pages of
pooled summaries, a row for every chunk of positions), a ring of pages
that holds the last ``window`` keys (``window_attn``), or a state row and
three convolution tails a slot and no pages (``kda``, in a stack whose
``full_attn`` layers keep the pages). ``page_rule_for``
is the RULE: how many pages of each role a length needs; a slot's table
holds the roles side by side. The POOLS go by rule: one array a pool,
each only as deep as the layers that read it. Every model but one has one
pool; a stack of ``full_attn`` and ``window_attn`` layers has two (``k``
and ``v`` are then PAIRS of arrays, the pool whose pages follow the
length first, the rings' second, each with its own allocator and its own
null page 0), so that no byte of a page belongs to a layer that never
reads it: a page of the first is ``n_full`` layers deep, a page of the
second ``n_window``. A mixed model's
pages hold the layers that have any, a page's ``(block_size, head_dim)``
last so that two or four key heads are not padded to a tile of sixteen:

    k, v:  (n_paged, num_blocks, n_kv_head, block_size, head_dim)
    kc:    (n_sparse, num_blocks, n_kv_head * windows_per_block, head_dim)
           the selector's pooled keys: a page's row h * w + i is key head
           h's mean over the window that starts at the page's token i * st
    state: one row a SLOT (not pages: it has one size whatever the length),
           float32: lightning (n_lightning, num_slots, n_head, head_dim,
           head_dim); mamba_attn {"ssm": (n, num_slots, ssm heads, ssm
           head_dim, d_state), "conv": (n, num_slots, d_conv - 1,
           conv_dim) in the compute dtype: the convolution's last inputs};
           kda {"kda": (n_kda, num_slots, heads, head_k, head_v), "conv":
           (n_kda, num_slots, d_conv - 1, conv_dim): the last inputs of
           q's, k's and v's convolutions side by side}

A request's cache lives in whichever blocks the allocator hands it; the
per-slot BLOCK TABLE (``(num_slots, blocks_per_slot)`` int32) maps the
request's logical block ``i`` to its physical block. Block 0 is the
reserved NULL block: idle slots' tables and padded table entries point at
it, so the fully static decode step can scatter/gather unconditionally —
garbage lands in (or comes from) block 0 and is masked out by the
per-slot length.

Writes are static-shape updates into slot pages: prefill scatters whole
``block_size`` pages (the dense prefill cache reshaped to pages, indexed
by the allocated block list), decode scatters each slot's single new
(K, V) row at ``(block_table[len // bs], len % bs)``, all layers in one
scatter after the layer loop. Reads never copy the pool: the XLA form
(``paged_attend_rows``) gathers the slot's pages into a contiguous
``blocks_per_slot * block_size`` view per layer; on one TPU
``ops/pallas/paged_decode_attn`` walks the table in HBM and copies only
the pages that hold live positions (``decode_attend_for`` chooses).

Prefix reuse generalizes the null-block trick into copy-on-write
sharing: blocks are REFCOUNTED, and a ``PrefixCache`` (radix trie over
token blocks) lets the scheduler map another request's already-prefilled
prompt blocks into a new slot's table read-only. A shared block returns
to the free list only when its last holder (requests AND the cache)
drops it, so evicting one sharer never frees a block another slot still
reads. The partially filled boundary block of a matched prefix is never
shared in place — admission copies its matched rows into a private block
(the CoW split, exactly once per admission) via the same gather/scatter
page machinery the prefill path uses.

What ONE program sees of all this is built here too, at the end of the
file: ``decode_view`` and ``chunk_view`` (the pools by role, the lists, the
write indexes and the forms the choosers picked, each reckoned once before
the layer loop: what the kinds' cores in ``serving/kinds.py`` read) and
the writes after the loop, ``write_decode_step`` and
``write_prefill_chunk``. ``serving/engine.py`` builds the frame of a
program round them and names no kind.
"""

import functools
import itertools
import math
from types import SimpleNamespace
from typing import (Callable, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp

from ..models import mixers as mx
from ..models.gpt import GROUPED_KINDS, GPTConfig
from .config import PageRule, ServingConfig

NULL_BLOCK = 0


class OutOfBlocks(Exception):
    """Raised only for internal invariant violations — normal exhaustion
    returns None from alloc() (backpressure, not an error)."""


class BlockAllocator:
    """Refcounted free-list allocator over the physical blocks of the KV
    pool.

    Block 0 (NULL_BLOCK) is never handed out. alloc() is all-or-nothing:
    a request that cannot get every block it asked for gets none, and the
    caller leaves it queued (backpressure) or preempts a victim.

    Sharing: ``alloc`` hands out blocks at refcount 1; ``ref`` adds a
    holder (a slot table mapping a cached prefix block, or the prefix
    cache's own resident reference); ``free`` drops one holder and the
    block returns to the free list only at refcount 0. Callers that never
    call ``ref`` see the original exclusive-ownership semantics
    unchanged. ``reclaim`` (set by PrefixCache) is consulted when alloc
    falls short, so cache-only blocks are evicted before backpressure.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is reserved)")
        self.num_blocks = num_blocks
        # LIFO free list: recently freed (cache-warm) blocks reused first
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self._refs: Dict[int, int] = {}
        # hook: callable(n_short) -> blocks actually released; installed
        # by PrefixCache so allocation pressure evicts idle cached
        # prefixes instead of backpressuring live traffic
        self.reclaim = None

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_allocated(self) -> int:
        return len(self._refs)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def refcount(self, block: int) -> int:
        return self._refs.get(block, 0)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n blocks, or None when the pool cannot satisfy the request."""
        if n < 0:
            raise ValueError(f"cannot alloc {n} blocks")
        if n > len(self._free) and self.reclaim is not None:
            self.reclaim(n - len(self._free))
        if n > len(self._free):
            return None
        blocks = [self._free.pop() for _ in range(n)]
        for b in blocks:
            self._refs[b] = 1
        return blocks

    def ref(self, block: int) -> None:
        """Add a holder to an allocated block (shared-prefix mapping)."""
        if block not in self._refs:
            raise OutOfBlocks(
                f"ref of unallocated block {block} "
                f"(allocated={sorted(self._refs)})"
            )
        self._refs[block] += 1

    def free(self, blocks: List[int]) -> None:
        for b in blocks:
            n = self._refs.get(b)
            if n is None:
                raise OutOfBlocks(
                    f"double free / foreign free of block {b} "
                    f"(allocated={sorted(self._refs)})"
                )
            if n > 1:
                self._refs[b] = n - 1
            else:
                del self._refs[b]
                self._free.append(b)


def blocks_needed(n_tokens: int, block_size: int) -> int:
    """Pages that hold ``n_tokens`` rows where a position keeps its row
    for ever: ``PageRule()``'s count, spelled for callers that hold no
    serving configuration (a configuration answers ``pages_needed``)."""
    return PageRule().counts(n_tokens, block_size)[0]


def page_rule_for(cfg: GPTConfig) -> PageRule:
    """The rule by which this model's cache holds pages for a length:
    every position a row for ever, or, for ``eva`` layers, the window's
    pages reused and a page of summaries for every ``chunk * block_size``
    positions."""
    if cfg.count("eva"):
        return PageRule(window=cfg.eva.window, chunk=cfg.eva.chunk)
    if cfg.count("window_attn"):
        # beside the full layers' pages (none where the stack has no full
        # layer: their pool is then no layer deep), a ring in its own pool
        return PageRule(ring=cfg.gqa.window)
    return PageRule()


# ------------------------------------------------------------------ #
# prefix-radix KV index
# ------------------------------------------------------------------ #


class _RadixNode:
    """One cached block of prompt tokens. Full nodes (len(tokens) ==
    block_size) may have children; a shorter node is a terminal partial
    leaf — the CoW-source boundary block of some cached prompt."""

    __slots__ = ("tokens", "block", "children", "parent", "last_used")

    def __init__(self, tokens: Tuple[int, ...], block: int, parent):
        self.tokens = tokens
        self.block = block
        self.children: List["_RadixNode"] = []
        self.parent = parent
        self.last_used = 0


def _common_prefix(a: Sequence[int], b: Sequence[int]) -> int:
    n = 0
    for x, y in zip(a, b):
        if x != y:
            break
        n += 1
    return n


class PrefixCache:
    """Radix trie over token blocks: the fleet-wide index of prompt KV
    already resident in the paged pool.

    Each node is one physical block's worth of tokens; the cache holds
    its own allocator reference on every indexed block, so a cached
    prefix outlives the request that prefilled it. ``match`` returns the
    longest cached prefix of a prompt as (full shared blocks, partial
    boundary source); ``insert`` indexes a freshly prefilled prompt,
    deduping against existing nodes. Under allocation pressure the
    allocator calls ``_reclaim`` and the cache drops least-recently-used
    leaves whose blocks no live slot shares — a block some slot still
    maps is dereferenced but NOT released (refcounts make that safe by
    construction).
    """

    def __init__(self, allocator: BlockAllocator, block_size: int):
        self.allocator = allocator
        self.block_size = block_size
        self._root = _RadixNode((), NULL_BLOCK, None)
        self._tick = itertools.count(1)
        # observability: the bench's prefix_reuse block reads these
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.indexed_blocks = 0
        allocator.reclaim = self._reclaim

    def match(self, tokens: Sequence[int]
              ) -> Tuple[int, List[int], Optional[Tuple[int, int]]]:
        """Longest cached prefix of ``tokens``.

        Returns ``(matched_len, full_blocks, partial)``: full_blocks map
        read-only into the slot's table; ``partial`` is ``(block, rows)``
        when the match ends mid-block — the CoW source whose matched rows
        admission copies into a private block. matched_len is capped at
        ``len(tokens) - 1``: at least one token must remain to prefill,
        because that forward produces the request's first-token logits.
        """
        bs = self.block_size
        limit = len(tokens) - 1
        node = self._root
        full: List[int] = []
        matched = 0
        partial: Optional[Tuple[int, int]] = None
        now = next(self._tick)
        while matched < limit:
            remaining = limit - matched
            # never look past the cap: a partial-node match must not
            # count tokens beyond limit, or an identical prompt would
            # "fully" match and leave nothing to prefill
            want = tokens[matched:matched + min(bs, remaining)]
            descend = None
            best_rows, best_child = 0, None
            for ch in node.children:
                n = _common_prefix(ch.tokens, want)
                if n == bs == len(ch.tokens) and remaining > bs:
                    descend = ch
                    break
                if n > best_rows:
                    best_rows, best_child = n, ch
            if descend is not None:
                descend.last_used = now
                full.append(descend.block)
                matched += bs
                node = descend
                continue
            if best_rows > 0:
                best_child.last_used = now
                partial = (best_child.block, best_rows)
                matched += best_rows
            break
        if matched > 0:
            self.hits += 1
        else:
            self.misses += 1
        return matched, full, partial

    def insert(self, tokens: Sequence[int], blocks: Sequence[int]) -> int:
        """Index a freshly prefilled prompt: ``tokens`` live in
        ``blocks`` (logical page order). Takes a cache-resident ref on
        every newly indexed block; existing nodes dedupe (the duplicate
        physical copy stays private to its request). Returns the number
        of blocks newly indexed."""
        bs = self.block_size
        node = self._root
        pos = 0
        new = 0
        now = next(self._tick)
        while pos < len(tokens):
            chunk = tuple(tokens[pos:pos + bs])
            existing = None
            for ch in node.children:
                if ch.tokens == chunk:
                    existing = ch
                    break
            if existing is not None:
                existing.last_used = now
                node = existing
                pos += len(chunk)
                continue
            block = blocks[pos // bs]
            self.allocator.ref(block)
            child = _RadixNode(chunk, block, node)
            child.last_used = now
            node.children.append(child)
            new += 1
            if len(chunk) < bs:
                break  # partial boundary blocks are terminal
            node = child
            pos += bs
        self.indexed_blocks += new
        return new

    def _reclaim(self, n_short: int) -> int:
        """Evict least-recently-used leaves until ``n_short`` blocks hit
        the free list. Dropping the cache ref on a block a live slot
        still shares releases nothing (and counts for nothing) — only
        cache-only blocks actually free capacity."""
        freed = 0
        while freed < n_short:
            victim = None
            stack = [self._root]
            while stack:
                nd = stack.pop()
                stack.extend(nd.children)
                if nd is self._root or nd.children:
                    continue
                if victim is None or nd.last_used < victim.last_used:
                    victim = nd
            if victim is None:
                break
            if self.allocator.refcount(victim.block) == 1:
                freed += 1
            self.allocator.free([victim.block])
            victim.parent.children.remove(victim)
            self.indexed_blocks -= 1
            self.evictions += 1
        return freed

    def stats(self) -> Dict[str, int]:
        lookups = self.hits + self.misses
        return {
            "lookups": lookups,
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "indexed_blocks": self.indexed_blocks,
        }


class PagedKVCache:
    """The device-side block pool plus its host-side allocator.

    ``k``/``v`` are replaced wholesale by the jitted prefill-write and
    decode steps (which donate the old pools); this object owns the
    handles and the block accounting.
    """

    def __init__(self, cfg: GPTConfig, scfg: ServingConfig,
                 num_blocks: Optional[int] = None):
        # num_blocks override: the speculative drafter's pool shares the
        # target's geometry (block_size, table width) but sizes its own
        # block count — and rides its own BlockAllocator instance of the
        # same refcount/reclaim machinery
        self.cfg = cfg
        self.scfg = scfg
        nb = scfg.num_blocks if num_blocks is None else int(num_blocks)
        self.kc = self.state = None
        if cfg.classic:
            shape = (cfg.n_layer, nb, scfg.block_size,
                     cfg.kv_heads, cfg.head_dim)
        else:
            sp = cfg.sparse
            if sp is not None and (scfg.block_size != sp.block_size
                                   or scfg.blocks_per_slot < sp.list_blocks):
                raise ValueError(
                    f"a page is one selection block: block_size must be "
                    f"{sp.block_size} (got {scfg.block_size}) and "
                    f"max_seq_len cover dense_len ({sp.dense_len})")
            # a cache layer for every (pass, layer) pair: as deep as the
            # stack but where the stack is looped
            n_sp, n_li, n_ma, n_ev, n_fu, n_wi, n_kd = (
                cfg.cache_layers(kind) for kind in (
                    "minicpm4", "lightning", "mamba_attn", "eva", "full_attn",
                    "window_attn", "kda"))
            kinds = set(cfg.mixer_types)
            if ((n_ma or n_ev) and len(kinds) > 1) \
                    or ((n_fu or n_wi or n_kd) and not (
                        kinds <= GROUPED_KINDS
                        or kinds == {"full_attn", "kda"})):
                raise NotImplementedError(
                    "mamba_attn and eva layers share a stack with no other "
                    "kind; full_attn layers share one with window_attn "
                    "layers (two page rules, a pool each) or with kda "
                    "layers (pages beside state rows and convolution "
                    "tails), and kda layers with full_attn layers alone: a "
                    "pool and the state rows are indexed by a layer's "
                    "place among the kinds that share them, and only a "
                    "ring has a pool beside the pages that follow the "
                    "length")
            if scfg.page_rule != page_rule_for(cfg):
                raise ValueError(
                    f"the serving configuration's page rule "
                    f"({scfg.page_rule}) is not this cache's "
                    f"({page_rule_for(cfg)}): size it with "
                    "ServingConfig.for_cache(page_rule_for(cfg))")
            if n_ev:
                check_eva_pages(cfg, scfg)
            if n_wi:
                check_ring_pages(cfg, scfg)
            shape = (n_sp + n_ma + n_ev + n_fu, nb, cfg.kv_heads,
                     scfg.block_size, cfg.head_dim)
            if sp is not None:
                self.kc = jnp.zeros(
                    (n_sp, nb, cfg.kv_heads * sp.windows_per_block,
                     cfg.head_dim), cfg.dtype)
            if n_li:
                self.state = jnp.zeros(
                    (n_li, scfg.num_slots, cfg.n_head, cfg.head_dim,
                     cfg.head_dim), jnp.float32)
            if n_ma:
                m = cfg.ssm
                self.state = {
                    "ssm": jnp.zeros((n_ma, scfg.num_slots, m.n_heads,
                                      m.head_dim, m.d_state), jnp.float32),
                    "conv": jnp.zeros((n_ma, scfg.num_slots, m.d_conv - 1,
                                       m.conv_dim), cfg.dtype)}
            if n_kd:
                kc = cfg.kda
                self.state = {
                    "kda": jnp.zeros((n_kd, scfg.num_slots, kc.n_heads,
                                      kc.head_k, kc.head_v), jnp.float32),
                    "conv": jnp.zeros((n_kd, scfg.num_slots, kc.d_conv - 1,
                                       kc.conv_dim), cfg.dtype)}
        self.k = jnp.zeros(shape, cfg.dtype)
        self.v = jnp.zeros(shape, cfg.dtype)
        # an allocator a pool, the one whose pages follow the length first
        self.allocators = [BlockAllocator(nb)]
        if scfg.page_rule.ring:
            # the rings' pool beside it: as deep as the window layers
            nr = scfg.pool_blocks[1]
            ring = (cfg.cache_layers("window_attn"), nr) + shape[2:]
            self.k = (self.k, jnp.zeros(ring, cfg.dtype))
            self.v = (self.v, jnp.zeros(ring, cfg.dtype))
            self.allocators.append(BlockAllocator(nr))
        self.allocator = self.allocators[0]
        # both retrace once per page count of their dense side: the
        # scatter once a prefill bucket, the gather once a staging-cache
        # bucket (monitor.compile_account() counts them by name)
        self._write_prefill = jax.jit(ds_scatter_prefill_pages,
                                      donate_argnums=(0, 1))
        self._gather_pages = jax.jit(ds_gather_pages)

    def write_prefill(self, k_dense, v_dense, blocks: List[int],
                      length: int) -> None:
        """Scatter a dense prefill cache (L, 1, bucket, Hkv, Dh) into the
        allocated ``blocks``. ``bucket`` is a multiple of block_size;
        pages beyond ``blocks`` (prompt padding) go to the null block."""
        bs = self.scfg.block_size
        assert len(blocks) == self.scfg.pages_needed(length), \
            (blocks, length)
        n_pages = k_dense.shape[2] // bs
        self.write_pages(k_dense, v_dense,
                         list(blocks) + [NULL_BLOCK] * (n_pages
                                                        - len(blocks)))

    def write_pages(self, k_dense, v_dense,
                    page_to_block: Sequence[int]) -> None:
        """Scatter selected pages of a dense (L, 1, bucket, Hkv, Dh)
        cache into physical blocks: page ``i`` lands in
        ``page_to_block[i]``. NULL_BLOCK entries discard the page (the
        null block's content is never read unmasked) — the suffix-prefill
        path uses that to skip pages whose data already lives in shared
        blocks, writing only private pages. Re-scattering a matched
        boundary page into a private block IS the CoW split: the dense
        cache carries the gathered shared rows plus the new suffix rows,
        so one scatter both copies and diverges."""
        bs = self.scfg.block_size
        bucket = k_dense.shape[2]
        assert bucket % bs == 0, (bucket, bs)
        assert len(page_to_block) == bucket // bs, (page_to_block, bucket)
        idx = jnp.asarray(list(page_to_block), jnp.int32)
        self.k, self.v = self._write_prefill(self.k, self.v, k_dense,
                                             v_dense, idx)

    def gather_pages(self, page_to_block: Sequence[int]):
        """Gather pool pages into a dense (L, 1, n_pages * bs, Hkv, Dh)
        staging cache — the read half of prefix reuse. Pages mapped to
        NULL_BLOCK come back as garbage rows; callers overwrite or mask
        them (same contract as the decode step's idle lanes)."""
        idx = jnp.asarray(list(page_to_block), jnp.int32)
        return self._gather_pages(self.k, self.v, idx)


def ds_scatter_prefill_pages(k_pool, v_pool, k_dense, v_dense, idx):
    """(L, 1, bucket, Hkv, Dh) dense prefill cache -> pool pages at idx."""
    L, _, bucket, Hkv, Dh = k_dense.shape
    bs = k_pool.shape[2]
    pages_k = k_dense.reshape(L, bucket // bs, bs, Hkv, Dh)
    pages_v = v_dense.reshape(L, bucket // bs, bs, Hkv, Dh)
    # duplicate null-block targets (padding pages) may race; block 0's
    # content is never read unmasked, so last-writer-wins is fine
    return (k_pool.at[:, idx].set(pages_k.astype(k_pool.dtype)),
            v_pool.at[:, idx].set(pages_v.astype(v_pool.dtype)))


def ds_gather_pages(k_pool, v_pool, idx):
    """Pool pages at idx -> dense (L, 1, n_pages * bs, Hkv, Dh) pair."""
    L, _, bs, Hkv, Dh = k_pool.shape
    n = idx.shape[0]
    k = k_pool[:, idx].reshape(L, 1, n * bs, Hkv, Dh)
    v = v_pool[:, idx].reshape(L, 1, n * bs, Hkv, Dh)
    return k, v


def paged_attend_multi(k_pool_l, v_pool_l, q, k_new, v_new, tables,
                       lengths, write_blocks, write_offs):
    """One layer of T-token paged-cache attention for all slots — the
    ``paged_attend`` math generalized from a single new token to a
    static window of T tokens per slot (the speculative verify step's
    attention core; T = draft_k + 1).

    q: (N, T, H, Dh); k_new/v_new: (N, T, Hkv, Dh) — the window's
    projections per slot. write_blocks/write_offs: (N, T) physical
    block + in-block offset for each new row (idle lanes target the
    null block). Token t of slot i sits at logical position
    ``lengths[i] + t`` and attends causally: keys at positions
    ``<= lengths[i] + t``. Returns (ctx (N, T, H, Dh), k_pool_l',
    v_pool_l'). Rows written for tokens the verify step later rejects
    are stale-but-invisible — the next round's length-derived mask
    hides them until they are overwritten (same contract as
    models/speculative's rollback-free cache).
    """
    N, T = q.shape[0], q.shape[1]
    Hq, Dh = q.shape[2], q.shape[3]
    cdt = k_pool_l.dtype
    with jax.named_scope("ds.decode/kv_write"):
        # duplicate (null block, t) targets across idle lanes may race;
        # block 0 is never read unmasked, so last-writer-wins is fine
        k_pool_l = k_pool_l.at[write_blocks, write_offs].set(
            k_new.astype(cdt))
        v_pool_l = v_pool_l.at[write_blocks, write_offs].set(
            v_new.astype(cdt))
    bs = k_pool_l.shape[1]
    view = tables.shape[1] * bs
    with jax.named_scope("ds.decode/kv_gather"):
        k_c = k_pool_l[tables].reshape(N, view, k_pool_l.shape[2], Dh)
        v_c = v_pool_l[tables].reshape(N, view, v_pool_l.shape[2], Dh)
    Hkv = k_c.shape[2]
    rep = Hq // Hkv
    with jax.named_scope("ds.decode/attn"):
        qg = q.reshape(N, T, Hkv, rep, Dh)
        scores = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k_c,
                            preferred_element_type=jnp.float32)
        scores = scores / math.sqrt(Dh)
        key_pos = jnp.arange(view, dtype=jnp.int32)
        q_pos = lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        valid = key_pos[None, None, :] <= q_pos[:, :, None]  # (N, T, view)
        scores = jnp.where(valid[:, None, None, :, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        ctx = jnp.einsum("bhrqk,bkhd->bqhrd", probs, v_c)
    return ctx.reshape(N, T, Hq, Dh), k_pool_l, v_pool_l


def paged_attend_rows(k_pool, v_pool, layer, q, k_row, v_row, tables,
                      lengths):
    """One layer of single-token paged attention that only READS the
    pool — the XLA form, the oracle of ops/pallas/paged_decode_attn.

    k_pool/v_pool: the STACKED pools (L, num_blocks, bs, Hkv, Dh);
    ``layer`` (traced) selects the layer inside the gather's index, so no
    whole-layer slice of the pool is ever made. q: (N, 1, H, Dh).
    k_row/v_row: (N, Hkv, Dh) — the new token's rows, already in the
    pool's dtype (the values the pool will hold). Slot i attends over
    the pool's logical positions ``< lengths[i]`` plus its own new row
    at position ``lengths[i]``; whoever calls writes the rows into the
    pool afterwards. Returns ctx (N, 1, H, Dh).

    Mirrors models/generation._cached_block's grouped-einsum math (GQA
    reads at the small Hkv width) so greedy serving outputs are
    token-identical to make_generator's.
    """
    N = q.shape[0]
    Hq, Dh = q.shape[2], q.shape[3]
    bs, Hkv = k_pool.shape[2], k_pool.shape[3]
    view = tables.shape[1] * bs
    key_pos = jnp.arange(view, dtype=jnp.int32)
    with jax.named_scope("ds.decode/kv_gather"):
        # each slot's pages as a contiguous logical view, the new row
        # laid over position == length (where the pool's row is stale)
        new = (key_pos[None, :] == lengths[:, None])[:, :, None, None]
        k_c = jnp.where(new, k_row[:, None],
                        k_pool[layer, tables].reshape(N, view, Hkv, Dh))
        v_c = jnp.where(new, v_row[:, None],
                        v_pool[layer, tables].reshape(N, view, Hkv, Dh))
    rep = Hq // Hkv
    with jax.named_scope("ds.decode/attn"):
        qg = q.reshape(N, 1, Hkv, rep, Dh)
        scores = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k_c,
                            preferred_element_type=jnp.float32)
        scores = scores / math.sqrt(Dh)
        # valid keys: logical positions 0..length inclusive
        valid = key_pos[None, :] <= lengths[:, None]      # (N, view)
        scores = jnp.where(valid[:, None, None, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        ctx = jnp.einsum("bhrqk,bkhd->bqhrd", probs, v_c)
    return ctx.reshape(N, 1, Hq, Dh)


def paged_attend(k_pool_l, v_pool_l, q, k_new, v_new, tables, lengths,
                 write_block, write_off):
    """``paged_attend_rows`` over ONE layer's pool, which it also writes:
    the form the speculative drafter's scan still carries its pool
    through (serving/spec/steps.py).

    k_pool_l/v_pool_l: (num_blocks, bs, Hkv, Dh) — this layer's pool.
    q: (N, 1, H, Dh); k_new/v_new: (N, 1, Hkv, Dh) — the new token's
    projections per slot. tables: (N, blocks_per_slot) int32; lengths:
    (N,) tokens already cached per slot; write_block/write_off: (N,)
    physical block + in-block offset for the new row.

    Returns (ctx (N, 1, H, Dh), k_pool_l', v_pool_l').
    """
    cdt = k_pool_l.dtype
    k_row, v_row = k_new[:, 0].astype(cdt), v_new[:, 0].astype(cdt)
    ctx = paged_attend_rows(k_pool_l[None], v_pool_l[None], 0, q, k_row,
                            v_row, tables, lengths)
    with jax.named_scope("ds.decode/kv_write"):
        # the new row: idle slots target (null block, 0) by construction
        k_pool_l = k_pool_l.at[write_block, write_off].set(k_row)
        v_pool_l = v_pool_l.at[write_block, write_off].set(v_row)
    return ctx, k_pool_l, v_pool_l


def decode_attend_for(k_pool, tables, n_head, mesh):
    """The form of one layer's decode attention (the signature of
    ``paged_attend_rows``) that a decode program takes, from what it can
    see when it is traced: on one TPU, shapes the kernel can tile get
    ops/pallas/paged_decode_attn (live pages only); every other platform
    and shape, and a multi-device mesh (XLA cannot partition a Mosaic
    kernel), get the XLA form, which GSPMD shards."""
    from ..ops.pallas import paged_decode_attn as kernel

    if (mesh is None or mesh.size == 1) and kernel.is_available(
            k_pool, tables, n_head):
        return kernel.paged_decode_attn
    return paged_attend_rows


# ------------------------------------------------------------------ #
# mixed stacks: selected pages, pooled keys, a state row a slot
# ------------------------------------------------------------------ #


def paged_sparse_attend_xla(k_pool, v_pool, layer, q, row_head, pages,
                            n_tokens, m0, l0, acc0):
    """Attention of R rows, each over the pages ITS list names — the XLA
    form, the oracle of ops/pallas/paged_sparse_attn.

    k_pool/v_pool: (L, num_blocks, Hkv, bs, Dh); ``layer`` traced. q: (R,
    G, Dh), a row's G query heads share the key head ``row_head[r]``.
    pages: (R, P) physical pages, read in order; the first ``n_tokens[r]``
    positions of their concatenation count (so only the last page that
    counts may be partly filled). m0, l0 (R, G) and acc0 (R, G, Dh),
    float32: the running maximum, sum and unnormalised output of what the
    caller already attended over (the new token itself, the chunk's own
    keys); the result is normalised over both. -> (R, G, Dh) in q's dtype.
    """
    R, G, Dh = q.shape
    P, bs = pages.shape[1], k_pool.shape[3]
    scale = 1.0 / math.sqrt(Dh)
    col = jnp.arange(P * bs, dtype=jnp.int32)

    def rows(a):
        q, rh, pg, nt, m0, l0, acc0 = a
        k = k_pool[layer, pg, rh[:, None]].reshape(-1, P * bs, Dh)
        v = v_pool[layer, pg, rh[:, None]].reshape(-1, P * bs, Dh)
        s = jnp.einsum("rgd,rkd->rgk", q, k,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where((col[None, :] < nt[:, None])[:, None, :], s, -1e30)
        m = jnp.maximum(m0, jnp.max(s, -1))
        p = jnp.exp(s - m[..., None])
        alpha = jnp.exp(m0 - m)
        l = alpha * l0 + jnp.sum(p, -1)
        acc = alpha[..., None] * acc0 + jnp.einsum(
            "rgk,rkd->rgd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return (acc / l[..., None]).astype(q.dtype)

    args = (q, row_head, pages, n_tokens, m0, l0, acc0)
    batch = 64      # rows gathered at once: P pages each, materialised
    if R <= batch or R % batch:
        return rows(args)
    out = jax.lax.map(rows, jax.tree.map(
        lambda a: a.reshape(R // batch, batch, *a.shape[1:]), args))
    return out.reshape(R, G, Dh)


def sparse_attend_for(k_pool, n_head, mesh):
    """``paged_sparse_attend_xla`` or, on one TPU at shapes it can tile,
    the kernel of the same signature (as ``decode_attend_for`` chooses)."""
    from ..ops.pallas import paged_sparse_attn as kernel

    if (mesh is None or mesh.size == 1) and kernel.is_available(
            k_pool, n_head):
        return kernel.paged_sparse_attn
    return paged_sparse_attend_xla


def slots_as_rows(attend_pages, k_pool, v_pool, layer, q, pages, n_tokens,
                  m0, l0, acc0):
    """Slots whose key heads share the slot's list, through a form that
    takes a list a ROW (``sparse_attend_for``'s): a row a (slot, key
    head), each with a copy of the list and the count. q, acc0: (N, Hkv,
    G, Dh); pages: (N, P); n_tokens: (N,); m0, l0: (N, Hkv, G). ->
    (N, Hkv, G, Dh)."""
    N, Hkv, G, Dh = q.shape
    R, P = N * Hkv, pages.shape[1]
    ctx = attend_pages(
        k_pool, v_pool, layer, q.reshape(R, G, Dh),
        jnp.tile(jnp.arange(Hkv), N),
        jnp.broadcast_to(pages[:, None, :], (N, Hkv, P)).reshape(R, P),
        jnp.repeat(n_tokens, Hkv), m0.reshape(R, G), l0.reshape(R, G),
        acc0.reshape(R, G, Dh))
    return ctx.reshape(N, Hkv, G, Dh)


def takes_slot_form(k_pool, n_head, lists_shape, mesh) -> bool:
    """Whether lists ``lists_shape`` = (slots, entries), one a slot for
    all its key heads, go through the kernel whose row is a slot: one
    TPU, shapes the kernel can tile, a page that fits its buffers."""
    from ..ops.pallas import paged_sparse_attn as kernel

    return (mesh is None or mesh.size == 1) and kernel.slots_available(
        k_pool, n_head, lists_shape)


def slot_attend_for(k_pool, n_head, lists_shape, mesh):
    """The form of ``decode_attend_all``'s read (the signature of
    ``slots_as_rows`` less its first argument): the kernel that copies a
    page once for all of a slot's key heads where ``takes_slot_form``,
    else a row a (slot, key head) through ``sparse_attend_for``'s choice
    (the XLA form under a mesh and off the TPU)."""
    from ..ops.pallas import paged_sparse_attn as kernel

    if takes_slot_form(k_pool, n_head, lists_shape, mesh):
        return kernel.paged_sparse_attn_slots
    return functools.partial(slots_as_rows,
                             sparse_attend_for(k_pool, n_head, mesh))


def lightning_chunk_for(q, mesh):
    """The chunkwise form of a lightning layer: the kernel on one TPU at
    shapes it can tile, else ``mixers.lightning_chunk_xla``."""
    from ..ops.pallas import lightning_chunk as kernel

    if (mesh is None or mesh.size == 1) and kernel.is_available(q):
        return kernel.lightning_chunk
    return mx.lightning_chunk_xla


def ssm_rows_for(rows, n_groups, mesh):
    """A decode step's update of a mamba_attn layer's state rows: the
    kernel (each row crosses HBM once each way) on one TPU at shapes it
    can tile, else ``mixers.ssm_rows_xla``."""
    from ..ops.pallas import ssm_row_update as kernel

    if (mesh is None or mesh.size == 1) and kernel.is_available(
            rows, n_groups):
        return kernel.ssm_row_update
    return mx.ssm_rows_xla


def kda_rows_for(rows, mesh):
    """A decode step's update of a kda layer's state rows: the kernel
    (each row crosses HBM once each way) on one TPU at shapes it can
    tile, else ``mixers.kda_rows_xla``."""
    from ..ops.pallas import kda_row_update as kernel

    if (mesh is None or mesh.size == 1) and kernel.is_available(rows):
        return kernel.kda_row_update
    return mx.kda_rows_xla


def kda_chunk_for(C: int, kc, mesh):
    """The chunkwise delta rule of a kda layer's prompt chunk of ``C``
    positions (``kc``: the model's ``KdaConfig``): the kernel on one TPU
    at shapes it can tile, else ``mixers.kda_chunk_xla``. -> (the form,
    "kernel" or "xla")."""
    from ..ops.pallas import kda_chunk as kernel

    if (mesh is None or mesh.size == 1) and kernel.is_available(
            C, kc.head_k, kc.head_v):
        return kernel.kda_chunk, "kernel"
    return mx.kda_chunk_xla, "xla"


def _own_token_init(q, k_row, v_row):
    """(m0, l0, acc0) of rows that have attended over one key so far:
    their own. q: (..., G, Dh); k_row, v_row: (..., Dh)."""
    s = jnp.sum(q.astype(jnp.float32)
                * k_row.astype(jnp.float32)[..., None, :],
                -1) / math.sqrt(q.shape[-1])
    acc = jnp.broadcast_to(v_row.astype(jnp.float32)[..., None, :], q.shape)
    return s, jnp.ones_like(s), acc


def pooled_keys_of(kc_pool, layer, table, Hkv):
    """A slot's pooled keys in window order: kc_pool (L, num_blocks, Hkv
    * w, Dh), table (..., n) physical pages -> (..., Hkv, n * w, Dh)."""
    kb = kc_pool[layer, table]                          # (..., n, Hkv w, Dh)
    *lead, n, hw, Dh = kb.shape
    kb = jnp.moveaxis(kb.reshape(*lead, n, Hkv, hw // Hkv, Dh), -3, -4)
    return kb.reshape(*lead, Hkv, n * (hw // Hkv), Dh)


def pages_of(table, blocks):
    """``table[blocks]`` for a list of blocks: table (..., n) a slot's
    physical pages by block, shaped to broadcast against blocks (..., P,
    1). A compare and a sum over the table, since a gather of a prompt
    chunk's 131,072 scalars takes the TPU five times as long; a block
    past the table reads the null page, which is a page of the pool."""
    m = jnp.arange(table.shape[-1], dtype=blocks.dtype)
    return jnp.sum(jnp.where(blocks[..., None] == m, table, NULL_BLOCK), -1,
                   dtype=table.dtype)


def decode_write_indices(sp, tables, lengths):
    """Where a decode step's new rows go, the same for every layer: the
    page and row of the new token's key and value, and the page and
    window (null page where none is completed) of the pooled key the new
    token completes."""
    bs, st, ks = sp.block_size, sp.kernel_stride, sp.kernel_size
    t = lengths
    one = lambda i: jnp.take_along_axis(tables, i[:, None], 1)[:, 0]
    completes = ((t + 1) % st == 0) & (t + 1 >= ks)
    j_new = jnp.maximum(t + 1 - ks, 0) // st
    return {"page": one(t // bs), "row": t % bs,
            "kc_page": jnp.where(completes, one(j_new * st // bs), NULL_BLOCK),
            "kc_window": j_new % sp.windows_per_block,
            "completes": completes, "j_new": j_new}


def lay_rows(cur, row, new):
    """Pages ``cur`` (L, N, ..., R, Dh) with ``new`` (L, N, ..., Dh) laid
    over row ``row[n]`` of page n."""
    R = cur.shape[-2]
    hit = (jnp.arange(R)[None, :] == row[:, None]).reshape(
        (1, len(row)) + (1,) * (cur.ndim - 4) + (R, 1))
    return jnp.where(hit, new[..., None, :].astype(cur.dtype), cur)


WRITE_DEPTH = 64


def write_rows(pool, page, row, new):
    """pool (L, num_blocks, ..., R, Dh) with ``new`` (L, N, ..., Dh) laid
    over row ``row[n]`` of page ``page[n]``: whole pages are read, changed
    and written back, so that nothing indexes inside a page of the pool
    (XLA re-lays the WHOLE pool out for a scatter or a gather that does).
    Rows of several slots on one page (idle slots, the null page) race;
    what lands there is never read unmasked. A pool deeper than
    ``WRITE_DEPTH`` layers (a looped stack's: a cache layer a (pass, layer)
    pair) is written ``WRITE_DEPTH`` layers at a time: the TPU compiler
    cuts a gather over more than 128 layers into SLICES of the pool, which
    it copies (3.3 GB beside two pools of 3.4 at Ouro's 192:
    ``tests/test_tpu_compile.py``)."""
    for lo in range(0, pool.shape[0], WRITE_DEPTH):
        part = slice(lo, lo + WRITE_DEPTH) if pool.shape[0] > WRITE_DEPTH \
            else slice(None)
        pool = pool.at[part, page].set(
            lay_rows(pool[part, page], row, new[part]))
    return pool


def write_kv_rows(k_pool, v_pool, page, row, k_rows, v_rows):
    """``write_rows`` for a pool of keys and its pool of values."""
    return (write_rows(k_pool, page, row, k_rows),
            write_rows(v_pool, page, row, v_rows))


def write_decode_rows(sp, k_pool, v_pool, kc_pool, at, k_rows, v_rows,
                      pooled):
    """A decode step's new keys, values (n, N, Hkv, Dh) and pooled keys
    of all sparse layers into the slots' pages at ``at``
    (``decode_write_indices``); a pooled key lands in the window its
    token completes, the null page's where it completes none."""
    k_pool, v_pool = write_kv_rows(k_pool, v_pool, at["page"], at["row"],
                                   k_rows, v_rows)
    n, N, Hkv, Dh = pooled.shape
    w = sp.windows_per_block
    cur = kc_pool[:, at["kc_page"]].reshape(n, N, Hkv, w, Dh)
    hit = (jnp.arange(w)[None, :] == at["kc_window"][:, None])
    kc_pool = kc_pool.at[:, at["kc_page"]].set(jnp.where(
        hit[None, :, None, :, None], pooled[..., None, :], cur
    ).reshape(n, N, -1, Dh))
    return k_pool, v_pool, kc_pool


def sparse_decode_attend(sp, k_pool, v_pool, kc_pool, layer, q, k_row,
                         v_row, tables, lengths, at, attend_pages):
    """One minicpm4 layer's decode attention for all slots. q: (N, 1, H,
    Dh); k_row, v_row: (N, Hkv, Dh), the new token's, in the pool's dtype;
    slot i's new token sits at position ``lengths[i]``; ``at`` is
    ``decode_write_indices``. Scores the slot's pooled keys (the window
    the new token completes among them), lists the pages under the one
    causal rule (mixers.page_list: ascending, so the slot's own partly
    filled page is the last that counts) and reads those. Returns (ctx
    (N, 1, H, Dh), that window's pooled key (N, Hkv, Dh))."""
    N, _, H, Dh = q.shape
    Hkv, bs = k_pool.shape[2], k_pool.shape[3]
    bps = tables.shape[1]
    w, ks = sp.windows_per_block, sp.kernel_size
    t = lengths
    # the window that ends at the new token: ks - 1 rows of the slot's own
    # page and the one before it (whole pages gathered, rows picked after)
    bt = t // bs
    two = jnp.take_along_axis(
        tables, jnp.stack([jnp.maximum(bt - 1, 0), bt], 1), axis=1)
    pg = jnp.swapaxes(k_pool[layer, two], 1, 2).reshape(N, Hkv, 2 * bs, Dh)
    idx = (t % bs)[:, None] + bs - (ks - 1) + jnp.arange(ks - 1)
    prev = jnp.take_along_axis(pg, idx[:, None, :, None], axis=2)
    window = jnp.concatenate([prev, k_row[:, :, None]], 2)  # (N, Hkv, ks, Dh)
    kbar_new = mx.pool_windows(jnp.moveaxis(window, 2, 0), sp)[0]
    kbar = pooled_keys_of(kc_pool, layer, tables, Hkv)      # (N, Hkv, J, Dh)
    J = bps * w
    new = (jnp.arange(J)[None, :] == at["j_new"][:, None]) \
        & at["completes"][:, None]
    kbar = jnp.where(new[:, None, :, None], kbar_new[:, :, None, :], kbar)
    b = mx.block_scores(q[:, 0], kbar, mx.visible_windows(t, J, sp), sp)
    blocks, valid = mx.select_blocks(b, bt, sp)
    blk, n = mx.page_list(blocks, valid, t, sp, sp.list_blocks)
    pages = pages_of(tables[:, None, None, :], blk)
    n_tokens = jnp.maximum(n - 1, 0) * bs + (t % bs)[:, None]
    R, G, P = N * Hkv, H // Hkv, pages.shape[-1]
    q_rows = q.reshape(R, G, Dh)
    ctx = attend_pages(
        k_pool, v_pool, layer, q_rows, jnp.tile(jnp.arange(Hkv), N),
        pages.reshape(R, P), n_tokens.reshape(R),
        *_own_token_init(q_rows, k_row.reshape(R, Dh), v_row.reshape(R, Dh)))
    return ctx.reshape(N, 1, H, Dh), kbar_new


def _keys_init(qg, k, v, sees=None):
    """(m, l, acc) of queries over the keys each one sees, at least one.
    qg: (T, Hkv, G, Dh); k, v: (K, Hkv, Dh); sees: (T, K) bool, or None
    for a prompt chunk over its own keys, each query up to itself."""
    T, Dh = qg.shape[0], qg.shape[-1]
    s = jnp.einsum("qhgd,khd->qhgk", qg, k,
                   preferred_element_type=jnp.float32) * (1.0 / math.sqrt(Dh))
    if sees is None:
        sees = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    s = jnp.where(sees[:, None, None, :], s, -1e30)
    m = jnp.max(s, -1)
    p = jnp.exp(s - m[..., None])
    acc = jnp.einsum("qhgk,khd->qhgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return m, jnp.sum(p, -1), acc


def chunk_attend_all(k_pool, v_pool, layer, q, k, v, table_row, offset,
                     n_past: int):
    """Causal attention of a prompt chunk's C queries at positions
    ``offset ..`` (traced) over ALL their past: the slot's first
    ``n_past`` pages, of which the positions below ``offset`` count (whole
    pages gathered once for the chunk), and the chunk's own keys up to
    each query, in one softmax. q: (C, H, Dh); k, v: (C, Hkv, Dh) in the
    pool's dtype; pools in the mixed layout. -> (C, H, Dh) in q's dtype.
    The route of a mamba_attn layer's chunk and of a sparse layer's chunk
    inside ``dense_len``; for an eva layer's list of two roles (the list
    and its count of rows for ``table_row`` and ``offset``) the route
    under a mesh and off the TPU, and the oracle of
    ``chunk_attend_all_kernel``, which one TPU runs (``chunk_attend_for``)."""
    C, H, Dh = q.shape
    Hkv, bs = k_pool.shape[2], k_pool.shape[3]
    scale = 1.0 / math.sqrt(Dh)
    qg = q.reshape(C, Hkv, H // Hkv, Dh)
    past = lambda pool: jnp.swapaxes(
        pool[layer, table_row[:n_past]], 0, 1).reshape(Hkv, n_past * bs, Dh)
    kp, vp = past(k_pool), past(v_pool)
    m0, l0, acc0 = _keys_init(qg, k, v)
    live = jnp.arange(n_past * bs) < offset

    def tile(a):
        qt, m0, l0, acc0 = a
        s = jnp.einsum("qhgd,hkd->qhgk", qt, kp,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(live, s, -1e30)
        m = jnp.maximum(m0, jnp.max(s, -1))
        p = jnp.exp(s - m[..., None])
        alpha = jnp.exp(m0 - m)
        acc = alpha[..., None] * acc0 + jnp.einsum(
            "qhgk,hkd->qhgd", p.astype(vp.dtype), vp,
            preferred_element_type=jnp.float32)
        return acc / (alpha * l0 + jnp.sum(p, -1))[..., None]

    tq = min(C, 256)
    out = jax.lax.map(tile, jax.tree.map(
        lambda a: a.reshape(C // tq, tq, *a.shape[1:]),
        (qg, m0, l0, acc0)))
    return out.reshape(C, H, Dh).astype(q.dtype)


def decode_attend_all(k_pool, v_pool, layer, q, k_row, v_row, tables,
                      lengths, attend_slots):
    """One layer's decode attention for all slots over EVERY page a
    slot's list names, pools in the mixed layout: ``tables`` (N, P) is a
    list a SLOT (its whole table, or its list of two roles), of which the
    first ``lengths`` positions count, the same for each of the slot's
    key heads; every query has its own new key as what it has attended
    over already. This is the one place where all of a slot's key heads
    provably read the same pages, so the list goes on unbroadcast. q: (N,
    1, H, Dh); k_row, v_row: (N, Hkv, Dh) in the pool's dtype.
    ``attend_slots``: ``slot_attend_for``'s. Returns ctx (N, 1, H, Dh)."""
    N, _, H, Dh = q.shape
    Hkv = k_pool.shape[2]
    qg = q.reshape(N, Hkv, H // Hkv, Dh)
    ctx = attend_slots(k_pool, v_pool, layer, qg, tables, lengths,
                       *_own_token_init(qg, k_row, v_row))
    return ctx.reshape(N, 1, H, Dh)


def chosen_list_width(sp) -> int:
    """Width of a prompt-chunk row's list of chosen pages:
    ``mixers.chosen_width`` (31 as published), taken up to whole runs of
    512 tokens of pages (8 of 64). The kernel moves a row's list in
    copy-chunks that divide its width and scores a copy-chunk at once:
    31 pages would be 1,984 positions, not whole lanes of scores."""
    from ..ops.pallas.paged_sparse_attn import _CHUNK_TOKENS

    most = max(1, _CHUNK_TOKENS // sp.block_size)
    width = max(1, mx.chosen_width(sp))
    return width if width <= most else -(-width // most) * most


def sparse_chunk_attend(sp, k_pool, v_pool, kc_pool, layer, q, k, v,
                        table_row, offset, attend_pages):
    """One minicpm4 layer's attention for a prompt chunk of C tokens at
    positions ``offset ..`` (traced; a multiple of C, which divides
    ``dense_len``) of one slot, whose earlier pages are in the pool. q:
    (C, H, Dh); k, v: (C, Hkv, Dh) in the pool's dtype. While the chunk
    ends inside ``dense_len`` every query attends to all its past; beyond
    it every query selects, and its selection is read in two parts. What
    is FORCED is the same run of the slot's table for every query of the
    chunk (``mixers.forced_past``: the initial blocks and the local
    blocks before the chunk): those pages are gathered once and attended
    densely beside the chunk's own keys (all inside each query's local
    window), a tile of queries at a time. What is CHOSEN differs from row
    to row and goes through ``attend_pages`` as ``mixers.select_chosen``
    lists it: whole blocks before the chunk in ascending order. A row's
    keys meet one online softmax, so their order decides the rounding of
    its sums and nothing else, and the kernel asks only that every entry
    name a page. Returns (ctx (C, H, Dh), the pooled keys of the C / st
    windows this chunk completes, the first of them starting st tokens
    before the chunk: (C / st, Hkv, Dh))."""
    C, H, Dh = q.shape
    Hkv, bs = k_pool.shape[2], k_pool.shape[3]
    G = H // Hkv
    bps = table_row.shape[0]
    w, st = sp.windows_per_block, sp.kernel_stride
    q_pos = offset + jnp.arange(C, dtype=jnp.int32)
    # the window that starts st tokens before the chunk ends inside it:
    # those tokens are the last rows of the page before (a whole page read)
    before = k_pool[layer, table_row[jnp.maximum(offset // bs - 1, 0)]]
    kbar_new = mx.pool_windows(
        jnp.concatenate([jnp.swapaxes(before, 0, 1)[bs - st:], k], 0), sp)
    qg = q.reshape(C, Hkv, G, Dh)

    def dense():
        return chunk_attend_all(k_pool, v_pool, layer, q, k, v, table_row,
                                offset, min(sp.dense_len // bs, bps))

    def forced_and_own():
        """(m, l, acc) of every query over the forced pages before the
        chunk and the chunk's own keys up to itself."""
        blocks, sees = mx.forced_past(offset // bs, q_pos // bs, sp)
        ids = table_row[jnp.maximum(blocks, 0)]
        keys = lambda pool, own: jnp.concatenate(
            [jnp.swapaxes(pool[layer, ids], 1, 2).reshape(-1, Hkv, Dh), own])
        kf, vf = keys(k_pool, k), keys(v_pool, v)
        sees = jnp.concatenate(
            [jnp.repeat(sees, bs, axis=1),
             jnp.arange(C)[None, :] <= jnp.arange(C)[:, None]], 1)
        tq = min(C, 256)
        return jax.tree.map(
            lambda a: a.reshape(C, *a.shape[2:]),
            jax.lax.map(lambda a: _keys_init(a[0], kf, vf, a[1]), jax.tree.map(
                lambda a: a.reshape(C // tq, tq, *a.shape[1:]), (qg, sees))))

    def sparse():
        J = bps * w
        kbar = jax.lax.dynamic_update_slice(
            pooled_keys_of(kc_pool, layer, table_row, Hkv),    # (Hkv, J, Dh)
            jnp.swapaxes(kbar_new, 0, 1), (0, offset // st - 1, 0))
        b = mx.block_scores(q, kbar, mx.visible_windows(q_pos, J, sp), sp)
        blk, n = mx.select_chosen(b, q_pos // bs, sp, chosen_list_width(sp))
        pages = pages_of(table_row, blk)                       # (C, Hkv, P)
        R = C * Hkv
        m0, l0, acc0 = forced_and_own()
        ctx = attend_pages(
            k_pool, v_pool, layer, q.reshape(R, G, Dh),
            jnp.tile(jnp.arange(Hkv), C), pages.reshape(R, -1),
            (n * bs).reshape(R), m0.reshape(R, G), l0.reshape(R, G),
            acc0.reshape(R, G, Dh))
        return ctx.reshape(C, H, Dh)

    ctx = jax.lax.cond(offset + C <= sp.dense_len, dense, sparse)
    return ctx, kbar_new


def write_chunk_pages(k_pool, v_pool, table_row, offset, kk, vv):
    """A prompt chunk's keys and values (n, C, Hkv, Dh) of all paged
    layers into the slot's pages from ``offset`` on, whole pages at a
    time (``table_row`` padded past the slot's last page)."""
    n, C, Hkv, Dh = kk.shape
    bs = k_pool.shape[3]
    pg = C // bs
    ids = jax.lax.dynamic_slice(table_row, (offset // bs,), (pg,))
    pages = lambda t: jnp.swapaxes(t.reshape(n, pg, bs, Hkv, Dh), 2, 3)
    return k_pool.at[:, ids].set(pages(kk)), v_pool.at[:, ids].set(pages(vv))


def write_chunk(sp, k_pool, v_pool, kc_pool, table_row, offset, kk, vv,
                pooled):
    """A prompt chunk's keys, values (n, C, Hkv, Dh) and pooled keys (n, C
    / st, Hkv, Dh) of all sparse layers into the slot's pages, whole
    pages at a time. ``table_row`` is padded past the slot's last page;
    the pooled keys start at the last window of the page BEFORE the chunk
    (the null page's at offset 0)."""
    n, C, Hkv, Dh = kk.shape
    bs, w = sp.block_size, sp.windows_per_block
    pg = C // bs
    k_pool, v_pool = write_chunk_pages(k_pool, v_pool, table_row, offset,
                                       kk, vv)
    ids = jax.lax.dynamic_slice(
        jnp.concatenate([jnp.zeros((1,), table_row.dtype), table_row]),
        (offset // bs,), (pg + 1,))
    cur = jnp.swapaxes(kc_pool[:, ids].reshape(n, pg + 1, Hkv, w, Dh), 1, 2)
    cur = cur.reshape(n, Hkv, (pg + 1) * w, Dh).at[
        :, :, w - 1:w - 1 + pooled.shape[1]].set(jnp.swapaxes(pooled, 1, 2))
    cur = jnp.swapaxes(cur.reshape(n, Hkv, pg + 1, w, Dh), 1, 2)
    return k_pool, v_pool, kc_pool.at[:, ids].set(
        cur.reshape(n, pg + 1, Hkv * w, Dh))


# ------------------------------------------------------------------ #
# eva: the window's pages reused, and pages of summaries
# ------------------------------------------------------------------ #


def check_eva_pages(cfg: GPTConfig, scfg: ServingConfig) -> None:
    """A slot's table is ``[window / bs pages of exact keys | pages of
    summaries]``: a window is whole pages, a chunk never straddles a page,
    and the summaries a window leaves behind fill whole pages."""
    ev, bs = cfg.eva, scfg.block_size
    if ev.window % bs or bs % ev.chunk or ev.summaries % bs:
        raise ValueError(
            f"eva pages: block_size ({bs}) must divide the window "
            f"({ev.window}) and the summaries a window leaves behind "
            f"({ev.summaries}), and be a multiple of the chunk ({ev.chunk})")


def eva_page_list(ev, scfg: ServingConfig, table, n, width: int):
    """The pages a query reads at ``n`` positions cached (traced, (...,)),
    ``width`` entries, and how many rows of their concatenation count:
    the summary pages of the ``n // window`` windows left behind, then the
    window's own pages from its first, ``summaries * (n // window) + n
    mod window`` rows in all. What lies beyond the count is there and not
    seen: the rows of the window before in the reused pages, and the
    summaries of the window being filled (their pages are not even
    listed). table: (..., blocks_per_slot). Entries past the last that
    counts name the null page."""
    bs, ring = scfg.block_size, scfg.table_widths[0]
    w, r = n // ev.window, n % ev.window
    n_sum = ((ev.summaries // bs) * w)[..., None]     # summary pages listed
    j = jnp.arange(width, dtype=jnp.int32)
    entry = jnp.where(j < n_sum, ring + j, j - n_sum)
    counts = j < n_sum + (r[..., None] + bs - 1) // bs
    pages = jnp.take_along_axis(
        table, jnp.clip(entry, 0, table.shape[-1] - 1), axis=-1)
    return (jnp.where(counts, pages, NULL_BLOCK),
            (ev.summaries * w + r).astype(jnp.int32))


def eva_decode_indices(ev, scfg: ServingConfig, tables, lengths):
    """Where a decode step reads and writes, the same for every layer.
    Slot i's new token sits at position ``t = lengths[i]``: its key and
    value go to row ``t mod window`` of the reused pages; it reads the
    list of ``eva_page_list``; where it completes a chunk (``(t + 1) mod
    chunk == 0``) the chunk's summary goes to row ``t // chunk`` of the
    summary pages (the null page's where it completes none)."""
    bs, ring = scfg.block_size, scfg.table_widths[0]
    t = lengths
    one = lambda i: jnp.take_along_axis(tables, i[:, None], 1)[:, 0]
    r = t % ev.window
    pages, count = eva_page_list(ev, scfg, tables, t, tables.shape[1])
    completes = (t + 1) % ev.chunk == 0
    s = t // ev.chunk
    return {"page": one(r // bs), "row": r % bs, "group": (r % bs) // ev.chunk,
            "pages": pages, "count": count,
            "s_page": jnp.where(completes, one(ring + s // bs), NULL_BLOCK),
            "s_row": s % bs}


def write_eva_decode(ev, k_pool, v_pool, at, k_rows, v_rows, mu, phi):
    """A decode step's new keys and values (n, N, H, Dh) of all eva
    layers into the slots' reused pages at ``at`` (``eva_decode_indices``)
    and, from the page as it then stands, the summary of the chunk the new
    token lies in into the summary row it completes (the null page's where
    it completes none). mu, phi: (n, H, Dh). Whole pages are read, changed
    and written back (``write_rows``)."""
    c = ev.chunk
    N = len(at["row"])
    pick = (jnp.arange(k_pool.shape[3] // c)[None, :]
            == at["group"][:, None])[None, :, None, :, None, None]

    def lay(pool, rows):
        """The pool with the new rows in, and the new token's chunk: c
        rows of its page, position-major (n, N, c, H, Dh)."""
        page = lay_rows(pool[:, at["page"]], at["row"], rows)
        n, _, H, bs, Dh = page.shape
        grp = jnp.sum(jnp.where(pick, page.reshape(n, N, H, bs // c, c, Dh),
                                0), 3)
        return pool.at[:, at["page"]].set(page), jnp.moveaxis(grp, 3, 2)

    k_pool, kc = lay(k_pool, k_rows)
    v_pool, vc = lay(v_pool, v_rows)
    sk, sv = mx.eva_summaries(kc, vc, mu[:, None, None], phi[:, None, None])
    return write_kv_rows(k_pool, v_pool, at["s_page"], at["s_row"], sk, sv)


def eva_chunk_past(ev, scfg: ServingConfig, C: int) -> int:
    """Entries of a prompt chunk's list of past pages: every summary page
    a slot may hold and the window's pages before a chunk of C."""
    return scfg.table_widths[1] + (ev.window - C) // scfg.block_size


def write_eva_chunk(ev, scfg: ServingConfig, k_pool, v_pool, table_row,
                    offset, n_valid, kk, vv, mu, phi):
    """A prompt chunk's keys and values (n, C, H, Dh) of all eva layers
    into the window's pages from row ``offset mod window`` on (whole
    pages; a chunk never straddles a window) and the summaries of its C /
    chunk chunks into the summary rows from ``offset / chunk`` on, zeros
    for a chunk not wholly below ``n_valid`` (the decode steps that
    complete it write its summary); ONE scatter of whole pages a pool
    (a scatter of one page alone has the TPU compiler re-lay the whole
    pool out). mu, phi: (n, H, Dh)."""
    n, C, H, Dh = kk.shape
    bs, ring, c = scfg.block_size, scfg.table_widths[0], ev.chunk
    ns = C // c
    chunks = lambda t: t.reshape(n, ns, c, H, Dh)
    sk, sv = mx.eva_summaries(chunks(kk), chunks(vv), mu[:, None, None],
                           phi[:, None, None])               # (n, ns, H, Dh)
    whole = (jnp.arange(ns) < n_valid // c)[None, :, None, None]
    first = offset // c
    pages = lambda t: jnp.swapaxes(t.reshape(n, -1, bs, H, Dh), 2, 3)
    ids = jnp.concatenate([
        jax.lax.dynamic_slice(table_row, (offset % ev.window // bs,),
                              (C // bs,)),
        jax.lax.dynamic_slice(table_row, (ring + first // bs,),
                              (max(ns // bs, 1),))])

    def write(pool, rows, summaries):
        summaries = jnp.where(whole, summaries, 0).astype(pool.dtype)
        if ns % bs == 0:
            new = pages(summaries)
        else:       # a part of one page: read, change, write back
            new = jax.lax.dynamic_update_slice(
                pool[:, ids[-1:]], jnp.swapaxes(summaries, 1, 2)[:, None],
                (0, 0, 0, first % bs, 0))
        return pool.at[:, ids].set(jnp.concatenate([pages(rows), new], 1))

    return write(k_pool, kk, sk), write(v_pool, vv, sv)


# ------------------------------------------------------------------ #
# full_attn and window_attn: every key in one pool, a ring in another
# ------------------------------------------------------------------ #


def check_ring_pages(cfg: GPTConfig, scfg: ServingConfig) -> None:
    """A slot's table is ``[a page for every block_size positions | window
    / bs pages of the ring]``, each section naming pages of its own pool:
    the ring is whole pages."""
    if cfg.gqa.window % scfg.block_size:
        raise ValueError(
            f"ring pages: block_size ({scfg.block_size}) must divide the "
            f"window ({cfg.gqa.window})")


def pool_bytes(kv: "PagedKVCache") -> Tuple[int, ...]:
    """Bytes of each pool of a cache, keys and values: what the rules'
    arithmetic reckons (pages x layers that read them x a page's bytes)."""
    pairs = zip(*(p if isinstance(p, tuple) else (p,) for p in (kv.k, kv.v)))
    return tuple(k.nbytes + v.nbytes for k, v in pairs)


def position_bytes(kv: "PagedKVCache") -> int:
    """Bytes one cached position costs, from the pools' own shapes: a row
    of every page pool (keys, values, the selector's pooled keys), as deep
    as its cache layers (a looped stack's are ``loop_steps`` times its
    layers)."""
    bs = kv.scfg.block_size
    return sum(a.nbytes // (a.shape[1] * bs)
               for a in jax.tree.leaves((kv.k, kv.v, kv.kc)))


def ring_decode_indices(scfg: ServingConfig, tables, lengths):
    """Where a decode step reads and writes in a stack of full_attn and
    window_attn layers, the same for every layer of a kind. Slot i's new
    token sits at position ``t = lengths[i]``. Full layers: row ``t mod
    bs`` of page ``t // bs`` of the table's first section, which they
    read whole. Window layers: ring row ``t mod ring``, on the ring's
    page ``own``, where position ``t - ring`` (just out of the window)
    lies until the new key replaces it; every OTHER page of the ring that
    holds a position of the window is whole inside it: all of them once
    the ring has wrapped (listed from the one after ``own``, in ring
    order), the pages before ``own`` until then. ``tables``: (N,
    blocks_per_slot), ``[every key | the ring]``."""
    bs = scfg.block_size
    n_full, R = scfg.table_widths
    t = lengths
    full, ring = tables[:, :n_full], tables[:, n_full:]
    one = lambda tab, i: jnp.take_along_axis(tab, i[:, None], 1)[:, 0]
    own = (t // bs) % R
    wrapped = t >= R * bs
    j = jnp.arange(R, dtype=jnp.int32)[None, :]
    entry = jnp.where(wrapped[:, None], (own[:, None] + 1 + j) % R, j)
    return {"full": full, "page": one(full, t // bs), "row": t % bs,
            "ring_page": one(ring, own), "wrapped": wrapped,
            "others": jnp.take_along_axis(ring, entry, 1),
            "others_rows": jnp.where(wrapped, R - 1, own) * bs}


def ring_decode_attend(k_pool, v_pool, layer, q, k_row, v_row, at,
                       attend_slots):
    """One window_attn layer's decode attention for all slots: the new
    token at position t over the keys ``t - ring < j <= t``. The ring's
    page that takes the new key is read whole in XLA with the new row laid
    over the one it replaces (the rows before it are the newest, those
    after it the window's oldest, there once the ring has wrapped), and
    seeds the softmax; the ring's other pages are whole inside the window
    and go through the page-list read (``attend_slots``, a list a slot).
    k_pool, v_pool: the rings' pool (n_window, blocks, Hkv, bs, Dh); q:
    (N, 1, H, Dh); k_row, v_row: (N, Hkv, Dh) in the pool's dtype; ``at``:
    ``ring_decode_indices``. Returns ctx (N, 1, H, Dh)."""
    N, _, H, Dh = q.shape
    Hkv, bs = k_pool.shape[2], k_pool.shape[3]
    qg = q.reshape(N, Hkv, H // Hkv, Dh)
    lay = lambda pool, new: lay_rows(
        pool[layer, at["ring_page"]][None], at["row"], new[None])[0]
    ko, vo = lay(k_pool, k_row), lay(v_pool, v_row)     # (N, Hkv, bs, Dh)
    s = jnp.einsum("nhgd,nhkd->nhgk", qg, ko,
                   preferred_element_type=jnp.float32) / math.sqrt(Dh)
    r = jnp.arange(bs)[None, :]
    sees = (r <= at["row"][:, None]) | at["wrapped"][:, None]
    s = jnp.where(sees[:, None, None, :], s, -1e30)
    m = jnp.max(s, -1)
    pr = jnp.exp(s - m[..., None])
    acc = jnp.einsum("nhgk,nhkd->nhgd", pr.astype(vo.dtype), vo,
                     preferred_element_type=jnp.float32)
    ctx = attend_slots(k_pool, v_pool, layer, qg, at["others"],
                       at["others_rows"], m, jnp.sum(pr, -1), acc)
    return ctx.reshape(N, 1, H, Dh)


def chunk_attend_past(k_pool, v_pool, layer, q, k, v, table_row, offset):
    """``chunk_attend_all`` for a slot whose past may be long: the C
    queries at positions ``offset ..`` (traced, a multiple of C) over ALL
    their past and the chunk's own keys up to each, the past read C
    positions (whole pages) at a time through one online softmax, as many
    times as the past is long (a traced trip count: a chunk at offset 0
    reads nothing, one at 30,720 thirty tiles). -> (C, H, Dh)."""
    C, H, Dh = q.shape
    Hkv, bs = k_pool.shape[2], k_pool.shape[3]
    scale = 1.0 / math.sqrt(Dh)
    pg = C // bs
    qg = q.reshape(C, Hkv, H // Hkv, Dh)

    def tile(i, carry):
        m0, l0, acc0 = carry
        ids = jax.lax.dynamic_slice(table_row, (i * pg,), (pg,))
        past = lambda pool: jnp.swapaxes(pool[layer, ids], 0, 1).reshape(
            Hkv, C, Dh)
        kp, vp = past(k_pool), past(v_pool)
        s = jnp.einsum("qhgd,hkd->qhgk", qg, kp,
                       preferred_element_type=jnp.float32) * scale
        m = jnp.maximum(m0, jnp.max(s, -1))
        p = jnp.exp(s - m[..., None])
        alpha = jnp.exp(m0 - m)
        acc = alpha[..., None] * acc0 + jnp.einsum(
            "qhgk,hkd->qhgd", p.astype(vp.dtype), vp,
            preferred_element_type=jnp.float32)
        return m, alpha * l0 + jnp.sum(p, -1), acc

    _, l, acc = jax.lax.fori_loop(0, offset // C, tile, _keys_init(qg, k, v))
    return (acc / l[..., None]).reshape(C, H, Dh).astype(q.dtype)


def ring_chunk_attend(window: int, k_pool, v_pool, layer, q, k, v,
                      ring_row, offset):
    """Attention of a prompt chunk's C queries at positions ``offset ..``
    (traced, a multiple of C, which divides ``window``) in a window_attn
    layer: query i over the keys ``i - window < j <= i``, a band that is
    a different set of ring rows for every query. The ring (``ring_row``:
    its ``window / bs`` pages, gathered once) holds, in row r, the last
    position before the chunk that is r modulo ``window``; each query
    sees the rows whose position lies inside its band, beside the chunk's
    own keys up to itself, in one softmax. -> (C, H, Dh) in q's dtype."""
    C, H, Dh = q.shape
    Hkv = k_pool.shape[2]
    qg = q.reshape(C, Hkv, H // Hkv, Dh)
    ring = lambda pool: jnp.swapaxes(pool[layer, ring_row], 0, 1).reshape(
        Hkv, window, Dh)
    keys = lambda pool, own: jnp.concatenate(
        [jnp.swapaxes(ring(pool), 0, 1), own])              # (window + C, ..)
    r = jnp.arange(window, dtype=jnp.int32)
    held = offset - 1 - (offset - 1 - r) % window           # row r's position
    i = jnp.arange(C, dtype=jnp.int32)
    sees = jnp.concatenate(
        [(held[None, :] >= 0) & (held[None, :] > offset + i[:, None] - window),
         i[None, :] <= i[:, None]], 1)
    m, l, acc = _keys_init(qg, keys(k_pool, k), keys(v_pool, v), sees)
    return (acc / l[..., None]).reshape(C, H, Dh).astype(q.dtype)


def ring_oldest_first(window: int, ring_row, offset):
    """The ring's pages in position order before a chunk at ``offset``:
    from the page the chunk will be written over (``offset mod window``,
    which holds the window's oldest keys, or nothing yet) on, wrapping;
    list position p then holds position ``offset - window + p``."""
    R = ring_row.shape[0]
    bs = window // R
    return ring_row[(offset % window // bs + jnp.arange(R, dtype=jnp.int32))
                    % R]


def chunk_attend_past_kernel(k_pool, v_pool, layer, q, k, v, table_row,
                             offset):
    """``chunk_attend_past`` through ops/pallas/chunk_past_attn: the list
    is the slot's pages of every key, of which ``offset`` positions
    count."""
    from ..ops.pallas.chunk_past_attn import chunk_past_attn

    return chunk_past_attn(k_pool, v_pool, layer, q, k, v, table_row, 0,
                           offset)


def ring_chunk_attend_kernel(window: int, k_pool, v_pool, layer, q, k, v,
                             ring_list, offset):
    """``ring_chunk_attend`` through ops/pallas/chunk_past_attn, the
    ring's pages listed oldest first (``ring_oldest_first``): list
    position p holds position ``offset - window + p``, nothing while that
    is negative, and query i sees it while ``p > i``."""
    from ..ops.pallas.chunk_past_attn import chunk_past_attn

    return chunk_past_attn(k_pool, v_pool, layer, q, k, v, ring_list,
                           jnp.maximum(window - offset, 0), window, band=True)


def chunk_attend_all_kernel(k_pool, v_pool, layer, q, k, v, table_row,
                            offset, n_past: int):
    """``chunk_attend_all`` through ops/pallas/chunk_past_attn: the list
    is the row's first ``n_past`` entries, of which ``offset`` rows count
    (an eva layer's list of two roles and its count, ``eva_page_list``:
    what lies beyond the count names the null page)."""
    from ..ops.pallas.chunk_past_attn import chunk_past_attn

    return chunk_past_attn(k_pool, v_pool, layer, q, k, v,
                           table_row[:n_past], 0, offset)


class ChunkAttend(NamedTuple):
    """How a prompt chunk attends over pages and itself: ``past`` has
    ``chunk_attend_past``'s signature, ``listed`` ``chunk_attend_all``'s
    (a list and a count: the eva layers') and ``ring``
    ``ring_chunk_attend``'s, but for the ring's pages, which ``ring``
    takes as ``ring_pages(window, ring_row, offset)`` gives them, once a
    chunk for all its layers."""
    name: str               # "kernel" or "xla": the span's ``attn``
    past: Callable
    ring: Callable
    ring_pages: Callable
    listed: Callable


def chunk_attend_for(k_pool, n_head, C, mesh) -> ChunkAttend:
    """The forms of a prompt chunk's attention in full_attn, window_attn
    and eva layers (a stack's pools have one page shape): the kernel on
    one TPU at shapes it can tile, else the XLA forms (as
    ``decode_attend_for`` chooses)."""
    from ..ops.pallas import chunk_past_attn as kernel

    if (mesh is None or mesh.size == 1) and kernel.is_available(
            k_pool, n_head, C):
        return ChunkAttend("kernel", chunk_attend_past_kernel,
                           ring_chunk_attend_kernel, ring_oldest_first,
                           chunk_attend_all_kernel)
    return ChunkAttend("xla", chunk_attend_past, ring_chunk_attend,
                       lambda window, ring_row, offset: ring_row,
                       chunk_attend_all)


def write_ring_chunk(window: int, k_pool, v_pool, ring_row, offset, n_valid,
                     kk, vv):
    """A prompt chunk's keys and values (n, C, Hkv, Dh) of all window_attn
    layers over the ring's rows from ``offset mod window`` on, whole pages
    at a time (C divides the window: a chunk never runs past the ring's
    end). The rows at or beyond ``n_valid`` (a prompt's ragged last chunk)
    keep what they held: those positions are still inside the window of
    the tokens to come."""
    n, C, Hkv, Dh = kk.shape
    bs = k_pool.shape[3]
    pg = C // bs
    ids = jax.lax.dynamic_slice(ring_row, (offset % window // bs,), (pg,))
    real = (jnp.arange(C) < n_valid).reshape(1, pg, 1, bs, 1)
    pages = lambda t: jnp.swapaxes(t.reshape(n, pg, bs, Hkv, Dh), 2, 3)
    write = lambda pool, t: pool.at[:, ids].set(
        jnp.where(real, pages(t), pool[:, ids]))
    return write(k_pool, kk), write(v_pool, vv)


# ------------------------------------------------------------------ #
# what ONE program sees of the cache, and its write after the layer loop
# ------------------------------------------------------------------ #

# the kinds whose decode step reads ONE list a slot, the same for all its
# key heads (``decode_attend_all``)
SLOT_LIST_KINDS = frozenset({"mamba_attn", "eva", "full_attn"})


def chosen_forms(cfg: GPTConfig, scfg: ServingConfig, k_pool, mesh,
                 C: Optional[int]):
    """What the choosers pick for a stack's two programs, from shapes,
    mesh and platform alone: known when an engine is built, for the host's
    accounting. ``k_pool``: ``PagedKVCache.k``; ``C``: a prompt chunk's
    positions (None for a stack that has no chunk program). -> (whether
    the decode step's list-sharing layers copy a page once for all of a
    slot's key heads (``takes_slot_form``); how a prompt chunk attends
    over pages of two rules or of two roles (``chunk_attend_for``) and how
    it runs a kda layer's delta rule (``kda_chunk_for``): "kernel" or
    "xla", None where it does neither)."""
    kinds = set(cfg.layer_kinds)
    if isinstance(k_pool, tuple):
        k_pool = k_pool[0]      # the pool of every key
    # the list a slot: its whole table, or its first section beside a ring
    width = scfg.table_widths[0] if scfg.page_rule.ring \
        else scfg.blocks_per_slot
    return (bool(kinds & SLOT_LIST_KINDS) and takes_slot_form(
                k_pool, cfg.n_head, (scfg.num_slots, width), mesh),
            chunk_attend_for(k_pool, cfg.n_head, C, mesh).name
            if kinds & (GROUPED_KINDS | {"eva"}) else None,
            kda_chunk_for(C, cfg.kda, mesh)[1] if "kda" in kinds else None)


def decode_view(cfg: GPTConfig, scfg: ServingConfig, mesh):
    """-> ``view(params, k_pool, v_pool, kc_pool, state, tables, lengths,
    positions)`` for a decode step's trace: the cache's layout as ONE
    decode step sees it, reckoned once before the layer loop for all
    layers (and, of a looped stack, all passes: a core is handed its CACHE
    layer, ``pass * count(kind) + layer``, and ``kept`` comes stacked
    cache layer by cache layer), read by the kinds' cores
    (``serving/kinds.py``) and by ``write_decode_step``. A field has ONE
    meaning; a stack sets those its kinds read:

    ``cfg``, ``scfg``, ``params`` (the weights: the experts' stacks, eva's
    mu and phi); ``positions`` (N, 1), ``tables`` (N, blocks_per_slot),
    ``lengths`` (N,): where each slot's new token sits.
    ``k``, ``v``: the pool whose pages follow the length (the only one, or
    the first of two); ``k_ring``, ``v_ring``: the rings' pool beside it
    (None without one); ``kc``: the selector's pooled keys.
    ``pages`` (N, P), ``count`` (N,): the ONE list a slot whose every page
    a layer reads and the rows of it that count: the table (its first
    section beside a ring) under the length, or eva's list of two roles
    under its count; set in one place, by the stack's page rule.
    ``page``, ``row`` (N,): where the new token's row goes in ``k`` and
    ``v``, for the ONE kind of a stack whose pages follow the length
    (``mamba_attn``; ``full_attn`` alone, beside kda rows or, from
    ``ring_at``, beside a ring: four stacks that exclude each other,
    ``PagedKVCache.__init__``, each setting them where the equations came
    out before); a kind whose indexes say more keeps them whole: ``sparse_at``
    (``decode_write_indices``), ``eva_at`` (``eva_decode_indices``),
    ``ring_at`` (``ring_decode_indices``; None without a ring).
    ``live`` (N, 1, 1, 1): the slots whose state rows this step writes
    (one whose prompt is still being chunked in is idle here: its rows
    are the chunks' to write); ``real`` (N, 1), or None: the lanes that
    are tokens (an idle lane is routed to no expert).
    The forms the choosers picked: ``attend_rows`` (``decode_attend_for``),
    ``attend_pages`` (``sparse_attend_for``), ``attend_slots``
    (``slot_attend_for`` over ``pages``), ``attend_ring`` (the same over
    the ring's other pages), ``update_ssm`` (``ssm_rows_for``),
    ``update_kda`` (``kda_rows_for``); ``slopes``: lightning's decays."""
    kinds = set(cfg.layer_kinds)
    # reckoned when the program is BUILT: constants of it, not equations
    slopes = mx.lightning_slopes(cfg.n_head)

    def view(params, k_pool, v_pool, kc_pool, state, tables, lengths,
             positions):
        N, bs = lengths.shape[0], scfg.block_size
        f = SimpleNamespace(
            cfg=cfg, scfg=scfg, params=params, positions=positions,
            tables=tables, lengths=lengths, k=k_pool, v=v_pool, kc=kc_pool,
            k_ring=None, v_ring=None, ring_at=None, real=None, slopes=slopes)
        if isinstance(k_pool, tuple):
            (f.k, f.k_ring), (f.v, f.v_ring) = k_pool, v_pool
        if "attention" in kinds:
            f.attend_rows = decode_attend_for(k_pool, tables, cfg.n_head, mesh)
        if "minicpm4" in kinds:
            f.attend_pages = sparse_attend_for(k_pool, cfg.n_head, mesh)
            f.sparse_at = decode_write_indices(cfg.sparse, tables, lengths)
        if "mamba_attn" in kinds:
            f.page, f.row = tables[jnp.arange(N), lengths // bs], lengths % bs
            f.update_ssm = ssm_rows_for(state["ssm"], cfg.ssm.n_groups, mesh)
        if "eva" in kinds:
            f.eva_at = eva_decode_indices(cfg.eva, scfg, tables, lengths)
        if kinds & {"lightning", "mamba_attn", "kda"}:
            f.live = (lengths > 0)[:, None, None, None]
        if "kda" in kinds:
            # the full_attn layers' pages follow the length in the ONE pool;
            # the kda layers keep rows and tails
            f.row, f.page = lengths % bs, tables[jnp.arange(N), lengths // bs]
            f.update_kda = kda_rows_for(state["kda"], mesh)
        elif scfg.page_rule.ring:
            at = f.ring_at = ring_decode_indices(scfg, tables, lengths)
            f.page, f.row = at["page"], at["row"]
            f.attend_ring = slot_attend_for(f.k_ring, cfg.n_head,
                                            at["others"].shape, mesh)
        elif "full_attn" in kinds:
            # full_attn layers alone: their pages follow the length
            f.row, f.page = lengths % bs, tables[jnp.arange(N), lengths // bs]
        if kinds & (GROUPED_KINDS | {"kda"}):
            f.real = (lengths > 0)[:, None]
        if kinds & SLOT_LIST_KINDS:
            # the ONE list a slot, set here alone, by the stack's page rule
            f.pages, f.count = (
                (f.eva_at["pages"], f.eva_at["count"]) if "eva" in kinds else
                (f.ring_at["full"] if f.ring_at else tables, lengths))
            f.attend_slots = slot_attend_for(f.k, cfg.n_head, f.pages.shape,
                                             mesh)
        return f

    return view


def write_decode_step(view, kept):
    """The layers' new rows (``kept`` by kind, as the kinds' blocks handed
    them out, stacked over each kind's layers) into the donated pools, in
    place, after the layer loop; idle slots all target (null block, 0),
    never read unmasked. ``view``: ``decode_view``'s. -> (k_pool, v_pool,
    kc_pool)."""
    k, v, kc, k_ring, v_ring = view.k, view.v, view.kc, view.k_ring, \
        view.v_ring
    if "attention" in kept:
        bs, t = view.scfg.block_size, view.lengths
        wblk = view.tables[jnp.arange(t.shape[0]), t // bs]
        woff = t % bs
        k = k.at[:, wblk, woff].set(kept["attention"][0])   # (L, N, Hkv, Dh)
        v = v.at[:, wblk, woff].set(kept["attention"][1])
    if "minicpm4" in kept:
        k, v, kc = write_decode_rows(view.cfg.sparse, k, v, kc,
                                     view.sparse_at, *kept["minicpm4"])
    if "mamba_attn" in kept:                        # (L, N, Hkv, Dh) each
        k, v = write_kv_rows(k, v, view.page, view.row, *kept["mamba_attn"])
    if "eva" in kept:
        k, v = write_eva_decode(view.cfg.eva, k, v, view.eva_at,
                                *kept["eva"], view.params["eva"]["mu"],
                                view.params["eva"]["phi"])
    if "full_attn" in kept:
        k, v = write_kv_rows(k, v, view.page, view.row, *kept["full_attn"])
    if "window_attn" in kept:
        k_ring, v_ring = write_kv_rows(
            k_ring, v_ring, view.ring_at["ring_page"], view.ring_at["row"],
            *kept["window_attn"])
    if view.ring_at is not None:
        k, v = (k, k_ring), (v, v_ring)
    return k, v, kc


def chunk_view(cfg: GPTConfig, scfg: ServingConfig, mesh):
    """-> ``view(params, k_pool, v_pool, kc_pool, state, table_row, slot,
    offset, n_valid, positions)`` for the trace of a prompt chunk (of ``C
    = len(positions)`` positions): the cache's layout as that chunk sees
    it, read by the kinds' cores and by ``write_prefill_chunk``. As
    ``decode_view``'s, a field has ONE meaning and a stack sets those its
    kinds read:

    ``cfg``, ``scfg``, ``mesh``, ``params``; ``positions`` (C,): offset +
    0 .. C - 1; ``table_row``: the slot's table, null pages past its end
    (the last chunk may run past it); ``slot``, ``offset``, ``n_valid``.
    ``carried``: the slot's state rows as the chunk finds them, every
    layer's (zeros at offset 0: a row is cleared by whoever enters it).
    ``k``, ``v``, ``k_ring``, ``v_ring``, ``kc``: the pools, as
    ``decode_view``'s.
    ``full_row``: the slot's pages of every key (the table's first
    section beside a ring, else all of it); ``ring_row``: its ring's (None
    without one), and ``ring_pages``: the ring's as ``attend.ring`` reads
    them.
    ``past``, ``n_seen``, ``n_past``: eva's list of two roles before the
    chunk: the pages, the rows that count, the entries (``eva_page_list``,
    ``eva_chunk_past``).
    ``real`` (1, C), or None: the positions that are tokens (a chunk's
    padding is routed to no expert).
    The forms the choosers picked: ``attend_pages`` (``sparse_attend_for``),
    ``attend`` (``chunk_attend_for``), ``kda_rule`` (``kda_chunk_for``);
    ``slopes``: lightning's decays."""
    kinds = set(cfg.layer_kinds)
    # reckoned when the program is BUILT: constants of it, not equations
    slopes = mx.lightning_slopes(cfg.n_head)

    def view(params, k_pool, v_pool, kc_pool, state, table_row, slot, offset,
             n_valid, positions):
        C, bs = positions.shape[0], scfg.block_size
        f = SimpleNamespace(
            cfg=cfg, scfg=scfg, mesh=mesh, params=params, positions=positions,
            slot=slot, offset=offset, n_valid=n_valid, k=k_pool, v=v_pool,
            kc=kc_pool, k_ring=None, v_ring=None, ring_row=None, real=None,
            slopes=slopes, table_row=jnp.pad(table_row, (0, C // bs)))
        f.full_row = f.table_row
        if isinstance(k_pool, tuple):
            (f.k, f.k_ring), (f.v, f.v_ring) = k_pool, v_pool
        if "minicpm4" in kinds:
            f.attend_pages = sparse_attend_for(k_pool, cfg.n_head, mesh)
        if kinds & (GROUPED_KINDS | {"eva"}):
            f.attend = chunk_attend_for(f.k, cfg.n_head, C, mesh)
        if "eva" in kinds:
            f.n_past = eva_chunk_past(cfg.eva, scfg, C)
            f.past, f.n_seen = eva_page_list(cfg.eva, scfg, f.table_row,
                                             offset, f.n_past)
        f.carried = jax.tree.map(
            lambda rows: jnp.where(
                offset == 0, 0.0,
                jax.lax.dynamic_index_in_dim(rows, slot, 1, keepdims=False)),
            state)
        if "kda" in kinds:
            f.kda_rule, _ = kda_chunk_for(C, cfg.kda, mesh)
        elif scfg.page_rule.ring:
            n_full, n_ring = scfg.table_widths
            full_row, f.ring_row = f.table_row[:n_full + C // bs], \
                f.table_row[n_full:n_full + n_ring]
            f.full_row = full_row.at[n_full:].set(NULL_BLOCK)
            # the ring's pages as that form reads them, once for all layers
            f.ring_pages = f.attend.ring_pages(cfg.gqa.window, f.ring_row,
                                               offset)
        if kinds & (GROUPED_KINDS | {"kda"}):
            f.real = (jnp.arange(C) < n_valid)[None, :]
        return f

    return view


def write_prefill_chunk(view, state, kept):
    """A prompt chunk's keys, values and pooled keys into the slot's pages
    and its layers' new state rows into the slot's rows of ``state``
    (``kept`` by kind, stacked over each kind's layers), in place, after
    the layer loop. ``view``: ``chunk_view``'s. -> (k_pool, v_pool,
    kc_pool, state)."""
    k, v, kc, k_ring, v_ring = view.k, view.v, view.kc, view.k_ring, \
        view.v_ring

    def into_slot(state, new):
        return jax.tree.map(
            lambda rows, n: jax.lax.dynamic_update_slice(
                rows, n[:, None].astype(rows.dtype),
                (0, view.slot) + (0,) * (rows.ndim - 2)), state, new)

    if "minicpm4" in kept:
        k, v, kc = write_chunk(view.cfg.sparse, k, v, kc, view.table_row,
                               view.offset, *kept["minicpm4"])
    if "mamba_attn" in kept:
        (kk, vv), new = kept["mamba_attn"]
        k, v = write_chunk_pages(k, v, view.table_row, view.offset, kk, vv)
        state = into_slot(state, new)
    if "eva" in kept:
        k, v = write_eva_chunk(
            view.cfg.eva, view.scfg, k, v, view.table_row, view.offset,
            view.n_valid, *kept["eva"], view.params["eva"]["mu"],
            view.params["eva"]["phi"])
    if "full_attn" in kept:
        k, v = write_chunk_pages(k, v, view.full_row, view.offset,
                                 *kept["full_attn"])
    if "window_attn" in kept:
        k_ring, v_ring = write_ring_chunk(
            view.cfg.gqa.window, k_ring, v_ring, view.ring_row, view.offset,
            view.n_valid, *kept["window_attn"])
    for kind in ("lightning", "kda"):
        if kind in kept:
            state = into_slot(state, kept[kind])
    if view.ring_row is not None:
        k, v = (k, k_ring), (v, v_ring)
    return k, v, kc, state
