"""The speculative decode path's two compiled programs.

``make_draft_step`` runs the DRAFTER: one jitted program containing a
``lax.scan`` over ``draft_k + 1`` single-token paged decodes (the same
per-layer math as the engine's decode step, against the drafter's own
paged pool), proposing ``draft_k`` tokens per slot. The scan runs one
extra iteration so the last proposal's KV row is already in the drafter
pool when every draft is accepted — a full-accept round never needs a
host-side drafter resync.

``make_verify_step`` runs the TARGET over the ``draft_k + 1`` window
``[pending, d_1..d_K]`` in one forward (``paged_attend_multi``), picks
the target's own next-token choice at every position with EXACTLY the
decode step's selection math (argmax when temperature <= 0, else a
top-k-filtered categorical keyed by ``request_sample_key(seed, token
index)``), and accepts the longest draft prefix that MATCHES those
choices. Because the emitted stream — accepted drafts plus the target's
choice at the first mismatch — is by construction the token stream the
plain decode step would have produced, greedy speculative output is
bit-identical to plain greedy decode, and sampled accept/reject is a
pure function of (per-rid seed, token index): a failover retry or a
spec-off replica replays the identical stream. (This is common-random-
numbers coupling: drafter and target sample with the SAME key per token
index, so close distributions agree often — that agreement rate IS the
acceptance rate.)

Both programs are static-shape over the full slot array (idle lanes:
token 0 / length 0 / null tables) and donate their pools, so together
with the engine's fallback plain decode the decode path holds exactly
three compiled programs, each watched by the recompile watchdog.

KV rows written for rejected drafts are stale-but-invisible: the next
round's length-derived masks hide them until overwritten (the same
rollback-free contract as models/speculative.py).
"""

from functools import partial

import jax
import jax.numpy as jnp

from ...models.gpt import GPTConfig, layer_norm
from ..config import ServingConfig
from ..engine import _paged_block, request_sample_key
from ..kv_cache import paged_attend, paged_attend_multi


def _choose(logits, temps, seeds, idx, top_k):
    """The decode step's next-token selection over (N, V) logits —
    replicated operation-for-operation (engine.make_decode_step) so the
    verify step's per-position choices are bit-identical to what the
    plain decode program would pick at the same position."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    l32 = logits.astype(jnp.float32) / jnp.maximum(temps, 1e-6)[:, None]
    if top_k is not None:
        kth = jax.lax.top_k(l32, top_k)[0][..., -1:]
        l32 = jnp.where(l32 < kth, -1e30, l32)
    keys = jax.vmap(request_sample_key)(seeds, idx)
    sampled = jax.vmap(
        lambda k, row: jax.random.categorical(k, row)
    )(keys, l32).astype(jnp.int32)
    return jnp.where(temps > 0.0, sampled, greedy)


def _resolve_top_k(cfg: GPTConfig, scfg: ServingConfig):
    top_k = scfg.top_k
    if top_k is not None and top_k >= cfg.vocab_size:
        return None  # full-vocab top-k is a no-op filter
    return top_k


def _unembed(cfg: GPTConfig, params, x):
    cdt = cfg.dtype
    x = layer_norm(x, params["final_ln"]["scale"],
                   params["final_ln"]["bias"], cfg.layernorm_eps)
    if cfg.tie_embeddings:
        return x @ params["embed"]["wte"].astype(cdt).T
    return x @ params["lm_head"].astype(cdt)


def make_draft_step(cfg: GPTConfig, scfg: ServingConfig, draft_k: int):
    """Build the jitted drafter program.

    draft_step(params, k_pool, v_pool, tables, lengths, tokens, temps,
    seeds, counts) -> (drafts (N, K) int32, k_pool', v_pool'). ``cfg``
    is the DRAFTER config; pools are the drafter's paged pool (donated).
    Scan iteration j feeds the running token (the slot's pending token
    at j=0), writes its KV at row ``lengths + j``, and proposes the
    token for emitted index ``counts + j`` with the engine's selection
    math keyed at that index.
    """
    top_k = _resolve_top_k(cfg, scfg)

    @partial(jax.jit, donate_argnums=(1, 2))
    def ds_draft_step(params, k_pool, v_pool, tables, lengths, tokens,
                      temps, seeds, counts):
        cdt = cfg.dtype
        N = tokens.shape[0]
        wte = params["embed"]["wte"].astype(cdt)

        def one(carry, j):
            tok, k_pool, v_pool = carry
            pos = lengths + j
            x = jnp.take(wte, tok, axis=0)[:, None, :]      # (N, 1, D)
            positions = pos[:, None]
            if not cfg.rotary:
                x = x + jnp.take(params["embed"]["wpe"], positions,
                                 axis=0).astype(cdt)
            wblk = tables[jnp.arange(N), pos // scfg.block_size]
            woff = pos % scfg.block_size

            def scan_body(h, xs):
                layer_params, k_l, v_l = xs

                def attend(q, k, v):
                    ctx, k2, v2 = paged_attend(k_l, v_l, q, k, v, tables,
                                               pos, wblk, woff)
                    return ctx, (k2, v2)

                return _paged_block(cfg, h, layer_params, positions,
                                    attend)

            x, (k_pool, v_pool) = jax.lax.scan(
                scan_body, x, (params["layers"], k_pool, v_pool))
            logits = _unembed(cfg, params, x)[:, 0]
            nxt = _choose(logits, temps, seeds, counts + j, top_k)
            return (nxt, k_pool, v_pool), nxt

        # K+1 iterations: the extra one writes d_K's KV row (and its
        # proposal is discarded), keeping the drafter cache complete
        # even when the verify step accepts every draft
        (_, k_pool, v_pool), drafts = jax.lax.scan(
            one, (tokens, k_pool, v_pool),
            jnp.arange(draft_k + 1, dtype=jnp.int32))
        return drafts[:draft_k].T, k_pool, v_pool

    return ds_draft_step


def make_verify_step(cfg: GPTConfig, scfg: ServingConfig, draft_k: int):
    """Build the jitted target verify program.

    verify_step(params, k_pool, v_pool, tables, lengths, tokens (N, K+1),
    temps, seeds, counts) -> (n_acc (N,), bonus (N,), k_pool', v_pool').
    ``tokens`` is ``[pending, d_1..d_K]`` per slot; ``cfg``/pools are
    the TARGET's. n_acc is the length of the longest draft prefix
    matching the target's own per-position choices; bonus is the
    target's choice at the first mismatch (== position n_acc) — the
    host emits ``drafts[:n_acc] + [bonus]``.
    """
    T = draft_k + 1
    top_k = _resolve_top_k(cfg, scfg)

    @partial(jax.jit, donate_argnums=(1, 2))
    def ds_verify_step(params, k_pool, v_pool, tables, lengths, tokens,
                       temps, seeds, counts):
        cdt = cfg.dtype
        N = tokens.shape[0]
        wte = params["embed"]["wte"].astype(cdt)
        x = jnp.take(wte, tokens, axis=0)                   # (N, T, D)
        positions = lengths[:, None] + jnp.arange(T, dtype=jnp.int32)
        if not cfg.rotary:
            x = x + jnp.take(params["embed"]["wpe"], positions,
                             axis=0).astype(cdt)
        wblk = jnp.take_along_axis(tables,
                                   positions // scfg.block_size, axis=1)
        woff = positions % scfg.block_size

        def scan_body(h, xs):
            layer_params, k_l, v_l = xs

            def attend(q, k, v):
                ctx, k2, v2 = paged_attend_multi(k_l, v_l, q, k, v, tables,
                                                 lengths, wblk, woff)
                return ctx, (k2, v2)

            return _paged_block(cfg, h, layer_params, positions, attend)

        x, (k_pool, v_pool) = jax.lax.scan(
            scan_body, x, (params["layers"], k_pool, v_pool))
        logits = _unembed(cfg, params, x)                   # (N, T, V)
        # target's own choice at every window position, one static
        # unroll per position (T is small) so the selection math stays
        # the decode step's, operation for operation
        choice = jnp.stack(
            [_choose(logits[:, t], temps, seeds, counts + t, top_k)
             for t in range(T)], axis=1)                    # (N, T)
        drafts = tokens[:, 1:]                              # (N, K)
        matches = (drafts == choice[:, :draft_k]).astype(jnp.int32)
        n_acc = jnp.sum(jnp.cumprod(matches, axis=1), axis=1)
        bonus = jnp.take_along_axis(choice, n_acc[:, None], axis=1)[:, 0]
        return (n_acc.astype(jnp.int32), bonus.astype(jnp.int32),
                k_pool, v_pool)

    return ds_verify_step
