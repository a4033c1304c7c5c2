"""KV-cache autoregressive generation for the GPT family.

The reference serves generation through the fork's
``PipelineEngine.inference_batch`` (reference runtime/pipe/engine.py:422 —
GPT-NeoX calls it per decoding step, recomputing the whole prefix each
time). The TPU rebuild keeps that API on the pipeline engine and adds the
design the hardware actually wants: a static-shape KV cache updated with
``dynamic_update_slice`` and a ``lax.scan`` over decode steps, so the whole
generate loop is ONE compiled program (no per-token dispatch, no prefix
recompute).

Usage::

    gen = make_generator(cfg)          # cfg: models.gpt.GPTConfig
    out = gen(params, prompt_ids, max_new_tokens=64,
              temperature=1.0, top_k=40, rng=key)   # (B, S+64) tokens

temperature=0 (default) is greedy argmax. The prompt is prefilled in one
pass; decode steps attend to the cache only.
"""

import dataclasses
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .gpt import GPTConfig, decoder_block, layer_norm


def init_cache(cfg: GPTConfig, batch: int, max_len: int):
    """Stacked per-layer KV cache: (L, B, max_len, Hkv, Dh) — GQA/MQA
    models cache only their n_kv_head heads (n_head/n_kv_head x smaller)."""
    shape = (cfg.n_layer, batch, max_len, cfg.kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
    }


def _cached_block(cfg: GPTConfig, x, layer_params, k_cache, v_cache,
                  offset, positions):
    """One decoder layer over S new tokens with a KV cache.

    x: (B, S, D); k/v_cache: (B, max_len, Hkv, Dh) — n_kv_head heads for
    GQA/MQA models; offset: scalar — number of tokens already cached.
    Returns (x_out, k_cache, v_cache). The layer math is gpt.decoder_block;
    only the attention core differs (cache update + absolute-position
    masking)."""
    cdt = cfg.dtype
    Dh = cfg.head_dim
    B_, S = x.shape[0], x.shape[1]

    vec = jnp.ndim(offset) == 1  # per-row offsets (batched speculative)

    def attend(q, k, v):
        if vec:
            # per-row write positions: scatter each row's S new entries at
            # its own offset
            rows = jnp.arange(B_, dtype=jnp.int32)[:, None]
            cols = offset[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
            k_c = k_cache.at[rows, cols].set(k.astype(cdt))
            v_c = v_cache.at[rows, cols].set(v.astype(cdt))
        else:
            k_c = jax.lax.dynamic_update_slice(
                k_cache, k.astype(cdt), (0, offset, 0, 0)
            )
            v_c = jax.lax.dynamic_update_slice(
                v_cache, v.astype(cdt), (0, offset, 0, 0)
            )
        # grouped attention: q heads fold to (Hkv, rep) so the cached K/V
        # are read at their small Hkv width — no materialized repeat (the
        # HBM reads of K/V dominate decode cost)
        Hq = q.shape[2]
        rep = Hq // k_c.shape[2]
        qg = q.reshape(B_, S, k_c.shape[2], rep, Dh)
        scores = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k_c,
                            preferred_element_type=jnp.float32)
        scores = scores / math.sqrt(Dh)
        key_pos = jnp.arange(k_c.shape[1])
        q_pos = (offset[:, None] if vec else offset) + jnp.arange(S)
        valid = key_pos[None, None, :] <= jnp.reshape(
            q_pos, (-1, S))[:, :, None]  # (B|1, S, max_len)
        scores = jnp.where(valid[:, None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(cdt)
        ctx = jnp.einsum("bhrqk,bkhd->bqhrd", probs, v_c)
        ctx = ctx.reshape(B_, S, Hq, Dh)
        return ctx, (k_c, v_c)

    moe_cfg = cfg.moe
    if moe_cfg is not None:
        from .moe import moe_ffn

        # no capacity where tokens are served: a token training would
        # drop over capacity would be a wrong token here
        moe_cfg = dataclasses.replace(moe_cfg, dispatch_impl="dropless")

        def mlp_fn(mlp_in):
            return moe_ffn(layer_params["moe"], mlp_in, moe_cfg)

        x, ((k_cache, v_cache), _) = decoder_block(
            cfg, None, x, layer_params, positions, attend, mlp_fn=mlp_fn
        )
    else:
        x, (k_cache, v_cache) = decoder_block(cfg, None, x, layer_params,
                                              positions, attend)
    return x, k_cache, v_cache


def apply_with_cache(cfg: GPTConfig, params, tokens, cache, offset):
    """Process S tokens given `offset` already-cached ones. Returns
    (logits (B, S, V), updated cache). ``offset`` is a scalar, or an (B,)
    int vector of PER-ROW offsets (batched speculative decoding, where
    rows accept different draft lengths and their caches desynchronize)."""
    cdt = cfg.dtype
    B, S = tokens.shape
    if (not cfg.rotary and isinstance(offset, int)
            and offset + S > cfg.max_seq):
        # (traced offsets are guarded at the generate() boundary instead)
        raise ValueError(
            f"offset ({offset}) + tokens ({S}) exceeds max_seq "
            f"({cfg.max_seq}): the learned-position table cannot extrapolate"
        )
    wte = params["embed"]["wte"].astype(cdt)
    x = jnp.take(wte, tokens, axis=0)
    if jnp.ndim(offset) == 1:
        positions = offset[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    else:
        positions = offset + jnp.arange(S, dtype=jnp.int32)
    if not cfg.rotary:
        x = x + jnp.take(params["embed"]["wpe"], positions, axis=0
                         ).astype(cdt).reshape((-1, S, cfg.d_model))

    def scan_body(carry, xs):
        x = carry
        layer_params, k_c, v_c = xs
        x, k_c, v_c = _cached_block(cfg, x, layer_params, k_c, v_c,
                                    offset, positions)
        return x, (k_c, v_c)

    x, (k_new, v_new) = jax.lax.scan(
        scan_body, x, (params["layers"], cache["k"], cache["v"])
    )
    x = layer_norm(x, params["final_ln"]["scale"], params["final_ln"]["bias"],
                   cfg.layernorm_eps)
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["wte"].astype(cdt).T
    else:
        logits = x @ params["lm_head"].astype(cdt)
    return logits, {"k": k_new, "v": v_new}


def prep_sampling_logits(logits, temperature, top_k):
    """Shared sampling transform: fp32 temperature divide + top-k filter.
    One implementation serves make_generator AND the speculative decoder
    (whose draft/target distributions must be filtered identically)."""
    logits = logits.astype(jnp.float32) / temperature
    if top_k is not None:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -1e30, logits)
    return logits


def _select_next(logits, temperature, top_k, rng):
    """logits (B, V) -> next token (B,). temperature<=0 = greedy."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = prep_sampling_logits(logits, temperature, top_k)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def make_generator(cfg: GPTConfig):
    """Build a jitted generate(params, prompt, max_new_tokens, ...) fn."""

    @partial(jax.jit, static_argnames=("max_new_tokens", "temperature", "top_k"))
    def generate(params, prompt, max_new_tokens: int, temperature: float = 0.0,
                 top_k: Optional[int] = None, rng=None):
        B, S = prompt.shape
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        max_len = S + max_new_tokens
        if not cfg.rotary and max_len > cfg.max_seq:
            raise ValueError(
                f"prompt ({S}) + max_new_tokens ({max_new_tokens}) exceeds "
                f"max_seq ({cfg.max_seq}) — learned position embeddings "
                "cannot extrapolate (the wpe slice would clamp silently)"
            )
        if rng is None:
            rng = jax.random.PRNGKey(0)
        cache = init_cache(cfg, B, max_len)
        logits, cache = apply_with_cache(cfg, params, prompt, cache, 0)
        rng, sub = jax.random.split(rng)
        next_tok = _select_next(logits[:, -1], temperature, top_k, sub)

        def body(carry, _):
            tok, cache, offset, rng = carry
            logits, cache = apply_with_cache(
                cfg, params, tok[:, None], cache, offset
            )
            rng, sub = jax.random.split(rng)
            nxt = _select_next(logits[:, -1], temperature, top_k, sub)
            return (nxt, cache, offset + 1, rng), tok

        (last, _, _, _), toks = jax.lax.scan(
            body, (next_tok, cache, jnp.int32(S), rng), None,
            length=max_new_tokens - 1,
        )
        generated = jnp.concatenate(
            [jnp.swapaxes(toks, 0, 1), last[:, None]], axis=1
        )
        return jnp.concatenate([prompt, generated], axis=1)

    return generate
