"""The layer kinds a serving program can run: ONE record a kind.

``engine.make_decode_step`` and ``engine.make_chunk_step`` build the FRAME
of a program (unpack, embed, the layer loop, write, sample) and know
nothing of what a layer reads, keeps or writes: they look the layer's kind
(``GPTConfig.layer_kinds``) up in ``KINDS``. A record says what a serving
program needs of the kind and nothing else (where its weights are stacked
is the model's to say, ``mixers.STACK_KEY``):

``block(view, core, x, layer_params, layer, rows, at) -> (x, rows, kept)``
    the layer inside a program, ONE shape for every kind. ``layer`` is the
    layer's place among its kind's WEIGHTS, ``at`` its cache layer: what
    the cores index pools and state rows by (``pass * count(kind) +
    layer``: the same number in a stack that is not looped,
    ``mixers.scan_passes``). The math is the
    model's own (``gpt.decoder_block``, the five bodies of
    ``models/mixers.py``, each under its own signature, as the references
    call them); the adapter here hands it the core and untangles what it
    returns. ``rows``: the state rows the layer loop carries, as they came
    in from a kind that keeps none, with this layer's rows written from one
    that does. ``kept``: what the cache's write after the loop takes (the
    new keys and values, with the experts' counts behind them where the
    feed-forward is routed). A prompt chunk carries no rows (``rows`` is
    None: it read the slot's in ``view.carried``), so there the layer's new
    rows are the write's to take and come back in ``kept``.
``decode(view, at, layer_params, rows, ...)``, ``chunk(...)``
    the part of the layer that knows the cache, for a decode step and for
    a prompt chunk: with the first four arguments bound, what the model's
    block calls with the new tokens' q, k and v (a ``mamba_attn`` layer
    has a pair of them, attention's and the scan's). ``view`` is the
    cache's layout as this step sees it (``kv_cache.decode_view``,
    ``chunk_view``): pools, lists, write indexes and the forms the cache's
    choosers picked.
``prefix_reuse``
    whether a cached prefix's pages say all there is to say of the layer
    after it (no state row beside them, no page overwritten behind a
    window).
``counts_experts``
    whether its block hands the routed experts' counts out: ``kept`` is
    then the pair (what the write takes, the counts), which
    ``split_expert_counts`` parts after the loop.

The arrows point one way: ``engine`` -> ``kinds`` -> ``kv_cache`` ->
``models/mixers`` -> ``ops/pallas``. A new kind is a record here, its view
fields and its write in ``kv_cache``, its body in ``models/mixers.py``;
the two program builders do not change.
"""

import dataclasses
from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from ..models import mixers
from ..models.gpt import GPTConfig, decoder_block
from . import kv_cache as kvc


class Kind(NamedTuple):
    block: Callable
    decode: Callable    # a pair of them for a kind of two cores
    chunk: Callable     # (None: the kind has no chunk program)
    prefix_reuse: bool = False
    counts_experts: bool = False


# which of a program's new tokens a core casts to what the cache holds: a
# decode step's q, k, v are (N, 1, ...), a chunk's (1, C, ...)
_STEP, _CHUNK = (slice(None), 0), 0


def _held(view, k, v, at, ring=False):
    """The new keys and values as the pool will hold them."""
    k_pool, v_pool = (view.k_ring, view.v_ring) if ring else (view.k, view.v)
    return k[at].astype(k_pool.dtype), v[at].astype(v_pool.dtype)


# ------------------------------------------------------------------ #
# the blocks: the model's layer under one signature
# ------------------------------------------------------------------ #


def _paged_block(cfg: GPTConfig, x, layer_params, positions, attend):
    """An ``attention`` layer inside a serving program: the layer math is
    gpt.decoder_block's (the block training runs); only the core differs
    (mirrors generation._cached_block): ``attend(q, k, v) -> (ctx, kept)``
    reads the layer's pages, and ``kept`` (the new tokens' keys and
    values) comes back beside the layer's output. Its experts go the
    dropless way here: what training bounds by a capacity would be a wrong
    token served."""
    moe_cfg = cfg.moe
    if moe_cfg is None:
        return decoder_block(cfg, None, x, layer_params, positions, attend)
    from ..models.moe import moe_ffn

    moe_cfg = dataclasses.replace(moe_cfg, dispatch_impl="dropless")

    def mlp_fn(mlp_in):
        return moe_ffn(layer_params["moe"], mlp_in, moe_cfg)

    x, (kv, _) = decoder_block(cfg, None, x, layer_params, positions, attend,
                               mlp_fn=mlp_fn)
    return x, kv


def _keeps_no_rows(body, view, core, x, p, layer, rows, at):
    """A kind with no state row, its layer ``body(cfg, x, p, positions,
    core) -> (x, kept)``."""
    x, kept = body(view.cfg, x, p, view.positions,
                   partial(core, view, at, p, rows))
    return x, rows, kept


def _minicpm4_block(cfg, *rest):
    return mixers.mixed_block(cfg, "minicpm4", *rest)


def _lightning(view, core, x, p, layer, rows, at):
    x, new = mixers.mixed_block(view.cfg, "lightning", x, p, view.positions,
                                partial(core, view, at, p, rows))
    if rows is None:
        return x, rows, new
    return x, jax.lax.dynamic_update_index_in_dim(rows, new, at, 0), ()


def _mamba_attn(view, cores, x, p, layer, rows, at):
    x, (kept, new) = mixers.mamba_attn_block(
        view.cfg, x, p, view.positions,
        *(partial(core, view, at, p, rows) for core in cores))
    return (x, rows, (kept, new)) if rows is None else (x, new, kept)


def _grouped(kind, view, core, x, p, layer, rows, at):
    # the kind's whole stack of feed-forwards and the layer's place in it:
    # routed experts read their weights where they lie
    x, kept = mixers.grouped_attn_block(
        view.cfg, kind, x, p, view.positions,
        partial(core, view, at, p, rows), view.real,
        (view.params[mixers.STACK_KEY[kind]].get("mlp"), layer))
    return x, rows, kept


def _kda(view, core, x, p, layer, rows, at):
    x, (new, counts) = mixers.kda_block(
        view.cfg, x, p, partial(core, view, at, p, rows), view.real,
        (view.params[mixers.STACK_KEY["kda"]].get("mlp"), layer))
    return (x, rows, (new, counts)) if rows is None else (x, new, ((), counts))


# ------------------------------------------------------------------ #
# a decode step's cores: the new token's row cast to what the cache will
# hold, then the cache's read; state rows are written in place in ``rows``
# ------------------------------------------------------------------ #


def _attention_step(view, layer, p, rows, q, k, v):
    k_row, v_row = _held(view, k, v, _STEP)
    ctx = view.attend_rows(view.k, view.v, layer, q, k_row, v_row,
                           view.tables, view.lengths)
    return ctx, (k_row, v_row)


def _minicpm4_step(view, layer, p, rows, q, k, v):
    k_row, v_row = _held(view, k, v, _STEP)
    ctx, pooled = kvc.sparse_decode_attend(
        view.cfg.sparse, view.k, view.v, view.kc, layer, q, k_row, v_row,
        view.tables, view.lengths, view.sparse_at, view.attend_pages)
    return ctx, (k_row, v_row, pooled)


def _lightning_step(view, layer, p, rows, q, k, v):
    o, new = mixers.lightning_step(q[:, 0], k[:, 0], v[:, 0], rows[layer],
                                   view.slopes)
    return o[:, None], jnp.where(view.live, new, rows[layer])


def _every_page_step(view, layer, p, rows, q, k, v):
    """Every page the slot's list names (its whole table: a ``mamba_attn``
    layer's, a ``full_attn`` layer's), the new token's own key beside."""
    k_row, v_row = _held(view, k, v, _STEP)
    ctx = kvc.decode_attend_all(view.k, view.v, layer, q, k_row, v_row,
                                view.pages, view.count, view.attend_slots)
    return ctx, (k_row, v_row)


def _state_space_step(view, layer, p, rows, xbc, dt):
    """The new token's convolution, then every slot's state row through
    the recurrence, in place in the carry."""
    sp_l, tail = p["ssm"], rows["conv"][layer]
    x, Bm, Cm, delta, dA, new_tail = mixers.ssm_step_inputs(
        view.cfg.ssm, sp_l, xbc[:, 0], dt[:, 0], tail)
    ssm_rows, y = view.update_ssm(
        rows["ssm"], layer, jnp.exp(dA), delta[..., None] * x, Bm, Cm,
        view.lengths > 0)
    y = y + sp_l["D"].astype(jnp.float32)[:, None] * x
    conv = jax.lax.dynamic_update_index_in_dim(
        rows["conv"], jnp.where(view.live[..., 0], new_tail, tail), layer, 0)
    return y[:, None], {"conv": conv, "ssm": ssm_rows}


def _two_roles_step(view, layer, p, rows, q, k, v):
    """Summaries first, the window's exact keys after, one count of live
    rows, the new token's own key beside."""
    k_row, v_row = _held(view, k, v, _STEP)
    with jax.named_scope("ds.eva.attn"):
        ctx = kvc.decode_attend_all(
            view.k, view.v, layer, q, k_row, v_row, view.pages, view.count,
            view.attend_slots)
    return ctx, (k_row, v_row)


def _last_keys_step(view, layer, p, rows, q, k, v):
    """The ring: its page that takes the new key in XLA, the others
    through the page-list read."""
    k_row, v_row = _held(view, k, v, _STEP, ring=True)
    ctx = kvc.ring_decode_attend(view.k_ring, view.v_ring, layer, q, k_row,
                                 v_row, view.ring_at, view.attend_ring)
    return ctx, (k_row, v_row)


def _delta_rule_step(view, layer, p, rows, qkv, g, beta):
    """The new token's convolutions, then every slot's state row through
    the rule, in place in the carry."""
    tail = rows["conv"][layer]
    q, k, v, new_tail = mixers.kda_step_inputs(view.cfg.kda, p, qkv[:, 0],
                                               tail)
    with jax.named_scope("ds.kda.rule"):
        kda_rows, o = view.update_kda(rows["kda"], layer, q, k, v, g[:, 0],
                                      beta[:, 0], view.lengths > 0)
    conv = jax.lax.dynamic_update_index_in_dim(
        rows["conv"], jnp.where(view.live[..., 0], new_tail, tail), layer, 0)
    return o[:, None], {"conv": conv, "kda": kda_rows}


# ------------------------------------------------------------------ #
# a prompt chunk's cores: the slot's rows come in ``view.carried``, the
# layer's new ones go out beside its keys
# ------------------------------------------------------------------ #


def _minicpm4_chunk(view, layer, p, rows, q, k, v):
    kk, vv = _held(view, k, v, _CHUNK)
    ctx, pooled = kvc.sparse_chunk_attend(
        view.cfg.sparse, view.k, view.v, view.kc, layer, q[0], kk, vv,
        view.table_row, view.offset, view.attend_pages)
    return ctx[None], (kk, vv, pooled)


def _lightning_chunk(view, layer, p, rows, q, k, v):
    o, new = kvc.lightning_chunk_for(q[0], view.mesh)(
        q[0], k[0], v[0], view.carried[layer], view.slopes, view.n_valid)
    return o[None], new


def _all_past_chunk(view, layer, p, rows, q, k, v):
    kk, vv = _held(view, k, v, _CHUNK)
    ctx = kvc.chunk_attend_all(
        view.k, view.v, layer, q[0], kk, vv, view.table_row, view.offset,
        view.scfg.blocks_per_slot)
    return ctx[None], (kk, vv)


def _state_space_chunk(view, layer, p, rows, xbc, dt):
    y, tail, h = mixers.ssm_chunk(
        view.cfg.ssm, p["ssm"], xbc[0], dt[0], view.carried["conv"][layer],
        view.carried["ssm"][layer], view.n_valid)
    return y[None], {"conv": tail, "ssm": h}


def _two_roles_chunk(view, layer, p, rows, q, k, v):
    kk, vv = _held(view, k, v, _CHUNK)
    with jax.named_scope("ds.eva.attn"):
        ctx = view.attend.listed(view.k, view.v, layer, q[0], kk, vv,
                                 view.past, view.n_seen, view.n_past)
    return ctx[None], (kk, vv)


def _every_key_chunk(view, layer, p, rows, q, k, v):
    kk, vv = _held(view, k, v, _CHUNK)
    ctx = view.attend.past(view.k, view.v, layer, q[0], kk, vv,
                           view.full_row, view.offset)
    return ctx[None], (kk, vv)


def _last_keys_chunk(view, layer, p, rows, q, k, v):
    kk, vv = _held(view, k, v, _CHUNK, ring=True)
    ctx = view.attend.ring(view.cfg.gqa.window, view.k_ring, view.v_ring,
                           layer, q[0], kk, vv, view.ring_pages, view.offset)
    return ctx[None], (kk, vv)


def _delta_rule_chunk(view, layer, p, rows, qkv, g, beta):
    o, tail, S = mixers.kda_chunk(
        view.cfg.kda, p, qkv[0], g[0], beta[0], view.carried["conv"][layer],
        view.carried["kda"][layer], view.n_valid, view.kda_rule)
    return o[None], {"conv": tail, "kda": S}


KINDS = {
    # a stack of attention layers takes its prompts whole or by their
    # suffix (ds_prefill, ds_suffix_prefill): it has no chunk program
    "attention": Kind(partial(_keeps_no_rows, _paged_block), _attention_step,
                      None, prefix_reuse=True),
    "minicpm4": Kind(partial(_keeps_no_rows, _minicpm4_block),
                     _minicpm4_step, _minicpm4_chunk, prefix_reuse=True),
    "lightning": Kind(_lightning, _lightning_step, _lightning_chunk),
    "mamba_attn": Kind(_mamba_attn, (_every_page_step, _state_space_step),
                       (_all_past_chunk, _state_space_chunk)),
    "eva": Kind(partial(_keeps_no_rows, mixers.eva_block), _two_roles_step,
                _two_roles_chunk),
    "full_attn": Kind(partial(_grouped, "full_attn"), _every_page_step,
                      _every_key_chunk, prefix_reuse=True,
                      counts_experts=True),
    "window_attn": Kind(partial(_grouped, "window_attn"), _last_keys_step,
                        _last_keys_chunk, counts_experts=True),
    "kda": Kind(_kda, _delta_rule_step, _delta_rule_chunk,
                counts_experts=True),
}


def counts_experts(cfg: GPTConfig) -> bool:
    """Whether this stack's programs count what their routed experts did
    (``moe.EXPERT_COUNTS``): the stacks whose layers hand the counts out."""
    return bool(cfg.moe_num_experts) and any(
        KINDS[kind].counts_experts for kind in cfg.layer_kinds)


def split_expert_counts(kept):
    """``kept`` by kind as the layer loop stacked it -> (the same with what
    the cache's write takes alone, the routed kinds' counts, in the
    table's order)."""
    routed = [kind for kind in KINDS
              if kind in kept and KINDS[kind].counts_experts]
    return ({**kept, **{kind: kept[kind][0] for kind in routed}},
            [kept[kind][1] for kind in routed])


def sum_expert_counts(by_kind):
    """One program's counts from its layers' (``(layers, 3 or 4)`` a
    kind, ``moe.EXPERT_COUNTS``): experts touched and assignments summed
    over the layers, the largest expert's load the largest of any layer,
    the assignments that left (where counted) summed. -> (3 or 4,)
    int32."""
    n = jnp.concatenate(by_kind)
    return jnp.stack([jnp.sum(n[:, 0]), jnp.sum(n[:, 1]), jnp.max(n[:, 2])]
                     + [jnp.sum(n[:, 3])] * (n.shape[1] > 3))
