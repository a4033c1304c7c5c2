"""The layer kinds beside ``gpt.decoder_block``'s: RMSNorm, a gated SiLU
feed-forward (dense, or experts: ``feed_forward``), muP scalings, and
mixers most of which keep something other than every key and value
between tokens. What a layer keeps has one of six shapes: pages
(``minicpm4``, ``full_attn``), a state row a slot (``lightning``), both
(``mamba_attn``), pages of two roles whose count stops following the
length (``eva``), a ring of pages that holds the last ``window`` keys
(``window_attn``), or a state row and three convolution tails a slot
(``kda``):

``lightning``  decayed linear attention (Lightning Attention): per head a
               state ``S_t = lam S_{t-1} + k_t^T v_t`` (Dh x Dh, float32),
               ``o_t = (q_t / sqrt(Dh)) S_t``; a prompt computes the same
               sum chunkwise, a decode step is the recurrence.
``minicpm4``   InfLLM-v2 block-sparse attention: keys mean-pooled over
               windows, a query scores the pooled keys and attends over
               the tokens of ``topk`` blocks only.
``mamba_attn`` causal attention over every key AND a Mamba-2 state-space
               mixer, side by side on one normed input and summed
               (Falcon-H1): per state-space head a state ``H_t = a_t
               H_{t-1} + dt_t x_t (x) B_t`` (head_dim x d_state, float32),
               ``y_t = H_t C_t + D x_t``, behind a depthwise causal
               convolution whose last ``d_conv - 1`` inputs are state
               too; a prompt computes the recurrence chunkwise (SSD), a
               decode step is the recurrence.
``eva``        softmax attention over the exact keys of the query's own
               aligned window of ``window`` positions and, in the same
               softmax, ONE pooled key and value for every ``chunk``
               positions of the windows before it (EVA, as EvaByte
               applies it): a chunk's pooled key is its keys' sum under
               ``softmax_j(k_j . mu_h / sqrt(Dh))``, its pooled value its
               values' sum under ``softmax_j(k_j . phi_h / sqrt(Dh))``,
               ``mu``, ``phi`` learned, a pair a head; one query a key
               head.
``full_attn``  grouped-query softmax attention over every key, q and k
               normed a head where the model says so, rotary by the
               kind's own constants (``GroupedAttnConfig``, YaRN among
               them).
``window_attn``  the same over the keys ``i - window < j <= i`` alone: the
               window slides with the query, and the layer keeps the last
               ``window`` keys (may share a stack with ``full_attn``).
``kda``        a gated delta rule with a decay a CHANNEL (Kimi Delta
               Attention): per head a state ``S_t = (I - b_t k_t k_t^T)
               Diag(a_t) S_{t-1} + b_t k_t v_t^T`` (dk x dv, float32),
               ``o_t = S_t^T q_t``, q, k and v each behind a depthwise
               causal convolution and a SiLU, q and k of unit length; a
               prompt computes the rule chunkwise, a decode step is the
               recurrence (may share a stack with ``full_attn``).

A model whose ``GPTConfig.mixer_types`` names them keeps its weights
stacked BY KIND (``STACK_KEY``) and is served only; the layer loop of every
program goes run by run (``layer_runs``), once or, in a LOOPED stack
(``GPTConfig.loop_steps``: the same weights ``loop_steps`` times a token,
the final norm after every pass, a cache layer for every (pass, layer)
pair, an exit gate read out), pass by pass (``scan_passes``). ``mixed_block``,
``mamba_attn_block``, ``eva_block``, ``grouped_attn_block`` and
``kda_block`` are the
layers the whole forward, the chunked prefill and the decode step share: a
program hands them the cache-dependent cores alone (``core(q, k, v) ->
(ctx, aux)``; for the state-space branch ``scan(xbc, dt) -> (y, aux)``).
"""

import math
from typing import List, Tuple

import jax
import jax.numpy as jnp

from .gpt import (LAYER_KINDS, GPTConfig, SparseAttnConfig,
                  _xla_causal_attention, expand_kv_heads, layer_norm,
                  rotary_embedding)

NEG = -1e30
# where each kind's stacked weights live in the parameter tree: under its
# own name, but for the two kinds that came first
STACK_KEY = {**{kind: kind for kind in LAYER_KINDS},
             "attention": "layers", "minicpm4": "sparse"}


# ------------------------------------------------------------------ #
# small parts
# ------------------------------------------------------------------ #


def rms_norm(x, scale, eps):
    """x / rms(x) * scale over the last axis, statistics in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def scaled(x, m: float):
    """x times a model's constant multiplier (nothing where it is 1)."""
    return x if m == 1.0 else x * jnp.asarray(m, x.dtype)


def gated_ffn(u, p, cdt, gate_mult: float = 1.0):
    h = jax.nn.silu(scaled(u @ p["w_gate"].astype(cdt), gate_mult)) \
        * (u @ p["w_up"].astype(cdt))
    return h @ p["w_down"].astype(cdt)


def feed_forward(cfg: GPTConfig, m, p, live=None, gate_mult: float = 1.0,
                 layer=None, shared=None):
    """The feed-forward of a mixed layer, as the configuration says: the
    dense gated SiLU one, or (``cfg.moe_num_experts``) gated SiLU experts
    routed ``moe_top_k`` a token with nothing dropped
    (``moe.gated_experts``). m: (B, S, D) normed; p: the layer's ``mlp``
    tree; ``live`` (B, S) bool or None: the tokens that are real (an idle
    lane, a chunk's padding is routed to no expert). With ``layer`` (a
    traced index) ``p`` is the kind's whole STACK of experts and the
    layer's are read where they lie (``moe.gated_experts``); ``shared``
    is then this layer's own shared expert (``cfg.moe_shared``), which a
    call without ``layer`` finds in ``p``. -> (y (B, S, D), the experts'
    counts int32 (``expert_counts_width`` of them), zeros for a dense
    feed-forward)."""
    if not cfg.moe_num_experts:
        return gated_ffn(m, p, cfg.dtype, gate_mult), \
            jnp.zeros((3,), jnp.int32)
    from .moe import gated_experts

    B, S, D = m.shape
    if cfg.moe_shared and layer is None:
        shared = p["shared"]
    y, counts = gated_experts(
        p, m.reshape(B * S, D), cfg.moe_top_k, cfg.moe_normalize_gates,
        None if live is None else live.reshape(B * S), gate_mult, layer,
        cfg.moe_held, shared, cfg.moe_rule)
    return y.reshape(B, S, D), counts


def expert_counts_width(cfg: GPTConfig) -> int:
    """How many of ``moe.EXPERT_COUNTS`` this model's routed layers count:
    the assignments that left only where it holds a share of the experts."""
    return 4 if cfg.moe_held is not None else 3


def lightning_slopes(n_head: int):
    """s_h = 2^(-8 (h+1) / n_head); the decay of head h is exp(-s_h)."""
    return jnp.exp2(-8.0 * jnp.arange(1, n_head + 1, dtype=jnp.float32)
                    / n_head)


def layer_runs(cfg: GPTConfig) -> List[Tuple[str, int, int]]:
    """The stack as runs of one kind: (kind, first index INSIDE the
    kind's stacked weights, count), in layer order."""
    runs, seen = [], {}
    for kind in cfg.layer_kinds:
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1], runs[-1][2] + 1)
        else:
            runs.append((kind, i, 1))
    return runs


def scan_runs(cfg: GPTConfig, params, carry, body):
    """The layer loop of every serving program: one ``lax.scan`` a run.
    ``body(kind, carry, layer_params, index) -> (carry, out)`` with
    ``index`` the layer's place among its kind; returns the carry and, by
    kind, the runs' stacked ``out`` in that kind's order. What is large
    and kept a layer (a state row) belongs in the carry, written in
    place; ``out`` is for the small (a new token's keys)."""
    outs = {}
    for kind, first, count in layer_runs(cfg):
        stack = params[STACK_KEY[kind]]
        ids = first + jnp.arange(count, dtype=jnp.int32)
        if count == jax.tree.leaves(stack)[0].shape[0]:
            # the whole stack: scan over it (the classic model's one scan)
            carry, out = jax.lax.scan(
                lambda c, xs, kind=kind: body(kind, c, *xs), carry,
                (stack, ids))
        else:
            def step(c, i, kind=kind, stack=stack):
                return body(kind, c, jax.tree.map(lambda a: a[i], stack), i)

            carry, out = jax.lax.scan(step, carry, ids)
        outs.setdefault(kind, []).append(out)
    return carry, {m: jax.tree.map(lambda *a: jnp.concatenate(a), *o)
                   for m, o in outs.items()}


def scan_passes(cfg: GPTConfig, params, x, rows, body, served):
    """The layer loop of a serving program over a stack that may be looped:
    ``cfg.loop_steps`` passes of ``scan_runs`` over ONE set of weights, the
    final norm after every pass (its output is the next pass's input and,
    after the last, the head's: ``head_logits(..., normed=True)``).
    ``body(kind, (x, rows), layer_params, layer, at) -> ((x, rows), out)``:
    ``layer`` the layer's place among its kind's WEIGHTS, ``at`` its cache
    layer, ``pass * count(kind) + layer`` (the same number in a stack that
    is not looped). ``served(x)``: the stream at the positions whose token
    is served, for the exit gate. -> (x, rows, the passes' ``out`` by kind
    stacked cache layer by cache layer, the gate ``lam`` (passes, ...)
    float32 or None). One ``lax.scan`` over the passes: a looped program
    is as long to trace and lower as one pass of it."""
    if cfg.loop_steps == 1:
        (x, rows), kept = scan_runs(
            cfg, params, (x, rows),
            lambda kind, carry, p, i: body(kind, carry, p, i, i))
        return x, rows, kept, None

    def one_pass(carry, t):
        carry, kept = scan_runs(
            cfg, params, carry, lambda kind, carry, p, i: body(
                kind, carry, p, i, t * cfg.count(kind) + i))
        x = final_norm(cfg, params, carry[0])
        return (x, carry[1]), (kept, exit_gate(params, served(x)))

    with jax.named_scope("ds.loop"):
        (x, rows), (kept, lam) = jax.lax.scan(
            one_pass, (x, rows), jnp.arange(cfg.loop_steps, dtype=jnp.int32))
    # (passes, layers, ...) -> (cache layers, ...)
    return x, rows, jax.tree.map(
        lambda a: a.reshape((-1,) + a.shape[2:]), kept), lam


def exit_gate(params, h):
    """A looped stack's exit gate on a pass's normed output h (..., D):
    ``sigmoid(h . w + b)``, float32 -> (...)."""
    g = params["exit_gate"]
    return jax.nn.sigmoid(
        jnp.sum(h.astype(jnp.float32) * g["w"].astype(jnp.float32), -1)
        + g["b"].astype(jnp.float32))


def exit_distribution(lam):
    """The passes' gates ``lam`` (T, ...) -> the distribution over the pass
    a token would leave after, (T, ...): ``p_t = lam_t prod_{j<t} (1 -
    lam_j)`` before the last pass, which takes what is left."""
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(lam[:1]), stay[:-1]])
    return jnp.concatenate([(lam * before)[:-1], before[-1:]])


# ------------------------------------------------------------------ #
# weights
# ------------------------------------------------------------------ #


def init_params(rng, cfg: GPTConfig):
    """float32 weights of a mixed stack, stacked by kind; no bias."""
    D, F, V = cfg.d_model, cfg.ffn_dim, cfg.vocab_size
    H, Dh = cfg.n_head, cfg.head_dim
    std, out_std = 0.02, 0.02 / math.sqrt(2.0 * cfg.n_layer)
    keys = iter(jax.random.split(rng, 32))

    def w(shape, s):
        return jax.random.normal(next(keys), shape, jnp.float32) * s

    def mlp(n):
        """The feed-forward ``feed_forward`` reads: dense, or experts
        (an expert axis behind the layers', and the router)."""
        E = (cfg.moe_num_experts,) if cfg.moe_num_experts else ()
        # a program that holds a share of the experts holds their weights
        # alone; the router scores all of them
        Eh = (cfg.moe_held[1],) if cfg.moe_held is not None else E
        p = {"w_gate": w((n, *Eh, D, F), std), "w_up": w((n, *Eh, D, F), std),
             "w_down": w((n, *Eh, F, D), out_std)}
        if E:
            p["router"] = w((n, D, *E), std)
        if E and cfg.moe_rule == "sigmoid_bias":
            p["router_bias"] = w((n, *E), std)
        if E and cfg.moe_shared:
            Fs = cfg.moe_shared * F
            p["shared"] = {"w_gate": w((n, D, Fs), std),
                           "w_up": w((n, D, Fs), std),
                           "w_down": w((n, Fs, D), out_std)}
        return p

    def kind(n, kv_heads, o_norm):
        p = {"ln1": jnp.ones((n, D)), "ln2": jnp.ones((n, D)),
             # one projection: all query heads, then the key heads, then
             # the value heads (as decoder_block's fused qkv)
             "wqkv": w((n, D, (H + 2 * kv_heads) * Dh), std),
             "wg": w((n, D, H * Dh), std), "wo": w((n, H * Dh, D), out_std),
             "q_norm": jnp.ones((n, Dh)), "k_norm": jnp.ones((n, Dh)),
             "mlp": mlp(n)}
        if o_norm:
            p["o_norm"] = jnp.ones((n, H * Dh))
        return p

    params = {"embed": {"wte": w((V, D), std)},
              "final_norm": {"scale": jnp.ones((D,))},
              "lm_head": w((D, cfg.n_pred * V), std)}
    if cfg.count("minicpm4"):
        params["sparse"] = kind(cfg.count("minicpm4"), cfg.kv_heads, False)
    if cfg.count("lightning"):
        params["lightning"] = kind(cfg.count("lightning"), H, True)
    if cfg.count("mamba_attn"):
        n, m = cfg.count("mamba_attn"), cfg.ssm
        params["mamba_attn"] = {
            "ln1": jnp.ones((n, D)), "ln2": jnp.ones((n, D)),
            "wqkv": w((n, D, cfg.qkv_dim), std),
            "wo": w((n, H * Dh, D), out_std),
            "ssm": {"w_in": w((n, D, m.proj_dim), std),
                    # tap j meets the input d_conv - 1 - j positions back
                    "conv_w": w((n, m.d_conv, m.conv_dim), 0.5),
                    "conv_b": jnp.zeros((n, m.conv_dim)),
                    "dt_bias": jnp.full((n, m.n_heads), -4.0),
                    "A_log": jnp.tile(jnp.log(jnp.arange(
                        1.0, m.n_heads + 1.0)), (n, 1)),
                    "D": jnp.ones((n, m.n_heads)),
                    "norm": jnp.ones((n, m.d_ssm)),
                    "w_out": w((n, m.d_ssm, D), out_std)},
            "mlp": mlp(n)}
    if cfg.count("eva"):
        n = cfg.count("eva")
        one = 1.0 - cfg.norm_offset     # the norms scale by 1 as they start
        params["eva"] = {
            "ln1": jnp.full((n, D), one), "ln2": jnp.full((n, D), one),
            "wqkv": w((n, D, 3 * H * Dh), std),
            "wo": w((n, H * Dh, D), out_std),
            # the pooling vectors of a head: a chunk's keys, then its values
            "mu": w((n, H, Dh), 1.0), "phi": w((n, H, Dh), 1.0),
            "mlp": mlp(n)}
        params["final_norm"]["scale"] = jnp.full((D,), one)
    for name in ("full_attn", "window_attn"):
        n = cfg.count(name)
        if n:
            p = {"ln1": jnp.ones((n, D)), "ln2": jnp.ones((n, D)),
                 "wqkv": w((n, D, cfg.qkv_dim), std),
                 "wo": w((n, H * Dh, D), out_std), "mlp": mlp(n)}
            if cfg.gqa.qk_norm:
                p["q_norm"] = jnp.ones((n, Dh))
                p["k_norm"] = jnp.ones((n, Dh))
            if cfg.gqa.out_gate:
                p["wg"] = w((n, D, H * Dh), std)
            if cfg.gqa.sandwich:
                p["ln1_post"] = jnp.ones((n, D))
                p["ln2_post"] = jnp.ones((n, D))
            params[name] = p
    if cfg.count("kda"):
        n, kc = cfg.count("kda"), cfg.kda
        Hk, dk, dv, r = kc.n_heads, kc.head_k, kc.head_v, kc.low_rank
        params["kda"] = {
            "ln1": jnp.ones((n, D)), "ln2": jnp.ones((n, D)),
            # one projection: every head's q, then the k's, then the v's
            "wqkv": w((n, D, kc.conv_dim), std),
            # tap j meets the input d_conv - 1 - j positions back; no bias
            "conv_w": w((n, kc.d_conv, kc.conv_dim), 0.5),
            "wf_down": w((n, D, r), std), "wf_up": w((n, r, Hk * dk), std),
            "f_bias": jnp.zeros((n, Hk * dk)),
            "A_log": jnp.zeros((n, Hk)),
            "w_beta": w((n, D, Hk), std),
            "wg_down": w((n, D, r), std), "wg_up": w((n, r, Hk * dv), std),
            "o_norm": jnp.ones((n, dv)),
            "wo": w((n, Hk * dv, D), out_std), "mlp": mlp(n)}
    if cfg.loop_steps > 1:
        params["exit_gate"] = {"w": w((D,), std), "b": jnp.zeros(())}
    return params


# ------------------------------------------------------------------ #
# the shared layer
# ------------------------------------------------------------------ #


def mixed_block(cfg: GPTConfig, kind: str, x, p, positions, core):
    """A ``minicpm4`` or ``lightning`` layer: x + r Mixer(RMSNorm(x)), then
    x + r FFN(RMSNorm(x)), the FFN gated SiLU. ``core(q, k, v) ->
    (ctx (B, S, H, Dh), aux)`` is the part that knows the cache: q and k
    come normed per head and, for ``lightning``, rotated; q is NOT yet
    scaled."""
    cdt, eps, r = cfg.dtype, cfg.layernorm_eps, cfg.residual_scale
    B, S, _ = x.shape
    H, Dh = cfg.n_head, cfg.head_dim
    Hkv = cfg.kv_heads if kind == "minicpm4" else H
    with jax.named_scope("ds.attn"):
        u = rms_norm(x, p["ln1"], eps)
        qkv = u @ p["wqkv"].astype(cdt)
        q = qkv[..., :H * Dh].reshape(B, S, H, Dh)
        k = qkv[..., H * Dh:(H + Hkv) * Dh].reshape(B, S, Hkv, Dh)
        v = qkv[..., (H + Hkv) * Dh:].reshape(B, S, Hkv, Dh)
        q = rms_norm(q, p["q_norm"], eps)
        k = rms_norm(k, p["k_norm"], eps)
        if kind == "lightning":
            q = rotary_embedding(q, positions, Dh)
            k = rotary_embedding(k, positions, Dh)
        ctx, aux = core(q, k, v)
        ctx = ctx.astype(cdt)
        if kind == "lightning":         # the output norm, head by head
            ctx = rms_norm(ctx, p["o_norm"].reshape(H, Dh), eps)
        gate = jax.nn.sigmoid(u @ p["wg"].astype(cdt))
        y = (ctx.reshape(B, S, H * Dh) * gate) @ p["wo"].astype(cdt)
        x = x + (r * y).astype(cdt)
    with jax.named_scope("ds.mlp"):
        m, _ = feed_forward(cfg, rms_norm(x, p["ln2"], eps), p["mlp"])
        x = x + (r * m).astype(cdt)
    return x, aux


def mamba_attn_block(cfg: GPTConfig, x, p, positions, attend, scan):
    """A ``mamba_attn`` layer: with u = RMSNorm(x), x + SSM(u) + Attn(u),
    then x + FFN(RMSNorm(x)), every branch times its multiplier
    (``cfg.ssm``). The two cores know the cache: ``attend(q, k, v) ->
    (ctx (B, S, H, Dh), aux)`` with q and k rotated, q NOT yet scaled;
    ``scan(xbc (B, S, conv_dim), dt (B, S, n_heads)) -> (y (B, S,
    n_heads, head_dim) float32, aux)`` is the convolution, its SiLU and
    the recurrence, ``D x`` included, over the projection's convolved
    channels (x, B, C, before the convolution, in the compute dtype) and
    its raw step sizes. Returns (x, (attention's aux, the scan's))."""
    cdt, eps, m = cfg.dtype, cfg.layernorm_eps, cfg.ssm
    B, S, _ = x.shape
    H, Hkv, Dh = cfg.n_head, cfg.kv_heads, cfg.head_dim
    u = rms_norm(x, p["ln1"], eps)
    with jax.named_scope("ds.ssm"):
        sp = p["ssm"]
        proj = scaled(u, m.ssm_in) @ sp["w_in"].astype(cdt)
        d, g = m.d_ssm, m.n_groups * m.d_state
        mz, mx, mb, mc, mdt = m.ssm_mult
        z = scaled(proj[..., :d].astype(jnp.float32), mz)
        xbc = jnp.concatenate(
            [scaled(proj[..., d:2 * d], mx),
             scaled(proj[..., 2 * d:2 * d + g], mb),
             scaled(proj[..., 2 * d + g:2 * d + 2 * g], mc)], -1)
        dt = scaled(proj[..., 2 * d + 2 * g:].astype(jnp.float32), mdt)
        y, kept_ssm = scan(xbc, dt)
        # gate, then an RMSNorm over each group's channels
        y = y.reshape(B, S, m.n_groups, d // m.n_groups) \
            * jax.nn.silu(z).reshape(B, S, m.n_groups, d // m.n_groups)
        y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                              + eps)
        y = (y.reshape(B, S, d) * sp["norm"].astype(jnp.float32)).astype(cdt)
        ssm = scaled(y @ sp["w_out"].astype(cdt), m.ssm_out)
    with jax.named_scope("ds.attn"):
        qkv = scaled(u, m.attn_in) @ p["wqkv"].astype(cdt)
        q = qkv[..., :H * Dh].reshape(B, S, H, Dh)
        k = scaled(qkv[..., H * Dh:(H + Hkv) * Dh], m.key).reshape(
            B, S, Hkv, Dh)
        v = qkv[..., (H + Hkv) * Dh:].reshape(B, S, Hkv, Dh)
        q = rotary_embedding(q, positions, Dh, cfg.rope_theta)
        k = rotary_embedding(k, positions, Dh, cfg.rope_theta)
        ctx, kept_attn = attend(q, k, v)
        attn = scaled(ctx.astype(cdt).reshape(B, S, H * Dh)
                      @ p["wo"].astype(cdt), m.attn_out)
    x = x + ssm + attn
    with jax.named_scope("ds.mlp"):
        mlp, _ = feed_forward(cfg, rms_norm(x, p["ln2"], eps), p["mlp"],
                              gate_mult=m.mlp_gate)
        x = x + scaled(mlp, m.mlp_out)
    return x, (kept_attn, kept_ssm)


def eva_summaries(k, v, mu, phi):
    """One pooled key and one pooled value a head for every chunk of keys:
    k, v (..., c, H, Dh), a chunk's c positions; mu, phi (H, Dh). The
    pooled key is the keys' sum under ``softmax_j(k_j . mu_h / sqrt(Dh))``,
    the pooled value the values' under ``softmax_j(k_j . phi_h /
    sqrt(Dh))``, softmax and sums in float32. -> (..., H, Dh) each, in
    k's and v's dtypes."""
    with jax.named_scope("ds.eva.pool"):
        k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)
        scale = 1.0 / math.sqrt(k.shape[-1])

        def weights(vec):
            s = jnp.sum(k32 * vec.astype(jnp.float32), -1) * scale
            return jax.nn.softmax(s, axis=-2)[..., None]    # over the chunk

        return (jnp.sum(weights(mu) * k32, -3).astype(k.dtype),
                jnp.sum(weights(phi) * v32, -3).astype(v.dtype))


def eva_block(cfg: GPTConfig, x, p, positions, attend):
    """An ``eva`` layer: x + Attn(RMSNorm(x)), then x + FFN(RMSNorm(x)),
    the FFN gated SiLU, no bias; the norms scale by ``norm_offset + w``
    and the stream ``x`` is float32 where the model says so
    (``cfg.fp32_stream``). ``attend(q, k, v) -> (ctx (B, S, H, Dh), aux)``
    knows the cache: q and k come rotated, q NOT yet scaled, one query
    head a key head."""
    cdt, eps = cfg.dtype, cfg.layernorm_eps
    B, S, _ = x.shape
    H, Dh = cfg.n_head, cfg.head_dim
    norm = lambda x, w: rms_norm(x, w + cfg.norm_offset, eps).astype(cdt)
    with jax.named_scope("ds.attn"):
        qkv = norm(x, p["ln1"]) @ p["wqkv"].astype(cdt)
        q, k, v = (qkv[..., i * H * Dh:(i + 1) * H * Dh].reshape(B, S, H, Dh)
                   for i in range(3))
        q = rotary_embedding(q, positions, Dh, cfg.rope_theta)
        k = rotary_embedding(k, positions, Dh, cfg.rope_theta)
        ctx, aux = attend(q, k, v)
        y = ctx.astype(cdt).reshape(B, S, H * Dh) @ p["wo"].astype(cdt)
        x = x + y.astype(x.dtype)
    with jax.named_scope("ds.mlp"):
        x = x + feed_forward(cfg, norm(x, p["ln2"]), p["mlp"])[0].astype(
            x.dtype)
    return x, aux


def grouped_attn_block(cfg: GPTConfig, kind: str, x, p, positions, attend,
                       live=None, stacked=None):
    """A ``full_attn`` or ``window_attn`` layer: x + Attn(RMSNorm(x)),
    then x + FFN(RMSNorm(x)) (each sublayer's output through an RMSNorm of
    its own first where ``cfg.gqa.sandwich``), no bias; ``n_head`` query
    heads over
    ``kv_heads`` key heads of ``head_dim`` (whatever ``d_model`` is), q
    and k normed a head where ``cfg.gqa.qk_norm``, then turned by the
    kind's rotary constants; the feed-forward is the configuration's
    (``feed_forward``), ``live`` (B, S) its real tokens. ``attend(q, k, v)
    -> (ctx (B, S, H, Dh), kept)`` knows the cache and the window: q and
    k come rotated, q NOT yet scaled. ``stacked``: (the kind's whole
    stack of ``mlp`` trees, this layer's index in it) where a program
    loops over the stack by a traced index: routed experts then read
    their weights in the stack, where ``p["mlp"]`` would be a copy.
    Returns (x, (kept, the experts' counts))."""
    cdt, eps = cfg.dtype, cfg.layernorm_eps
    B, S, _ = x.shape
    H, Hkv, Dh = cfg.n_head, cfg.kv_heads, cfg.head_dim
    rope = cfg.gqa.rope(kind)
    with jax.named_scope("ds.attn"):
        u = rms_norm(x, p["ln1"], eps)
        qkv = u @ p["wqkv"].astype(cdt)
        q = qkv[..., :H * Dh].reshape(B, S, H, Dh)
        k = qkv[..., H * Dh:(H + Hkv) * Dh].reshape(B, S, Hkv, Dh)
        v = qkv[..., (H + Hkv) * Dh:].reshape(B, S, Hkv, Dh)
        if cfg.gqa.qk_norm:
            q = rms_norm(q, p["q_norm"], eps)
            k = rms_norm(k, p["k_norm"], eps)
        if cfg.gqa.rotary:
            q = rotary_embedding(q, positions, Dh, rope=rope)
            k = rotary_embedding(k, positions, Dh, rope=rope)
        ctx, kept = attend(q, k, v)
        ctx = ctx.astype(cdt).reshape(B, S, H * Dh)
        if cfg.gqa.out_gate:
            ctx = ctx * jax.nn.sigmoid(u @ p["wg"].astype(cdt))
        y = ctx @ p["wo"].astype(cdt)
        if cfg.gqa.sandwich:
            y = rms_norm(y, p["ln1_post"], eps)
        x = x + y
    x, counts = routed_ffn(cfg, x, p, live, stacked)
    return x, (kept, counts)


def routed_ffn(cfg: GPTConfig, x, p, live, stacked):
    """The second half of a ``grouped_attn_block`` or ``kda_block``: x +
    FFN(RMSNorm(x)), the feed-forward the configuration's, its routed
    experts read in the kind's stack where ``stacked`` (that stack of
    ``mlp`` trees, the layer's index) is given. -> (x, the experts'
    counts)."""
    with jax.named_scope("ds.mlp"):
        mlp, layer = (p["mlp"], None) if stacked is None \
            or not cfg.moe_num_experts else stacked
        y, counts = feed_forward(cfg, rms_norm(x, p["ln2"], cfg.layernorm_eps),
                                 mlp, live, layer=layer,
                                 shared=p["mlp"].get("shared"))
        if "ln2_post" in p:     # a sandwich-normed layer
            y = rms_norm(y, p["ln2_post"], cfg.layernorm_eps)
        x = x + y
    return x, counts


def kda_block(cfg: GPTConfig, x, p, scan, live=None, stacked=None):
    """A ``kda`` layer: x + KDA(RMSNorm(x)), then x + FFN(RMSNorm(x)), no
    bias, no rotary (the decay and the convolution carry the position).
    With m the normed input: one projection to every head's q, k and v
    (the convolution's inputs, in the compute dtype); the log-decay of
    every channel ``g = -exp(A_h) softplus(W_up (W_down m) + b)`` and
    ``beta = beta_scale sigmoid(W_beta m)`` a head, float32. ``scan(qkv
    (B, S, conv_dim), g (B, S, H, dk), beta (B, S, H)) -> (o (B, S, H,
    dv) float32, kept)`` knows the cache: the convolution over the tails,
    its SiLU, the unit keys and the rule (``kda_inputs``, then the
    chunkwise form or the recurrence). The output passes an RMSNorm over
    each head's ``dv`` entries (one learned scale for all heads) and a
    low-rank gate before the projection out. ``live``, ``stacked``:
    ``grouped_attn_block``'s. Returns (x, (kept, the experts' counts))."""
    cdt, eps, kc = cfg.dtype, cfg.layernorm_eps, cfg.kda
    B, S, _ = x.shape
    H, dk, dv = kc.n_heads, kc.head_k, kc.head_v
    f32 = jnp.float32
    with jax.named_scope("ds.kda"):
        u = rms_norm(x, p["ln1"], eps)
        with jax.named_scope("ds.kda.proj"):
            qkv = u @ p["wqkv"].astype(cdt)
            f = jnp.dot((u @ p["wf_down"].astype(cdt)),
                        p["wf_up"].astype(cdt), preferred_element_type=f32)
            g = -jnp.exp(p["A_log"].astype(f32))[:, None] * jax.nn.softplus(
                (f + p["f_bias"].astype(f32)).reshape(B, S, H, dk))
            beta = kc.beta_scale * jax.nn.sigmoid(jnp.dot(
                u, p["w_beta"].astype(cdt), preferred_element_type=f32))
        o, kept = scan(qkv, g, beta)
        with jax.named_scope("ds.kda.out"):
            o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                                  + eps) * p["o_norm"].astype(f32)
            gate = jax.nn.sigmoid(jnp.dot(
                u @ p["wg_down"].astype(cdt), p["wg_up"].astype(cdt),
                preferred_element_type=f32))
            y = (o.reshape(B, S, H * dv) * gate).astype(cdt)
            x = x + y @ p["wo"].astype(cdt)
    x, counts = routed_ffn(cfg, x, p, live, stacked)
    return x, (kept, counts)


def embed_tokens(cfg: GPTConfig, params, tokens, positions=None):
    """Where the residual stream starts, for either parameter tree: the
    table's rows of ``tokens`` (any shape), times ``scale_emb`` where the
    model scales them, plus the learned rows of ``positions`` (tokens'
    shape) where the model has such a table."""
    emb = params["embed"]
    x = jnp.take(emb["wte"].astype(cfg.dtype), tokens, axis=0)
    if cfg.scale_emb != 1.0:
        x = x * jnp.asarray(cfg.scale_emb, cfg.dtype)
    if "wpe" in emb:
        x = x + jnp.take(emb["wpe"], positions, axis=0).astype(cfg.dtype)
    return x.astype(jnp.float32) if cfg.fp32_stream else x


def final_norm(cfg: GPTConfig, params, x):
    """The norm a stack ends in, for either parameter tree: a stack of
    attention layers' LayerNorm (``final_ln``), a mixed one's RMSNorm
    (``final_norm``)."""
    if "final_ln" in params:
        return layer_norm(x, params["final_ln"]["scale"],
                          params["final_ln"]["bias"], cfg.layernorm_eps)
    scale = params["final_norm"]["scale"]
    if cfg.norm_offset:
        scale = scale + cfg.norm_offset
    return rms_norm(x, scale, cfg.layernorm_eps)


def head_logits(cfg: GPTConfig, params, x, normed: bool = False):
    """The final norm (``final_norm``; not where ``normed``: a looped
    stack's last pass ended in it) and the head. ``cfg.n_pred *
    vocab_size`` columns, block p scoring the token p + 1 positions on
    (``served_logits`` takes block 0); float32 where the model keeps its
    stream so."""
    if not normed:
        x = final_norm(cfg, params, x)
    if cfg.fp32_stream or cfg.fp32_logits:
        def dot(a, b):
            return jnp.dot(a.astype(cfg.dtype), b,
                           preferred_element_type=jnp.float32)
    else:
        dot = jnp.matmul
    if cfg.tie_embeddings:
        logits = dot(x, params["embed"]["wte"].astype(cfg.dtype).T)
    else:
        logits = dot(x, params["lm_head"].astype(cfg.dtype))
    if cfg.logit_scale != 1.0:
        logits = logits * jnp.asarray(cfg.logit_scale, cfg.dtype)
    return logits


def served_logits(cfg: GPTConfig, logits):
    """Of ``head_logits``' columns, the block a served token is picked
    from: the next position's (all of them where the head has one)."""
    return logits if cfg.n_pred == 1 else logits[..., :cfg.vocab_size]


# ------------------------------------------------------------------ #
# lightning: the chunkwise form and the recurrence
# ------------------------------------------------------------------ #

LIGHTNING_BLOCK = 256


def lightning_chunk_xla(q, k, v, s_in, slopes, n_valid, block=None):
    """The chunkwise form in plain XLA, and the oracle of
    ops/pallas/lightning_chunk. q, k, v: (C, H, Dh); s_in: (H, Dh, Dh)
    float32, the state BEFORE position 0; ``n_valid`` (traced) of the C
    positions are real: the rest neither decay the state nor add to it.
    Returns (o (C, H, Dh) float32, the state after position n_valid-1)."""
    C, H, Dh = q.shape
    B = min(block or LIGHTNING_BLOCK, C)
    assert C % B == 0, (C, B)
    scale = 1.0 / math.sqrt(Dh)
    s = slopes.astype(jnp.float32)[:, None, None]          # (H, 1, 1)
    pos = jnp.arange(B, dtype=jnp.int32)

    def blk(S, xs):
        qb, kb, vb, start = xs                              # (B, H, Dh)
        nvl = jnp.clip(n_valid - start, 0, B)
        cnt = jnp.minimum(pos + 1, nvl).astype(jnp.float32)  # decays so far
        live = pos < nvl
        qh, kh, vh = (jnp.swapaxes(t, 0, 1) for t in (qb, kb, vb))
        a = jnp.einsum("hid,hjd->hij", qh, kh,
                       preferred_element_type=jnp.float32) * scale
        keep = (pos[None, :] <= pos[:, None]) & live[None, :]
        m = jnp.where(keep, jnp.exp(-s * (cnt[:, None] - cnt[None, :])), 0.0)
        v32 = vh.astype(jnp.float32)
        o = jnp.einsum("hij,hjd->hid", a * m, v32, precision="highest")
        o = o + jnp.exp(-s * cnt[None, :, None]) * jnp.einsum(
            "hid,hde->hie", qh.astype(jnp.float32) * scale, S,
            precision="highest")
        kd = jnp.where(live[None, :, None], kh.astype(jnp.float32)
                       * jnp.exp(-s * (nvl - cnt)[None, :, None]), 0.0)
        S = jnp.exp(-s * nvl) * S + jnp.einsum("hjd,hje->hde", kd, v32,
                                               precision="highest")
        return S, jnp.swapaxes(o, 0, 1)

    split = lambda t: t.reshape(C // B, B, H, Dh)
    S, o = jax.lax.scan(blk, s_in.astype(jnp.float32),
                        (split(q), split(k), split(v),
                         jnp.arange(0, C, B, dtype=jnp.int32)))
    return o.reshape(C, H, Dh), S


def lightning_step(q, k, v, S, slopes):
    """The recurrence for one token a row. q, k, v: (N, H, Dh); S: (N, H,
    Dh, Dh) float32. Returns (o (N, H, Dh) float32, the new state)."""
    lam = jnp.exp(-slopes.astype(jnp.float32))[None, :, None, None]
    S = lam * S + (k.astype(jnp.float32)[..., :, None]
                   * v.astype(jnp.float32)[..., None, :])
    o = jnp.einsum("nhd,nhde->nhe",
                   q.astype(jnp.float32) / math.sqrt(q.shape[-1]), S,
                   precision="highest")
    return o, S


# ------------------------------------------------------------------ #
# mamba_attn: the convolution, the chunkwise form and the recurrence
# ------------------------------------------------------------------ #


def ssm_inputs(m, sp, conv, dt, valid=None):
    """What the recurrence takes, in float32, from the convolution's
    output ``conv`` (..., conv_dim) (bias and SiLU here) and the raw step
    sizes ``dt`` (..., n_heads): x (..., n_heads, head_dim), B, C (...,
    n_groups, d_state), delta = softplus(dt + dt_bias) and delta * A
    (..., n_heads), A = -exp(A_log). Where ``valid`` (...) is False delta
    is 0: such a position neither decays the state nor adds to it."""
    c = jax.nn.silu(conv + sp["conv_b"].astype(jnp.float32))
    lead, d, g = c.shape[:-1], m.d_ssm, m.n_groups * m.d_state
    x = c[..., :d].reshape(*lead, m.n_heads, m.head_dim)
    Bm = c[..., d:d + g].reshape(*lead, m.n_groups, m.d_state)
    Cm = c[..., d + g:].reshape(*lead, m.n_groups, m.d_state)
    delta = jax.nn.softplus(dt.astype(jnp.float32)
                            + sp["dt_bias"].astype(jnp.float32))
    if valid is not None:
        delta = jnp.where(valid[..., None], delta, 0.0)
    dA = -delta * jnp.exp(sp["A_log"].astype(jnp.float32))
    return x, Bm, Cm, delta, dA


def ssd_chunk_xla(x, Bm, Cm, delta, dA, h_in, block: int):
    """The recurrence ``H_t = exp(dA_t) H_{t-1} + delta_t x_t (x) B_t``,
    ``y_t = H_t C_t`` chunkwise (SSD) in plain XLA: inside a block of
    ``block`` positions a masked matrix of decays times C B^T, across
    blocks the carried state. x: (T, Hs, P); Bm, Cm: (T, G, N); delta,
    dA: (T, Hs); h_in: (Hs, P, N) float32, the state BEFORE position 0.
    All float32. Returns (y (T, Hs, P), the state after position T-1);
    a position whose delta is 0 leaves the state as it was."""
    T, Hs, P = x.shape
    G, N = Bm.shape[1:]
    R = Hs // G
    Q = min(block, T)
    pad = -T % Q
    if pad:     # delta 0: the state passes through
        x, Bm, Cm, delta, dA = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                                for a in (x, Bm, Cm, delta, dA))
    causal = jnp.tril(jnp.ones((Q, Q), bool))

    def blk(h, xs):
        xb, Bb, Cb, db, ab = xs
        cs = jnp.cumsum(ab, 0)                              # (Q, Hs)
        dx = (db[..., None] * xb).reshape(Q, G, R, P)
        cb = jnp.einsum("tgn,sgn->gts", Cb, Bb, precision="highest")
        seg = cs.T[:, :, None] - cs.T[:, None, :]           # (Hs, t, s)
        decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
        mat = decay.reshape(G, R, Q, Q) * cb[:, None]
        y = jnp.einsum("grts,sgrp->tgrp", mat, dx, precision="highest")
        hg = h.reshape(G, R, P, N)
        y = y + jnp.exp(cs).reshape(Q, G, R, 1) * jnp.einsum(
            "tgn,grpn->tgrp", Cb, hg, precision="highest")
        rest = jnp.exp(cs[-1][None] - cs).reshape(Q, G, R, 1)
        hg = jnp.exp(cs[-1]).reshape(G, R, 1, 1) * hg + jnp.einsum(
            "sgrp,sgn->grpn", dx * rest, Bb, precision="highest")
        return hg.reshape(Hs, P, N), y.reshape(Q, Hs, P)

    split = lambda a: a.reshape((T + pad) // Q, Q, *a.shape[1:])
    h, y = jax.lax.scan(blk, h_in.astype(jnp.float32),
                        tuple(split(a) for a in (x, Bm, Cm, delta, dA)))
    return y.reshape(T + pad, Hs, P)[:T], h


def ssm_chunk(m, sp, xbc, dt, tail, h, n_valid):
    """The state-space branch between its projections, for T positions of
    one sequence whose first ``n_valid`` (traced) are real. xbc: (T,
    conv_dim), the convolution's inputs; dt: (T, n_heads) raw; tail:
    (d_conv - 1, conv_dim), the inputs just before position 0; h:
    (n_heads, head_dim, d_state) float32, the state before it. Returns (y
    (T, n_heads, head_dim) float32 with ``D x`` in it, the new tail: the
    last d_conv - 1 inputs before ``n_valid``, reaching into the old tail
    where n_valid is smaller, and the state after position n_valid - 1)."""
    T, K = xbc.shape[0], m.d_conv
    ext = jnp.concatenate([tail.astype(xbc.dtype), xbc], 0)  # (T + K-1, ch)
    w = sp["conv_w"].astype(jnp.float32)
    conv = sum(w[j] * ext[j:j + T].astype(jnp.float32) for j in range(K))
    x, Bm, Cm, delta, dA = ssm_inputs(
        m, sp, conv, dt, jnp.arange(T, dtype=jnp.int32) < n_valid)
    y, h = ssd_chunk_xla(x, Bm, Cm, delta, dA, h, m.chunk)
    y = y + sp["D"].astype(jnp.float32)[:, None] * x
    return y, jax.lax.dynamic_slice_in_dim(ext, n_valid, K - 1, 0), h


def ssm_step_inputs(m, sp, xbc, dt, tail):
    """One token a row up to the recurrence: the convolution over the
    tail and the new input. xbc: (N, conv_dim); dt: (N, n_heads); tail:
    (N, d_conv - 1, conv_dim). Returns ``ssm_inputs``' five and the new
    tail."""
    ext = jnp.concatenate([tail.astype(xbc.dtype), xbc[:, None]], 1)
    conv = jnp.einsum("nkc,kc->nc", ext.astype(jnp.float32),
                      sp["conv_w"].astype(jnp.float32), precision="highest")
    return (*ssm_inputs(m, sp, conv, dt), ext[:, 1:])


def ssm_rows_xla(rows, layer, decay, dx, Bm, Cm, live):
    """The recurrence for one token a slot on the STACKED state rows, in
    plain XLA, and the oracle of ops/pallas/ssm_row_update. rows: (L, N,
    Hs, P, Nst) float32, the layers' rows of every slot; ``layer``
    traced. decay: (N, Hs), a = exp(delta A); dx: (N, Hs, P), delta x; Bm,
    Cm: (N, G, Nst); live: (N,) bool. ``H <- a H + dx (x) B``, ``y = H
    C``; a slot that is not live keeps its row (its y means nothing).
    Returns (rows with the layer's rows written in place, y (N, Hs, P))."""
    R = rows.shape[2] // Bm.shape[1]
    heads = lambda a: jnp.repeat(a, R, axis=1)[:, :, None, :]  # (N, Hs, 1, Nst)
    h = rows[layer]
    new = decay[..., None, None] * h + dx[..., None] * heads(Bm)
    # a multiply and a sum, not a dot: one pass with the update above
    y = jnp.sum(new * heads(Cm), -1)
    rows = jax.lax.dynamic_update_index_in_dim(
        rows, jnp.where(live[:, None, None, None], new, h), layer, 0)
    return rows, y


def ssm_step(m, sp, xbc, dt, tail, h):
    """``ssm_chunk`` for one token a row: the recurrence itself. xbc: (N,
    conv_dim); dt: (N, n_heads); tail: (N, d_conv - 1, conv_dim); h: (N,
    n_heads, head_dim, d_state) float32. Returns (y (N, n_heads,
    head_dim) float32, the new tail, the new state)."""
    x, Bm, Cm, delta, dA, tail = ssm_step_inputs(m, sp, xbc, dt, tail)
    rows, y = ssm_rows_xla(h[None], 0, jnp.exp(dA), delta[..., None] * x, Bm,
                           Cm, jnp.ones(h.shape[:1], bool))
    return y + sp["D"].astype(jnp.float32)[:, None] * x, tail, rows[0]


# ------------------------------------------------------------------ #
# kda: the convolution, the chunkwise delta rule and the recurrence
# ------------------------------------------------------------------ #


def kda_inputs(kc, conv):
    """What the rule takes, float32, from the convolution's output
    ``conv`` (..., conv_dim): its SiLU, split into every head's q, k (...,
    H, dk) and v (..., H, dv); q and k of unit length a head, q over
    ``sqrt(dk)`` besides."""
    c = jax.nn.silu(conv.astype(jnp.float32))
    lead, H, dk, dv = c.shape[:-1], kc.n_heads, kc.head_k, kc.head_v
    q = c[..., :H * dk].reshape(*lead, H, dk)
    k = c[..., H * dk:2 * H * dk].reshape(*lead, H, dk)
    v = c[..., 2 * H * dk:].reshape(*lead, H, dv)
    unit = lambda a: a * jax.lax.rsqrt(
        jnp.sum(jnp.square(a), -1, keepdims=True) + 1e-6)
    return unit(q) * (1.0 / math.sqrt(dk)), unit(k), v


def kda_recurrence(q, k, v, g, beta, s_in):
    """The rule by its definition, a token at a time, and the oracle of
    the chunkwise forms. q, k, g: (T, H, dk); v: (T, H, dv); beta: (T,
    H); s_in: (H, dk, dv) float32. ``S <- (I - b k k^T) Diag(exp g) S + b
    k v^T``, ``o = S^T q``. -> (o (T, H, dv), the state after)."""
    def step(S, xs):
        qt, kt, vt, gt, bt = xs
        S = jnp.exp(gt)[..., None] * S
        pred = jnp.einsum("hk,hkv->hv", kt, S, precision="highest")
        S = S + kt[..., None] * (bt[:, None] * (vt - pred))[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", qt, S, precision="highest")

    f32 = lambda a: a.astype(jnp.float32)
    S, o = jax.lax.scan(step, f32(s_in), tuple(map(f32, (q, k, v, g, beta))))
    return o, S


def kda_chunk_xla(q, k, v, g, beta, s_in):
    """The chunkwise rule in plain XLA: ``ops/pallas/kda_chunk.
    block_rule`` a block and a head at a time (the heads side by side, the
    blocks in order). Shapes as ``kda_recurrence``, T any length: padding
    comes with g = 0 and beta = 0, which leaves the state as it was. ->
    (o (T, H, dv) float32, the state after position T - 1)."""
    from ..ops.pallas.kda_chunk import BLOCK, block_rule

    T = q.shape[0]
    pad = -T % BLOCK
    f32 = lambda a: jnp.pad(a.astype(jnp.float32),
                            ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    q, k, v, g, beta = map(f32, (q, k, v, g, beta))
    b = beta[..., None]
    roll = lambda x, d: jnp.roll(x, d, 0)
    dot = lambda a, c, dims: jax.lax.dot_general(
        a, c, (dims, ((), ())), precision="highest",
        preferred_element_type=jnp.float32)
    rule = jax.vmap(lambda *a: block_rule(*a, roll, dot), in_axes=1,
                    out_axes=(1, 0))

    def blk(St, xs):
        # the heads on axis 1 of every (B, H, .) operand, 0 of the state
        o, St = rule(*xs, jnp.moveaxis(St, 0, 1))
        return St, o

    split = lambda a: a.reshape((T + pad) // BLOCK, BLOCK, *a.shape[1:])
    St, o = jax.lax.scan(
        blk, jnp.swapaxes(s_in.astype(jnp.float32), 1, 2),
        tuple(map(split, (q, k, k * b, v * b, g))))
    return o.reshape(T + pad, *o.shape[2:])[:T], jnp.swapaxes(St, 1, 2)


def kda_chunk(kc, p, qkv, g, beta, tail, S, n_valid, rule=kda_chunk_xla):
    """The mixer between its projections, for T positions of one sequence
    whose first ``n_valid`` (traced) are real. qkv: (T, conv_dim), the
    convolution's inputs; g: (T, H, dk); beta: (T, H); tail: (d_conv - 1,
    conv_dim), the inputs just before position 0; S: (H, dk, dv) float32,
    the state before it. ``rule``: the chunkwise form (``kda_chunk_xla``'s
    signature). Returns (o (T, H, dv) float32, the new tail: the last
    d_conv - 1 inputs before ``n_valid``, and the state after position
    n_valid - 1)."""
    T, K = qkv.shape[0], kc.d_conv
    with jax.named_scope("ds.kda.conv"):
        ext = jnp.concatenate([tail.astype(qkv.dtype), qkv], 0)
        w = p["conv_w"].astype(jnp.float32)
        conv = sum(w[j] * ext[j:j + T].astype(jnp.float32) for j in range(K))
        q, k, v = kda_inputs(kc, conv)
        real = jnp.arange(T, dtype=jnp.int32) < n_valid
        g = jnp.where(real[:, None, None], g, 0.0)
        beta = jnp.where(real[:, None], beta, 0.0)
    with jax.named_scope("ds.kda.rule"):
        o, S = rule(q, k, v, g, beta, S)
    return o, jax.lax.dynamic_slice_in_dim(ext, n_valid, K - 1, 0), S


def kda_step_inputs(kc, p, qkv, tail):
    """One token a row up to the recurrence: the convolution over the
    tail and the new input. qkv: (N, conv_dim); tail: (N, d_conv - 1,
    conv_dim). Returns ``kda_inputs``' three and the new tail."""
    ext = jnp.concatenate([tail.astype(qkv.dtype), qkv[:, None]], 1)
    conv = jnp.sum(ext.astype(jnp.float32)
                   * p["conv_w"].astype(jnp.float32), 1)
    return (*kda_inputs(kc, conv), ext[:, 1:])


def kda_rows_xla(rows, layer, q, k, v, g, beta, live):
    """The recurrence for one token a slot on the STACKED state rows, in
    plain XLA, and the oracle of ops/pallas/kda_row_update. rows: (L, N,
    H, dk, dv) float32, the layers' rows of every slot; ``layer`` traced.
    q, k, g: (N, H, dk); v: (N, H, dv); beta: (N, H); live: (N,) bool: a
    slot that is not live keeps its row (its o means nothing). Returns
    (rows with the layer's rows written in place, o (N, H, dv))."""
    S = rows[layer]
    Sd = jnp.exp(g)[..., None] * S
    pred = jnp.sum(k[..., None] * Sd, -2)                   # (N, H, dv)
    new = Sd + k[..., None] * (beta[..., None] * (v - pred))[..., None, :]
    o = jnp.sum(q[..., None] * new, -2)
    rows = jax.lax.dynamic_update_index_in_dim(
        rows, jnp.where(live[:, None, None, None], new, S), layer, 0)
    return rows, o


# ------------------------------------------------------------------ #
# minicpm4: pooled keys, block scores, the selection
# ------------------------------------------------------------------ #


def pool_windows(k, sp: SparseAttnConfig):
    """Means of k over the windows [i st, i st + ks) that lie wholly
    inside its T tokens (T a multiple of the stride, the first token on a
    stride boundary). k: (T, Hkv, Dh) -> (T / st - 1, Hkv, Dh), summed in
    float32, in k's dtype."""
    T = k.shape[0]
    st = sp.kernel_stride
    half = jnp.sum(k.astype(jnp.float32).reshape(T // st, st, *k.shape[1:]), 1)
    return ((half[:-1] + half[1:]) / sp.kernel_size).astype(k.dtype)


def block_scores(q, kbar, visible, sp: SparseAttnConfig):
    """The score of every block for every query row and key head.
    q: (R, H, Dh); kbar: (R, Hkv, J, Dh), or (Hkv, J, Dh) shared by the
    rows, pooled key j the window that starts at token j * stride;
    visible: (R, J) bool, the windows
    wholly inside the row's causal past. Each head's softmax over the
    visible windows, summed over a group's heads (float32), max-pooled
    over the windows that touch a block. -> (R, Hkv, J / windows a
    block) float32; a block with no visible window scores -1."""
    R, H, Dh = q.shape
    Hkv, J = kbar.shape[-3], kbar.shape[-2]
    w = sp.windows_per_block
    qg = q.reshape(R, Hkv, H // Hkv, Dh)
    s = jnp.einsum("rhgd,rhjd->rhgj" if kbar.ndim == 4 else "rhgd,hjd->rhgj",
                   qg, kbar,
                   preferred_element_type=jnp.float32) / math.sqrt(Dh)
    vis = visible[:, None, None, :]
    p = jax.nn.softmax(jnp.where(vis, s, NEG), axis=-1)
    a = jnp.where(visible[:, None, :], jnp.sum(jnp.where(vis, p, 0.0), 2),
                  -1.0)                                     # (R, Hkv, J)
    # block m is touched by the windows m w - 1 .. m w + w - 1
    own = jnp.max(a.reshape(R, Hkv, J // w, w), -1)
    before = jnp.concatenate(
        [jnp.full((R, Hkv, 1), -1.0), a[..., w - 1::w][..., :-1]], -1)
    return jnp.maximum(own, before)


def top_mask(score, k: int):
    """The ``k`` largest of every row of ``score`` (..., M) float32 as a
    mask, equal scores to the lower index: the set a stable descending
    sort puts first, with no sort. The k-th largest value of a row is
    found bit by bit over the order-preserving integer image of a float
    (32 steps, each a compare and a row count), the ties at it taken by a
    prefix count."""
    M = score.shape[-1]
    if k >= M:
        return jnp.ones(score.shape, bool)
    lead = score.shape[:-1]
    # one row a (query, key head): two key heads alone would leave three
    # quarters of every (8, 128) tile empty through the 32 steps
    score = score.reshape(-1, M)
    score = jnp.where(score == 0, 0.0, score)               # -0.0 is 0.0
    bits = jax.lax.bitcast_convert_type(score, jnp.uint32)
    # negative floats order backwards and below every positive one
    u = jnp.where(score < 0, ~bits, bits | jnp.uint32(1 << 31))

    def step(i, t):
        cand = t | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        enough = jnp.sum(u >= cand[:, None], -1, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, t)

    # the largest t that k entries reach: the k-th largest value
    t = jax.lax.fori_loop(0, 32, step, jnp.zeros(u.shape[:1], jnp.uint32))
    above, tie = u > t[:, None], u == t[:, None]
    room = k - jnp.sum(above, -1, dtype=jnp.int32)
    mask = above | (tie & (jnp.cumsum(tie, -1, dtype=jnp.int32)
                           <= room[:, None]))
    return mask.reshape(*lead, M)


def selection_mask(b, q_block, sp: SparseAttnConfig):
    """The blocks a query attends to under the sparse rule, as a mask. b:
    (R, Hkv, M) block scores; q_block: (R,) the block of the query's own
    position. The first ``init_blocks`` and the ``local_blocks`` that end
    at the query's own are forced in; the best-scored of its past fill
    the rest of ``topk``, a block counting only if its score is >= 0 (it
    has a visible window), equal scores to the lower index. -> (R, Hkv,
    M) bool."""
    m = jnp.arange(b.shape[-1], dtype=jnp.int32)[None, None, :]
    bt = q_block[:, None, None]
    forced = (m < sp.init_blocks) | ((m <= bt) & (m > bt - sp.local_blocks))
    score = jnp.where(forced, 1e9, jnp.where(m <= bt, b, -1e9))
    return top_mask(score, sp.topk) & (score >= 0.0)


def compact(mask, width: int):
    """A mask over blocks (..., M) as a list: entry j is the j-th set
    block in ASCENDING order (the blocks before it are those whose prefix
    count is <= j), an entry past the count reads M. -> (blocks (...,
    width) int32, n (...) how many count)."""
    cum = jnp.cumsum(mask, -1, dtype=jnp.int32)
    j = jnp.arange(width, dtype=jnp.int32)[:, None]
    blocks = jnp.sum(cum[..., None, :] <= j, -1, dtype=jnp.int32)
    return blocks, cum[..., -1]


def select_blocks(b, q_block, sp: SparseAttnConfig):
    """``selection_mask`` as a list ``topk`` wide, in ascending block
    order, the entries that count first. Every selected block lies at or
    before the query's own, so its own (partly filled) block is the last
    that counts, and a rule that keeps the blocks below a bound
    (``page_list``) keeps a prefix of the list. The kernel reads a row's
    pages in list order into one online softmax: the order moves the
    rounding of its sums and nothing else. -> (blocks (R, Hkv, topk)
    int32, valid (R, Hkv, topk))."""
    blocks, n = compact(selection_mask(b, q_block, sp), sp.topk)
    return blocks, jnp.arange(sp.topk, dtype=jnp.int32) < n[..., None]


def forced_mask(M: int, q_block, sp: SparseAttnConfig):
    """The blocks ``selection_mask`` takes whatever they score: the first
    ``init_blocks`` and the ``local_blocks`` that end at the query's own.
    q_block: (R,) -> (R, 1, M) bool."""
    m = jnp.arange(M, dtype=jnp.int32)[None, None, :]
    bt = q_block[:, None, None]
    return (m < sp.init_blocks) | ((m <= bt) & (m > bt - sp.local_blocks))


def forced_count(q_block: int, sp: SparseAttnConfig) -> int:
    """How many blocks ``forced_mask`` sets for a query in block
    ``q_block``: an initial block that is also local counts once."""
    return len(set(range(sp.init_blocks)) | set(
        range(max(q_block - sp.local_blocks + 1, 0), q_block + 1)))


def chosen_width(sp: SparseAttnConfig) -> int:
    """The most blocks a query beyond ``dense_len`` can select that are
    not forced: ``topk`` less the fewest forced blocks such a query has
    (its block is ``dense_len / block_size`` or later)."""
    return sp.topk - forced_count(sp.dense_len // sp.block_size, sp)


def select_chosen(b, q_block, sp: SparseAttnConfig, width: int):
    """``selection_mask`` less the forced blocks, as a list ``width`` wide
    (``chosen_width`` or more) in ascending order, the entries that count
    first: what of a query's selection differs from its neighbours'. Every
    one is a whole block, ``local_blocks`` or more before the query's own.
    -> (blocks (R, Hkv, width) int32, n (R, Hkv) how many count)."""
    return compact(selection_mask(b, q_block, sp)
                   & ~forced_mask(b.shape[-1], q_block, sp), width)


def forced_past(before, q_block, sp: SparseAttnConfig):
    """The forced blocks of a prompt chunk's queries that lie before the
    chunk, which starts at block ``before`` (traced) and holds at most
    ``local_blocks`` blocks: the initial blocks and the ``local_blocks -
    1`` that end at ``before - 1``, one run shared by every query. A
    local block ``b`` counts for a query in block ``bt`` iff ``bt -
    local_blocks < b``, an initial block if it lies before the chunk, a
    block that is both as an initial one. q_block: (R,) -> (blocks (F,)
    int32, negative where the run starts before the sequence; sees (R, F)
    bool)."""
    init = jnp.arange(sp.init_blocks, dtype=jnp.int32)
    local = before - (sp.local_blocks - 1) \
        + jnp.arange(sp.local_blocks - 1, dtype=jnp.int32)
    bt = q_block[:, None]
    sees = jnp.concatenate([
        jnp.broadcast_to(init < before, (len(q_block), sp.init_blocks)),
        (local >= sp.init_blocks) & (local > bt - sp.local_blocks)], 1)
    return jnp.concatenate([init, local]), sees


def visible_windows(q_pos, J: int, sp: SparseAttnConfig):
    """(R, J): window j (tokens j st .. j st + ks - 1) lies wholly at or
    before the query's position."""
    j = jnp.arange(J, dtype=jnp.int32)[None, :]
    return j * sp.kernel_stride + sp.kernel_size - 1 <= q_pos[:, None]


def page_list(blocks, valid, q_pos, sp: SparseAttnConfig, width: int,
              before=None):
    """A query's list of blocks, ``width`` wide, under the one causal
    rule: the selection if it sees more than ``dense_len`` tokens, else
    every block up to its own; of either, the blocks below ``before``
    only (a prompt chunk reads the pages before it and attends to its
    own keys densely). Ascending, so the entries that count come first,
    the query's own (partly filled) block last of them, and each rule
    keeps a prefix: nothing is moved. An entry past the count may name a
    block past the slot's last (``kv_cache.pages_of`` reads the null page
    there: a page of the pool is all the kernel asks of such an entry).
    blocks, valid: (R, Hkv, topk) from ``select_blocks``; q_pos: (R,). ->
    (blocks (R, Hkv, width) int32, n (R, Hkv) how many count)."""
    pad = ((0, 0), (0, 0), (0, width - blocks.shape[-1]))
    m = jnp.arange(width, dtype=jnp.int32)[None, None, :]
    bt = (q_pos // sp.block_size)[:, None, None]
    dense = (q_pos + 1 <= sp.dense_len)[:, None, None]
    blk = jnp.where(dense, m, jnp.pad(blocks, pad))
    ok = jnp.where(dense, m <= bt, jnp.pad(valid, pad))
    if before is not None:
        ok = ok & (blk < before)
    return blk, jnp.sum(ok, -1, dtype=jnp.int32)


# ------------------------------------------------------------------ #
# the whole forward, no cache (tests, and what a cache must agree with)
# ------------------------------------------------------------------ #


def dense_sparse_attention(q, k, v, sp: SparseAttnConfig):
    """minicpm4 over a whole sequence by its definition: every query
    scores, selects and attends under the one causal rule. q: (S, H, Dh);
    k, v: (S, Hkv, Dh); S a multiple of the block size. O(S^2) memory: a
    small-size form. -> (S, H, Dh) float32."""
    S, H, Dh = q.shape
    Hkv = k.shape[1]
    bs = sp.block_size
    pos = jnp.arange(S, dtype=jnp.int32)
    kbar = pool_windows(k, sp)                              # (S/st - 1, ...)
    J = (S // bs) * sp.windows_per_block
    kbar = jnp.pad(kbar, ((0, J - kbar.shape[0]), (0, 0), (0, 0)))
    b = block_scores(q, jnp.swapaxes(kbar, 0, 1),
                     visible_windows(pos, J, sp), sp)
    chosen = selection_mask(b, pos // bs, sp)
    see = jnp.where((pos + 1 <= sp.dense_len)[:, None, None], True, chosen)
    see = jnp.repeat(see, bs, axis=-1) & (pos[None, None, :] <= pos[:, None, None])
    qg = q.reshape(S, Hkv, H // Hkv, Dh)
    s = jnp.einsum("qhgd,khd->qhgk", qg, k,
                   preferred_element_type=jnp.float32) / math.sqrt(Dh)
    p = jax.nn.softmax(jnp.where(see[:, :, None, :], s, NEG), -1)
    o = jnp.einsum("qhgk,khd->qhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(S, H, Dh)


def dense_eva_attention(q, k, v, mu, phi, ev):
    """eva over a whole sequence by its definition: every query attends
    to the exact keys of its own window up to itself and to the pooled
    key and value of every chunk of the windows before it, in one
    softmax. q, k, v: (S, H, Dh), one query a key head; mu, phi: (H, Dh).
    O(S^2) memory: a small-size form. -> (S, H, Dh) float32."""
    S, H, Dh = q.shape
    W, c = ev.window, ev.chunk
    n = S // c                      # the chunks whose positions all exist
    kbar, vbar = eva_summaries(k[:n * c].reshape(n, c, H, Dh),
                               v[:n * c].reshape(n, c, H, Dh), mu, phi)
    pos = jnp.arange(S, dtype=jnp.int32)
    exact = (pos[None, :] <= pos[:, None]) \
        & (pos[None, :] // W == pos[:, None] // W)
    behind = (jnp.arange(n, dtype=jnp.int32)[None, :] * c) // W \
        < pos[:, None] // W
    keys = jnp.concatenate([k, kbar], 0)
    s = jnp.einsum("qhd,khd->qhk", q, keys,
                   preferred_element_type=jnp.float32) / math.sqrt(Dh)
    see = jnp.concatenate([exact, behind], 1)[:, None, :]
    pr = jax.nn.softmax(jnp.where(see, s, NEG), -1)
    return jnp.einsum("qhk,khd->qhd", pr.astype(v.dtype),
                      jnp.concatenate([v, vbar], 0),
                      preferred_element_type=jnp.float32)


def dense_windowed_attention(q, k, v, window: int = 0):
    """Grouped-query causal attention over a whole sequence, a query at
    ``i`` seeing the keys ``i - window < j <= i`` (every ``j <= i`` where
    ``window`` is 0). q: (S, H, Dh); k, v: (S, Hkv, Dh). O(S^2) memory: a
    small-size form. -> (S, H, Dh) float32."""
    S, H, Dh = q.shape
    Hkv = k.shape[1]
    pos = jnp.arange(S, dtype=jnp.int32)
    see = pos[None, :] <= pos[:, None]
    if window:
        see = see & (pos[None, :] > pos[:, None] - window)
    s = jnp.einsum("qhgd,khd->qhgk", q.reshape(S, Hkv, H // Hkv, Dh), k,
                   preferred_element_type=jnp.float32) / math.sqrt(Dh)
    pr = jax.nn.softmax(jnp.where(see[:, None, None, :], s, NEG), -1)
    return jnp.einsum("qhgk,khd->qhgd", pr.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).reshape(S, H, Dh)


def forward(cfg: GPTConfig, params, tokens, gates: bool = False):
    """tokens (1, S) -> logits (1, S, V): the mixed stack with no cache,
    each mixer by its definition (S a multiple of the sparse block where
    the stack has sparse layers). ``gates`` (a looped stack): -> (logits,
    the passes' exit gates (passes, 1, S))."""
    S = tokens.shape[1]
    slopes = lightning_slopes(cfg.n_head)
    x = embed_tokens(cfg, params, tokens)
    positions = jnp.arange(S, dtype=jnp.int32)

    def body(kind, x, p, _i):
        if kind in ("full_attn", "window_attn"):
            def attend(q, k, v):
                return dense_windowed_attention(
                    q[0], k[0], v[0],
                    cfg.gqa.window if kind == "window_attn" else 0)[None], ()

            return grouped_attn_block(cfg, kind, x, p, positions,
                                      attend)[0], ()
        if kind == "kda":
            kc = cfg.kda

            def scan(qkv, g, beta):
                o, _, _ = kda_chunk(
                    kc, p, qkv[0], g[0], beta[0],
                    jnp.zeros((kc.d_conv - 1, kc.conv_dim), qkv.dtype),
                    jnp.zeros((kc.n_heads, kc.head_k, kc.head_v)), S)
                return o[None], ()

            return kda_block(cfg, x, p, scan)[0], ()
        if kind == "eva":
            def attend(q, k, v):
                return dense_eva_attention(q[0], k[0], v[0], p["mu"],
                                           p["phi"], cfg.eva)[None], None

            return eva_block(cfg, x, p, positions, attend)[0], ()
        if kind == "mamba_attn":
            m = cfg.ssm

            def attend(q, k, v):
                return _xla_causal_attention(q, *expand_kv_heads(q, k, v)), None

            def scan(xbc, dt):
                y, _, _ = ssm_chunk(
                    m, p["ssm"], xbc[0], dt[0],
                    jnp.zeros((m.d_conv - 1, m.conv_dim), xbc.dtype),
                    jnp.zeros((m.n_heads, m.head_dim, m.d_state)), S)
                return y[None], None

            return mamba_attn_block(cfg, x, p, positions, attend, scan)[0], ()

        def core(q, k, v):
            if kind == "lightning":
                o, _ = lightning_chunk_xla(
                    q[0], k[0], v[0],
                    jnp.zeros((cfg.n_head, cfg.head_dim, cfg.head_dim)),
                    slopes, S, block=S)
            else:
                o = dense_sparse_attention(q[0], k[0], v[0], cfg.sparse)
            return o[None], None
        return mixed_block(cfg, kind, x, p, positions, core)[0], ()

    if cfg.loop_steps == 1:
        x, _ = scan_runs(cfg, params, x, body)
        return head_logits(cfg, params, x)
    out = forward_looped(cfg, params, x, body)
    return out if gates else out[0]


def forward_looped(cfg: GPTConfig, params, x, body):
    """``forward``'s tail for a looped stack: the passes one after another
    over the same weights, the final norm after each. -> (logits (1, S, V)
    of the last pass, the passes' exit gates (passes, 1, S) float32)."""
    lam = []
    for _ in range(cfg.loop_steps):
        x, _ = scan_runs(cfg, params, x, body)
        x = final_norm(cfg, params, x)
        lam.append(exit_gate(params, x))
    return head_logits(cfg, params, x, normed=True), jnp.stack(lam)
