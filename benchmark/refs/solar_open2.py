"""Solar Open 2 (huggingface.co/upstage/Solar-Open2-250B), the plain
forward in float32 of ONE CHIP'S SHARE of it: a decoder whose layers are,
a period of four, one grouped-query softmax attention layer WITHOUT rotary
(64 query heads over 8 key heads of 128, an output gate) and three Kimi
Delta Attention layers (a gated delta rule with a decay a channel, 64
heads of 128 x 128, a causal convolution of 4 taps); every feed-forward
routes 8 of 320 gated SiLU experts a token beside 1 shared expert;
RMSNorm, no bias, an untied head.

With ``norm(x; w) = x / sqrt(mean(x^2) + eps) w``, positions ``t`` from 0:

    x_0 = E[token];  x <- x + Mixer_l(norm(x; w_1));
    x <- x + FFN_l(norm(x; w_2));  logits = norm(x_L; w_f) W_head

    GQA layer (l mod 4 = 0), m the normed input, query head h reading key
    head h // 8:
      q_t, k_t, v_t the heads' columns of m W_qkv, NO rotary, no q/k norm
      o_t = sum_{j <= t} softmax_j(q_t . k_j / sqrt(128)) v_j
      y = (o * sigmoid(m W_g)) W_o            (the gate ASSUMED: entry by
                                              entry, before W_o)
    KDA layer (the other three), H = 64 heads, dk = dv = 128:
      c_t = sum_{j<4} w_j * (m W_qkv)_{t-3+j}   depthwise, causal, no bias,
                                                zeros before position 0
      (q~, k~, v) = the heads' columns of SiLU(c_t)
      q = q~ / sqrt(|q~|^2 + 1e-6) / sqrt(dk);  k = k~ / sqrt(|k~|^2 + 1e-6)
      g_t = -exp(A_h) softplus(W_f^up (W_f^down m_t) + b)   a CHANNEL
      beta_t = 2 sigmoid(W_beta m_t)            a head (2: the eigenvalue
                                                of I - beta k k^T along k
                                                lies in (-1, 1))
      S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t
      y_t = (norm_head(o_t; w_o) * sigmoid(W_g^up (W_g^down m_t))) W_o
    FFN: s = sigmoid(m W_r) over ALL 320 experts; T = the 8 largest of
      s + bias (ties: the lower index); g_e = s_e / sum_{e' in T} s_e'
      y = sum_{e in T, e HELD HERE} g_e E_e(m) + E_shared(m),
      E(m) = W_down(SiLU(W_gate m) * (W_up m))

THE SHARE: the router scores and chooses over all 320 experts, but only
the experts ``first .. first + held - 1`` of a layer live here (this
chip's eighth of an 8-chip layer): what the absent experts would have
added is left out, here as in the program, and that partial result goes
on to the next layer. The vocabulary is a slice (rows 0 .. V-1).

HERE the rule runs a token at a time (``lax.scan`` over the positions; no
chunkwise form, no triangular solve), the convolution is a sum of four
shifted copies, every query's key set is a mask over the keys themselves,
EVERY held expert's product is computed for every token, one expert after
another, weighted by a gate that is zero where the expert was not chosen:
no sort, no grouped product, no pages, no state row carried between
programs, no chunked prefill, no kernel, and the routing is the
reference's own. Departures and assumed constants are listed in the
configuration's file (``assumed``). Parameters use the layout the system
under test is handed: per-layer tensors stacked on a leading axis BY KIND
(``full_attn``, ``kda``), projections as (in, out).

Controls (``make(cfg, control=...)``): ``nodelta`` (the ``beta k k^T``
term dropped: plain gated linear attention), ``headdecay`` (a channel's
log-decay replaced by its head's mean), ``posbeta`` (beta not doubled),
``noconv`` (q, k, v not convolved), ``noshared`` (no shared expert),
``nobias`` (the selection without its bias), ``nogate`` (the GQA layer's
output gate off), ``softmaxroute`` (a softmax over the 320 scores, its 8
largest renormalised).
"""

import math
import types

import jax
import jax.numpy as jnp
import numpy as np

from .init import Spec
from .mellum import served_gaps  # noqa: F401  (the same two statistics)
from .numerics import F32

Q_BLOCK = 128     # queries that meet their keys at once
PAD_TO = 2048     # a request's length is padded up to a multiple of this
CONTROLS = ("nodelta", "headdecay", "posbeta", "noconv", "noshared",
            "nobias", "nogate", "softmaxroute")


def dims(cfg: dict) -> dict:
    lin, share = cfg["linear_attn_config"], cfg["share"]
    n = cfg["num_layers"]
    kinds = ["full_attn" if i in cfg["gqa_layers"] else "kda"
             for i in range(n)]
    return {"D": cfg["hidden_size"], "F": cfg["moe_intermediate_size"],
            "V": cfg["vocab_size"], "H": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "Dh": cfg["head_dim"],
            "Hk": lin["num_heads"], "dk": lin["head_dim"],
            "K": lin["short_conv_kernel_size"], "r": cfg["kda_low_rank"],
            "E": share["experts_routed_over"], "held": cfg["n_routed_experts"],
            "first": share["first_expert"], "k": cfg["num_experts_per_tok"],
            "shared": cfg["n_shared_experts"], "eps": cfg["rms_norm_eps"],
            "kinds": kinds, "L": n}


def leaf_specs(cfg: dict):
    """How every weight starts (``assumed.weights`` in the configuration's
    file says why): normal(0, std) with one std a tensor from the
    configuration's ``weights`` block, or a constant from it."""
    d, w = dims(cfg), cfg["weights"]
    D, F, E, held = d["D"], d["F"], d["E"], d["held"]
    one = lambda *shape: Spec(shape, const=1.0)

    def mlp(n):
        Fs = d["shared"] * F
        return {"w_gate": Spec((n, held, D, F), w["w_gate"]),
                "w_up": Spec((n, held, D, F), w["w_up"]),
                "w_down": Spec((n, held, F, D), w["w_down"]),
                "router": Spec((n, D, E), w["router"]),
                "router_bias": Spec((n, E), w["router_bias"]),
                "shared": {"w_gate": Spec((n, D, Fs), w["w_gate"]),
                           "w_up": Spec((n, D, Fs), w["w_up"]),
                           "w_down": Spec((n, Fs, D), w["w_down"])}}

    specs = {"embed": {"wte": Spec((d["V"], D), w["embed"])},
             "final_norm": {"scale": one(D)},
             "lm_head": Spec((D, d["V"]), w["lm_head"])}
    n = d["kinds"].count("full_attn")
    if n:
        H, Hkv, Dh = d["H"], d["Hkv"], d["Dh"]
        specs["full_attn"] = {
            "ln1": one(n, D), "ln2": one(n, D),
            "wqkv": Spec((n, D, (H + 2 * Hkv) * Dh), w["wqkv"]),
            "wg": Spec((n, D, H * Dh), w["wg"]),
            "wo": Spec((n, H * Dh, D), w["wo"]), "mlp": mlp(n)}
    n = d["kinds"].count("kda")
    if n:
        Hk, dk, r = d["Hk"], d["dk"], d["r"]
        specs["kda"] = {
            "ln1": one(n, D), "ln2": one(n, D),
            "wqkv": Spec((n, D, 3 * Hk * dk), w["wqkv"]),
            "conv_w": Spec((n, d["K"], 3 * Hk * dk), w["conv_w"]),
            "wf_down": Spec((n, D, r), w["low_rank_down"]),
            "wf_up": Spec((n, r, Hk * dk), w["low_rank_up"]),
            "f_bias": Spec((n, Hk * dk), const=w["f_bias"]),
            "A_log": Spec((n, Hk), const=w["A_log"]),
            "w_beta": Spec((n, D, Hk), w["w_beta"]),
            "wg_down": Spec((n, D, r), w["low_rank_down"]),
            "wg_up": Spec((n, r, Hk * dk), w["low_rank_up"]),
            "o_norm": one(n, dk),
            "wo": Spec((n, Hk * dk, D), w["wo"]), "mlp": mlp(n)}
    return specs


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def make(cfg: dict, num=F32, control: str = None):
    """The model's parts over one sequence x (T, D), T a multiple of
    ``Q_BLOCK``. ``control``: one of ``CONTROLS``, or None."""
    assert control in (None,) + CONTROLS, control
    d = dims(cfg)
    H, Hkv, Dh, eps = d["H"], d["Hkv"], d["Dh"], d["eps"]
    Hk, dk, K = d["Hk"], d["dk"], d["K"]
    G = H // Hkv

    def embed(outer, ids):
        return jnp.take(outer["embed"]["wte"].astype(jnp.float32), ids, axis=0)

    def attention(p, u):
        T = u.shape[0]
        pos = jnp.arange(T, dtype=jnp.int32)
        qkv = num.dot(u, p["wqkv"])
        q = qkv[:, :H * Dh].reshape(T, H, Dh)
        kh = jnp.swapaxes(qkv[:, H * Dh:(H + Hkv) * Dh].reshape(T, Hkv, Dh),
                          0, 1)
        vh = jnp.swapaxes(qkv[:, (H + Hkv) * Dh:].reshape(T, Hkv, Dh), 0, 1)
        B = Q_BLOCK

        def block(a):
            qb, pb = a                                  # (B, H, Dh), (B,)
            sees = pos[None, :] <= pb[:, None]
            qh = jnp.swapaxes(qb, 0, 1).reshape(Hkv, G * B, Dh)
            sc = num.dot(qh, jnp.swapaxes(kh, 1, 2)) / math.sqrt(Dh)
            sc = jnp.where(jnp.tile(sees, (G, 1))[None], sc, -jnp.inf)
            o = num.dot(jax.nn.softmax(sc, axis=-1), vh)    # (Hkv, G B, Dh)
            return jnp.swapaxes(o.reshape(H, B, Dh), 0, 1)

        o = jax.lax.map(block, (q.reshape(T // B, B, H, Dh),
                                pos.reshape(T // B, B))).reshape(T, H * Dh)
        if control != "nogate":
            o = o * jax.nn.sigmoid(num.dot(u, p["wg"]))
        return num.dot(o, p["wo"])

    def kda(p, u):
        T = u.shape[0]
        x = num.dot(u, p["wqkv"])                       # (T, 3 Hk dk)
        if control == "noconv":
            c = x
        else:
            ext = jnp.pad(x, ((K - 1, 0), (0, 0)))
            c = sum(p["conv_w"][j] * ext[j:j + T] for j in range(K))
        c = jax.nn.silu(c)
        qt, kt, v = (c[:, i * Hk * dk:(i + 1) * Hk * dk].reshape(T, Hk, dk)
                     for i in range(3))
        unit = lambda a: a * jax.lax.rsqrt(
            jnp.sum(jnp.square(a), -1, keepdims=True) + 1e-6)
        q, k = unit(qt) / math.sqrt(dk), unit(kt)
        f = num.dot(num.dot(u, p["wf_down"]), p["wf_up"]) + p["f_bias"]
        g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
            f.reshape(T, Hk, dk))
        if control == "headdecay":
            g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
        beta = jax.nn.sigmoid(num.dot(u, p["w_beta"]))  # (T, Hk)
        if control != "posbeta":
            beta = 2.0 * beta

        def step(S, a):
            q_t, k_t, v_t, g_t, b_t = a
            S = jnp.exp(g_t)[..., None] * S             # (Hk, dk, dv)
            pred = jnp.sum(k_t[..., None] * S, 1)       # S^T k
            if control == "nodelta":
                pred = jnp.zeros_like(pred)
            S = S + k_t[..., None] * (b_t[:, None] * (v_t - pred))[:, None, :]
            return S, jnp.sum(q_t[..., None] * S, 1)

        _, o = jax.lax.scan(step, jnp.zeros((Hk, dk, dk), jnp.float32),
                            (q, k, v, g, beta))
        o = norm(o, p["o_norm"], eps)
        gate = jax.nn.sigmoid(num.dot(num.dot(u, p["wg_down"]), p["wg_up"]))
        return num.dot(o.reshape(T, Hk * dk) * gate, p["wo"])

    def gated(m, wg, wu, wd):
        return num.dot(jax.nn.silu(num.dot(m, wg)) * num.dot(m, wu), wd)

    def route(p, m):
        """A token's chosen experts among ALL of the layer's and their
        gates: (indices (T, k), gates (T, k))."""
        logits = num.dot(m, p["router"])                # (T, E)
        if control == "softmaxroute":
            top, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), d["k"])
        else:
            s = jax.nn.sigmoid(logits)
            pick = s if control == "nobias" else s + p["router_bias"]
            _, idx = jax.lax.top_k(pick, d["k"])
            top = jnp.take_along_axis(s, idx, axis=-1)
        if cfg["norm_topk_prob"]:
            top = top / jnp.sum(top, -1, keepdims=True)
        return idx, top * cfg["routed_scaling_factor"]

    def experts(p, m):
        """Every HELD expert over every token, one after another, each
        weighted by its gate (zero where it was not among the token's
        chosen), and the shared expert once."""
        idx, top = route(p, m)
        gates = jnp.sum(jax.nn.one_hot(idx, d["E"], dtype=jnp.float32)
                        * top[..., None], 1)            # (T, E)
        here = gates[:, d["first"]:d["first"] + d["held"]]

        def one(y, e):
            wg, wu, wd, g = e
            return y + g[:, None] * gated(m, wg, wu, wd), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(m),
                            (p["w_gate"], p["w_up"], p["w_down"], here.T))
        if d["shared"] and control != "noshared":
            sh = p["shared"]
            y = y + gated(m, sh["w_gate"], sh["w_up"], sh["w_down"])
        return y

    def layer(kind, p, x):
        """``p`` may hold the served dtype: it is cast here."""
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        mixer = attention if kind == "full_attn" else kda
        x = x + mixer(p, norm(x, p["ln1"], eps))
        return x + experts(p["mlp"], norm(x, p["ln2"], eps))

    def head(outer, x):
        x = norm(x, outer["final_norm"]["scale"].astype(jnp.float32), eps)
        return num.dot(x, outer["lm_head"].astype(jnp.float32))

    return types.SimpleNamespace(embed=embed, layer=layer, head=head,
                                 route=route, experts=experts,
                                 kinds=d["kinds"], vocab=d["V"])


class Forward:
    """A model's logits for one request, a layer at a time: each layer's
    weights are cast to float32 when it runs; each part is one jitted
    program a length, under ``highest`` matmul precision."""

    def __init__(self, model):
        self.model = model
        self._layer = {
            kind: jax.jit(lambda stack, i, x, kind=kind: model.layer(
                kind, jax.tree.map(lambda a: a[i], stack), x),
                donate_argnums=2)
            for kind in set(model.kinds)}
        self._embed = jax.jit(model.embed)
        self._head = jax.jit(model.head)

    def logits(self, params, tokens, first: int):
        """Logits at positions first-1 .. len(tokens)-2, those that
        predict tokens[first:]. Right padding cannot reach them
        (causal)."""
        T = len(tokens)
        pad = PAD_TO if T > PAD_TO else Q_BLOCK     # a short one: few shapes
        ids = np.zeros((-(-T // pad) * pad,), np.int32)
        ids[:T] = tokens
        outer = {k: v for k, v in params.items()
                 if k not in ("full_attn", "kda")}
        seen = {}
        with jax.default_matmul_precision("highest"):
            x = self._embed(outer, jnp.asarray(ids))
            for kind in self.model.kinds:
                i = seen.get(kind, 0)
                seen[kind] = i + 1
                x = self._layer[kind](params[kind], i, x)
            return self._head(outer, x[first - 1:T - 1])
