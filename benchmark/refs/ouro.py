"""Ouro (huggingface.co/ByteDance/Ouro-2.6B; arXiv:2510.25741, "Scaling
Latent Reasoning via Looped Language Models"), the plain forward in
float32: a decoder whose ONE stack of layers is applied ``total_ut_steps``
times to the residual stream with the same weights. A layer is multi-head
softmax attention (16 query heads over 16 key heads of 128, rotary over
the whole head) and a gated SiLU feed-forward, each between two RMSNorms
("sandwich": four norms a layer), no bias, an untied head.

With ``N(x; w) = x / sqrt(mean(x^2) + eps) w``, ``s = 1 /
sqrt(head_dim)``, positions ``i`` from 0, ``T = total_ut_steps``, ``L``
layers, ``h^0 = E[token]``:

    for t in 0 .. T-1:                      the SAME weights in every pass
        x = h^t
        for l in 0 .. L-1:
            u = N(x; w1_l); q_i, k_i, v_i the heads' columns of u Wqkv_l
            q, k turned by rotary, half-split over all of head_dim,
                angles i theta^(-2j/head_dim) in float32
            a_i = sum_{j <= i} softmax_j(s q_i . k_j) v_j     pass t's OWN
                keys and values: nothing of another pass is read
            x <- x + N(a Wo_l; w1'_l)                          (ASSUMED)
            m = N(x; w2_l)
            x <- x + N((SiLU(m Wg_l) * (m Wu_l)) Wd_l; w2'_l)  (ASSUMED)
        h^{t+1} = N(x; w_f)        the final norm, after EVERY pass (ASSUMED)
        lam_t = sigmoid(h^{t+1} . w_exit + b_exit)
    logits = h^T W_head            early_exit_threshold 1: the last pass

HERE every query meets every key of its own pass under a mask, one layer
at a time (each layer's weights cast to float32 when it runs): no pages,
no cache, no loop inside a program, no kernel, no batching. Departures and
assumed constants are listed in the configuration's file (``assumed``).
Parameters use the layout the system under test is handed: per-layer
tensors stacked on a leading axis under ``full_attn``, projections as
(in, out), q, k and v side by side in one.

Controls (``make(cfg, control=...)``), each the reference put in the
program's place with one thing wrong: ``threeloops`` (one pass fewer),
``sharedcache`` (every pass after the first attends over the FIRST pass's
keys and values at the positions before the query's own: a program whose
passes share one cache), ``nosandwich`` (no norm on a sublayer's output),
``normonce`` (the final norm after the last pass alone).
"""

import math
import types

import jax
import jax.numpy as jnp
import numpy as np

from .init import Spec
from .mellum import served_gaps  # noqa: F401  (the runners call it here)
from .numerics import F32

PAD_TO = 128      # a request's length is padded up to a multiple of this
CONTROLS = ("threeloops", "sharedcache", "nosandwich", "normonce")


def dims(cfg: dict) -> dict:
    assert set(cfg["layer_types"]) == {"full_attention"}, cfg["layer_types"]
    return {"D": cfg["hidden_size"], "F": cfg["intermediate_size"],
            "V": cfg["vocab_size"], "H": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "Dh": cfg["head_dim"],
            "eps": cfg["rms_norm_eps"], "L": cfg["num_hidden_layers"],
            "T": cfg["total_ut_steps"], "theta": float(cfg["rope_theta"])}


def leaf_specs(cfg: dict):
    """How every weight starts (``assumed.weights`` in the configuration's
    file says why): normal(0, std) with one std a tensor from the
    configuration's ``weights`` block; every norm's scale 1 but those on
    the sublayers' outputs (``weights.post_norm``), the exit gate's bias
    0."""
    d, w = dims(cfg), cfg["weights"]
    D, F, n = d["D"], d["F"], d["L"]
    one = lambda *shape: Spec(shape, const=1.0)
    post = lambda *shape: Spec(shape, const=w["post_norm"])
    return {"embed": {"wte": Spec((d["V"], D), w["embed"])},
            "final_norm": {"scale": one(D)},
            "lm_head": Spec((D, d["V"]), w["lm_head"]),
            "exit_gate": {"w": Spec((D,), w["exit_gate"]),
                          "b": Spec((), const=0.0)},
            "full_attn": {
                "ln1": one(n, D), "ln2": one(n, D),
                "ln1_post": post(n, D), "ln2_post": post(n, D),
                "wqkv": Spec((n, D, (d["H"] + 2 * d["Hkv"]) * d["Dh"]),
                             w["wqkv"]),
                "wo": Spec((n, d["H"] * d["Dh"], D), w["wo"]),
                "mlp": {"w_gate": Spec((n, D, F), w["w_gate"]),
                        "w_up": Spec((n, D, F), w["w_up"]),
                        "w_down": Spec((n, F, D), w["w_down"])}}}


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def rotary(x, theta):
    """x: (S, H, Dh) at positions 0 .. S-1; the half-split form over all
    of Dh."""
    S, _, Dh = x.shape
    half = Dh // 2
    freq = theta ** (-2.0 * np.arange(half, dtype=np.float64) / Dh)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] \
        * jnp.asarray(freq, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def make(cfg: dict, num=F32, control: str = None):
    """The model's parts over one sequence x (S, D). ``control``: one of
    ``CONTROLS``, or None."""
    assert control in (None,) + CONTROLS, control
    d = dims(cfg)
    H, Hkv, Dh, eps = d["H"], d["Hkv"], d["Dh"], d["eps"]
    G = H // Hkv
    s = 1.0 / math.sqrt(Dh)
    post = (lambda y, w: y) if control == "nosandwich" \
        else (lambda y, w: norm(y, w, eps))

    def embed(outer, ids):
        return jnp.take(outer["embed"]["wte"].astype(jnp.float32), ids, axis=0)

    def attention(p, u, first):
        """-> (the heads' output through Wo, this pass's (k, v)).
        ``first``: the first pass's (k, v) of this layer where the passes
        share a cache (the control), else None."""
        S = u.shape[0]
        qkv = num.dot(u, p["wqkv"])
        q = rotary(qkv[:, :H * Dh].reshape(S, H, Dh), d["theta"])
        k = rotary(qkv[:, H * Dh:(H + Hkv) * Dh].reshape(S, Hkv, Dh),
                   d["theta"])
        v = qkv[:, (H + Hkv) * Dh:].reshape(S, Hkv, Dh)
        pos = jnp.arange(S)
        sees = pos[None, :] <= pos[:, None]
        own = pos[None, :] == pos[:, None]
        qh = jnp.swapaxes(q, 0, 1).reshape(Hkv, G, S, Dh)
        heads = lambda a: jnp.swapaxes(a, 0, 1)[:, None]    # (Hkv, 1, S, Dh)
        score = lambda kk: s * num.dot(qh, jnp.swapaxes(heads(kk), -1, -2))
        sc = score(k)                                       # (Hkv, G, S, S)
        if first is not None:
            # the positions before the query's own come from the first
            # pass's rows; its own row is this pass's
            sc = jnp.where(own, sc, score(first[0]))
        pr = jax.nn.softmax(jnp.where(sees, sc, -jnp.inf), axis=-1)
        if first is None:
            o = num.dot(pr, heads(v))
        else:
            o = num.dot(jnp.where(own, 0.0, pr), heads(first[1])) \
                + num.dot(jnp.where(own, pr, 0.0), heads(v))
        o = jnp.swapaxes(o.reshape(H, S, Dh), 0, 1).reshape(S, H * Dh)
        return num.dot(o, p["wo"]), (k, v)

    def layer(p, x, first=None):
        """``p`` may hold the served dtype: it is cast here. -> (x, this
        pass's keys and values of the layer)."""
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        a, kv = attention(p, norm(x, p["ln1"], eps), first)
        x = x + post(a, p["ln1_post"])
        m = norm(x, p["ln2"], eps)
        mlp = p["mlp"]
        y = num.dot(jax.nn.silu(num.dot(m, mlp["w_gate"]))
                    * num.dot(m, mlp["w_up"]), mlp["w_down"])
        return x + post(y, p["ln2_post"]), kv

    def end_pass(outer, x, last: bool):
        """-> (what the next pass, or the head, reads; the exit gate)."""
        normed = norm(x, outer["final_norm"]["scale"].astype(jnp.float32),
                      eps)
        g = outer["exit_gate"]
        lam = jax.nn.sigmoid(
            jnp.sum(normed * g["w"].astype(jnp.float32), -1)
            + g["b"].astype(jnp.float32))
        return (normed if last or control != "normonce" else x), lam

    def head(outer, h):
        return num.dot(h, outer["lm_head"].astype(jnp.float32))

    return types.SimpleNamespace(
        embed=embed, layer=layer, end_pass=end_pass, head=head,
        layers=d["L"], passes=d["T"] - (control == "threeloops"),
        shared=control == "sharedcache", vocab=d["V"])


class Forward:
    """A model's logits for one request, a layer and a pass at a time:
    each part is one jitted program a length, under ``highest`` matmul
    precision."""

    def __init__(self, model):
        self.model = model
        at = lambda stack, i: jax.tree.map(lambda a: a[i], stack)
        self._layer = jax.jit(lambda stack, i, x: model.layer(
            at(stack, i), x)[0], donate_argnums=2)
        self._layer_kv = jax.jit(lambda stack, i, x: model.layer(
            at(stack, i), x), donate_argnums=2)
        self._layer_on = jax.jit(lambda stack, i, x, first: model.layer(
            at(stack, i), x, first)[0], donate_argnums=2)
        self._embed = jax.jit(model.embed)
        self._end = jax.jit(model.end_pass, static_argnums=2)
        self._head = jax.jit(model.head)

    def run(self, params, tokens, first: int):
        """(logits, the passes' exit gates (passes, n)) at positions
        first-1 .. len(tokens)-2, those that predict tokens[first:]. Right
        padding cannot reach them (causal)."""
        m, S = self.model, len(tokens)
        ids = np.zeros((-(-S // PAD_TO) * PAD_TO,), np.int32)
        ids[:S] = tokens
        outer = {k: v for k, v in params.items() if k != "full_attn"}
        stack, lam, kept = params["full_attn"], [], []
        with jax.default_matmul_precision("highest"):
            x = self._embed(outer, jnp.asarray(ids))
            for t in range(m.passes):
                for l in range(m.layers):
                    if not m.shared:
                        x = self._layer(stack, l, x)
                    elif t == 0:
                        x, kv = self._layer_kv(stack, l, x)
                        kept.append(kv)
                    else:
                        x = self._layer_on(stack, l, x, kept[l])
                x, g = self._end(outer, x, t == m.passes - 1)
                lam.append(g[first - 1:S - 1])
            return self._head(outer, x[first - 1:S - 1]), jnp.stack(lam)

    def logits(self, params, tokens, first: int):
        return self.run(params, tokens, first)[0]


def exit_distribution(lam):
    """The gates (T, n) -> where a token would leave, (T, n): ``p_t =
    lam_t prod_{j<t} (1 - lam_j)``, the last pass taking what is left."""
    lam = np.asarray(lam, np.float64)
    p, left = [], np.ones_like(lam[0])
    for t in range(len(lam) - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return np.stack(p + [left])
