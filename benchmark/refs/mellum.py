"""Mellum 2 (huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct), the
plain forward in float32: a decoder whose layers are grouped-query softmax
attention (32 query heads over 4 key heads of 128; three layers of a
sliding window of 1,024 keys, then one of full attention, a period) and a
feed-forward of 64 gated SiLU experts routed 8 a token; RMSNorm, no bias,
an untied head.

With ``norm(x; w) = x / sqrt(mean(x^2) + eps) w``, ``s = 1 /
sqrt(head_dim)``, positions ``i`` from 0, query head ``h`` reading key
head ``h // (heads / key heads)``:

    u = norm(x; w_1); q_i, k_i, v_i the heads' columns of u W_qkv
    q <- norm(q; w_qn), k <- norm(k; w_kn) over each head's entries
        (ASSUMED: configs/mellum2-12b-a2.5b.json, ``assumed.qk_norm``)
    rotary, half-split over all of head_dim, angles in float32, pair j:
      sliding layers  f_j = theta^(-2j/head_dim), a = 1
      full layers     YaRN: low = floor(head_dim ln(P / (beta_fast 2 pi))
                      / (2 ln theta)), high = ceil(the same with
                      beta_slow), r_j = clip((j - low) / (high - low), 0,
                      1), f_j = (1 - r_j) theta^(-2j/head_dim) + r_j
                      theta^(-2j/head_dim) / factor; cos and sin BOTH
                      times a = attention_factor
    V_i = {j : i - window < j <= i} (sliding), {j <= i} (full)
    o_i = sum_{j in V_i} softmax_j(s q_i . k_j) v_j;  x <- x + o W_o
    m = norm(x; w_2); rho = softmax(m W_r) over ALL experts
    T = the experts_per_tok largest of rho (ties: the lower index)
    g_e = rho_e / sum_{e' in T} rho_e'
    x <- x + sum_{e in T} g_e W_down^e(SiLU(W_gate^e m) * (W_up^e m))
    x_0 = E[token];  logits_i = norm(x_L; w_f) W_head

HERE every query's key set is a mask over the keys themselves (a sliding
layer's over the slice of the sequence its band can reach), queries a
block at a time so that 32,768 positions fit; EVERY expert's product is
computed for every token, one expert after another, and weighted by a
gate that is zero where the expert was not chosen: no sort, no grouped
product, no pages, no ring, no chunked prefill, no kernel, and the
routing is the reference's own (never the program's). Departures and
assumed constants are listed in the configuration's file (``assumed``).
Parameters use the layout the system under test is handed: per-layer
tensors stacked on a leading axis BY KIND (``full_attn``,
``window_attn``), projections as (in, out), q, k and v side by side in
one, the experts' weights with the expert axis behind the layers'.

Controls (``make(cfg, control=...)``): ``nowindow`` (every layer sees
every key: a program that forgot the window), ``noyarn`` (the full layers
turn by the sliding layers' frequencies with a = 1), ``rawgates`` (the
gates not renormalised), ``noqknorm`` (q and k not normed a head).
"""

import math
import types

import jax
import jax.numpy as jnp
import numpy as np

from .init import Spec
from .numerics import F32

Q_BLOCK = 128     # queries that meet their keys at once
PAD_TO = 2048     # a request's length is padded up to a multiple of this
KINDS = {"sliding_attention": "window_attn", "full_attention": "full_attn"}


def dims(cfg: dict) -> dict:
    kinds = [KINDS[t] for t in cfg["layer_types"][:cfg["num_layers"]]]
    return {"D": cfg["hidden_size"], "F": cfg["moe_intermediate_size"],
            "V": cfg["vocab_size"], "H": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "Dh": cfg["head_dim"],
            "E": cfg["num_experts"], "k": cfg["num_experts_per_tok"],
            "eps": cfg["rms_norm_eps"], "W": cfg["sliding_window"],
            "kinds": kinds, "L": cfg["num_layers"],
            "norm_gates": cfg["norm_topk_prob"]}


def inv_freq(section: dict, head_dim: int) -> np.ndarray:
    """The head_dim / 2 inverse frequencies of one section of the config's
    ``rope_parameters``, and the factor on cos and sin."""
    half = head_dim // 2
    theta = float(section["rope_theta"])
    base = theta ** (-2.0 * np.arange(half, dtype=np.float64) / head_dim)
    if section["rope_type"] == "default":
        return base.astype(np.float32), 1.0
    P = section["original_max_position_embeddings"]
    pair = lambda turns: (head_dim * math.log(P / (turns * 2 * math.pi))
                          / (2 * math.log(theta)))
    low = max(math.floor(pair(section["beta_fast"])), 0)
    high = min(math.ceil(pair(section["beta_slow"])), head_dim - 1)
    r = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    f = (1 - r) * base + r * base / section["factor"]
    return f.astype(np.float32), float(section["attention_factor"])


def leaf_specs(cfg: dict):
    """How every weight starts (``assumed.weights`` in the configuration's
    file says why): normal(0, std) with one std a tensor from the
    configuration's ``weights`` block; every norm weight 1."""
    d, w = dims(cfg), cfg["weights"]
    D, F, E, Dh = d["D"], d["F"], d["E"], d["Dh"]
    qkv = (d["H"] + 2 * d["Hkv"]) * Dh

    def kind(n):
        return {"ln1": Spec((n, D), const=1.0), "ln2": Spec((n, D), const=1.0),
                "wqkv": Spec((n, D, qkv), w["wqkv"]),
                "wo": Spec((n, d["H"] * Dh, D), w["wo"]),
                "q_norm": Spec((n, Dh), const=1.0),
                "k_norm": Spec((n, Dh), const=1.0),
                "mlp": {"w_gate": Spec((n, E, D, F), w["w_gate"]),
                        "w_up": Spec((n, E, D, F), w["w_up"]),
                        "w_down": Spec((n, E, F, D), w["w_down"]),
                        "router": Spec((n, D, E), w["router"])}}

    specs = {"embed": {"wte": Spec((d["V"], D), w["embed"])},
             "final_norm": {"scale": Spec((D,), const=1.0)},
             "lm_head": Spec((D, d["V"]), w["lm_head"])}
    for name in ("full_attn", "window_attn"):
        if d["kinds"].count(name):
            specs[name] = kind(d["kinds"].count(name))
    return specs


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def rotary(x, pos, freq, a):
    """x: (T, H, Dh) at positions ``pos`` (T,) float32; the half-split
    form over all of Dh; cos and sin both times ``a``."""
    half = x.shape[-1] // 2
    ang = pos[:, None] * jnp.asarray(freq)
    cos, sin = a * jnp.cos(ang)[:, None, :], a * jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def make(cfg: dict, num=F32, control: str = None):
    """The model's parts over one sequence x (T, D), T a multiple of
    ``Q_BLOCK``. ``control``: one of the module's controls, or None."""
    assert control in (None, "nowindow", "noyarn", "rawgates", "noqknorm")
    d = dims(cfg)
    H, Hkv, Dh, eps, W = d["H"], d["Hkv"], d["Dh"], d["eps"], d["W"]
    G = H // Hkv
    s = 1.0 / math.sqrt(Dh)
    ropes = cfg["rope_parameters"]
    rope = {"window_attn": inv_freq(ropes["sliding_attention"], Dh),
            "full_attn": inv_freq(ropes["full_attention"], Dh)}
    if control == "noyarn":
        rope["full_attn"] = rope["window_attn"]

    def embed(outer, ids):
        return jnp.take(outer["embed"]["wte"].astype(jnp.float32), ids, axis=0)

    def attention(kind, p, u):
        T = u.shape[0]
        pos = jnp.arange(T, dtype=jnp.int32)
        qkv = num.dot(u, p["wqkv"])
        q = qkv[:, :H * Dh].reshape(T, H, Dh)
        k = qkv[:, H * Dh:(H + Hkv) * Dh].reshape(T, Hkv, Dh)
        v = qkv[:, (H + Hkv) * Dh:].reshape(T, Hkv, Dh)
        if control != "noqknorm":
            q, k = norm(q, p["q_norm"], eps), norm(k, p["k_norm"], eps)
        q = rotary(q, pos.astype(jnp.float32), *rope[kind])
        k = rotary(k, pos.astype(jnp.float32), *rope[kind])
        windowed = kind == "window_attn" and control != "nowindow"
        B = Q_BLOCK
        # the keys a block of queries can reach: a sliding layer's lie in
        # the W + B positions that end with the block (the sequence padded
        # in front by W positions that no mask lets through), every key
        # otherwise
        span = W + B if windowed else T
        front = W if windowed else 0
        kh = jnp.pad(jnp.swapaxes(k, 0, 1), ((0, 0), (front, 0), (0, 0)))
        vh = jnp.pad(jnp.swapaxes(v, 0, 1), ((0, 0), (front, 0), (0, 0)))

        def block(a):
            qb, pb = a                                  # (B, H, Dh), (B,)
            start = pb[0] if windowed else 0            # in the padded axis
            kw = jax.lax.dynamic_slice_in_dim(kh, start, span, 1)
            vw = jax.lax.dynamic_slice_in_dim(vh, start, span, 1)
            at = start - front + jnp.arange(span, dtype=jnp.int32)
            sees = (at[None, :] <= pb[:, None]) & (at[None, :] >= 0)
            if windowed:
                sees = sees & (at[None, :] > pb[:, None] - W)
            qh = jnp.swapaxes(qb, 0, 1).reshape(Hkv, G * B, Dh)
            sc = s * num.dot(qh, jnp.swapaxes(kw, 1, 2))   # (Hkv, G B, span)
            sc = jnp.where(jnp.tile(sees, (G, 1))[None], sc, -jnp.inf)
            o = num.dot(jax.nn.softmax(sc, axis=-1), vw)    # (Hkv, G B, Dh)
            return jnp.swapaxes(o.reshape(H, B, Dh), 0, 1)

        assert T % B == 0, (T, B)
        o = jax.lax.map(block, (q.reshape(T // B, B, H, Dh),
                                pos.reshape(T // B, B)))
        return num.dot(o.reshape(T, H * Dh), p["wo"])

    def experts(p, m):
        """Every expert over every token, one after another, each weighted
        by its gate (zero where it was not among the token's largest)."""
        rho = jax.nn.softmax(num.dot(m, p["router"]), axis=-1)   # (T, E)
        top, idx = jax.lax.top_k(rho, d["k"])
        if d["norm_gates"] and control != "rawgates":
            top = top / jnp.sum(top, -1, keepdims=True)
        gates = jnp.sum(jax.nn.one_hot(idx, d["E"], dtype=jnp.float32)
                        * top[..., None], 1)                      # (T, E)

        def one(y, e):
            wg, wu, wd, g = e
            h = jax.nn.silu(num.dot(m, wg)) * num.dot(m, wu)
            return y + g[:, None] * num.dot(h, wd), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(m),
                            (p["w_gate"], p["w_up"], p["w_down"], gates.T))
        return y

    def layer(kind, p, x):
        """``p`` may hold the served dtype: it is cast here."""
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        x = x + attention(kind, p, norm(x, p["ln1"], eps))
        return x + experts(p["mlp"], norm(x, p["ln2"], eps))

    def head(outer, x):
        x = norm(x, outer["final_norm"]["scale"].astype(jnp.float32), eps)
        return num.dot(x, outer["lm_head"].astype(jnp.float32))

    return types.SimpleNamespace(embed=embed, layer=layer, head=head,
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
                 if k not in ("full_attn", "window_attn")}
        seen = {}
        with jax.default_matmul_precision("highest"):
            x = self._embed(outer, jnp.asarray(ids))
            for kind in self.model.kinds:
                i = seen.get(kind, 0)
                seen[kind] = i + 1
                x = self._layer[kind](params[kind], i, x)
            return self._head(outer, x[first - 1:T - 1])


def served_gaps(forward: Forward, params, requests, controls=None,
                control_max_tokens: int = None) -> dict:
    """requests: [{"prompt": [...], "output": [...]}]. How far the served
    (greedy) tokens' reference logits lie below the reference's best, two
    ways: ``widest_gap``, the largest over every served token, and
    ``request_mean_gap``, the largest over the requests of a request's
    MEAN over its served tokens (where the 8th and 9th of a token's
    experts nearly tie, bfloat16 picks the other in a few (token, layer)
    pairs of a thousand and the flipped expert moves that token's logits:
    the widest gap is the tail of those flips, a request's mean what the
    arithmetic does to every token). For each of ``controls`` ({name:
    Forward}, the reference put in the program's place) the same two of
    the tokens the control puts first, under ``controls`` and
    ``controls_request_mean``. A control costs a whole forward a request:
    with ``control_max_tokens`` the controls are read on the sampled
    requests no longer than that (on the shortest where none is), which
    can only make a control's readings smaller."""
    names = list(controls or {})
    out = {"widest_gap": 0.0, "request_mean_gap": 0.0, "tokens": 0,
           "logit_std": 0.0, "mean_gap": 0.0,
           "controls": {name: 0.0 for name in names},
           "controls_request_mean": {name: 0.0 for name in names},
           "control_tokens": 0}
    total = 0.0
    size = lambda r: len(r["prompt"]) + len(r["output"])
    most = max(control_max_tokens or max(map(size, requests)),
               min(map(size, requests)))
    for r in requests:
        toks, first = list(r["prompt"]) + list(r["output"]), len(r["prompt"])
        ref = forward.logits(params, toks, first)
        best = jnp.max(ref, axis=-1)
        gaps = lambda picked: best - jnp.take_along_axis(
            ref, picked[:, None], axis=-1)[:, 0]
        mine = gaps(jnp.asarray(r["output"], jnp.int32))
        out["widest_gap"] = max(out["widest_gap"], float(jnp.max(mine)))
        out["request_mean_gap"] = max(out["request_mean_gap"],
                                      float(jnp.mean(mine)))
        total += float(jnp.sum(mine))
        out["tokens"] += len(r["output"])
        out["logit_std"] = float(jnp.std(ref))
        if names and size(r) <= most:
            out["control_tokens"] += len(r["output"])
        for name in names if size(r) <= most else ():
            low = controls[name].logits(params, toks, first)
            theirs = gaps(jnp.argmax(low, axis=-1))
            out["controls"][name] = max(out["controls"][name],
                                        float(jnp.max(theirs)))
            out["controls_request_mean"][name] = max(
                out["controls_request_mean"][name], float(jnp.mean(theirs)))
    out["mean_gap"] = total / max(out["tokens"], 1)
    return out
