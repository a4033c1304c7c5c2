"""Falcon-H1 (tiiuae, huggingface.co/tiiuae/Falcon-H1-34B-Instruct), the
plain forward in float32: every layer runs causal attention AND a Mamba-2
state-space mixer side by side on one normed input and sums them, then a
gated SiLU feed-forward; RMSNorm; a muP multiplier on every branch; no
bias but the convolution's. With ``u = RMSNorm(x; w_1)``:

    state-space   p = ((u ssm_in) W_in) * mu, mu = ssm_multipliers over the
                  segments [z d_ssm | x d_ssm | B G N | C G N | dt heads];
                  xBC <- SiLU(conv1d(xBC) + b), depthwise, causal, d_conv
                  taps; per head h, in group g(h) = h // (heads / G):
                  dl_t = softplus(dt_t + dt_bias), a_t = exp(-dl_t exp(A_log)),
                  H_t = a_t H_{t-1} + dl_t x_t (x) B_t   (head_dim x N)
                  y_t = H_t C_t + D x_t, computed HERE AS THE RECURRENCE,
                  one position at a time;
                  y <- RMSNorm over each group's channels of y * SiLU(z);
                  ssm = (y W_out) ssm_out
    attention     ut = u attn_in; q = ut W_q; k = (ut W_k) key_mult;
                  v = ut W_v; rotary (half-split, theta) on q, k; causal
                  softmax(q k^T / sqrt(head_dim)) v, key head j serving
                  queries j H/Hkv ..; attn = (ctx W_o) attn_out
    x <- x + ssm + attn;  m = RMSNorm(x; w_2)
    x <- x + (W_down((W_up m) * SiLU((W_gate m) mlp_mult[0]))) mlp_mult[1]
    x_0 = E[tok] embedding_multiplier; logits = (RMSNorm(x_L) W_head) lm_head_mult

Departures and assumed constants are listed in
``configs/falcon-h1-34b.json`` (``assumed``). Parameters use the layout the
system under test is handed: per-layer tensors stacked on a leading axis
(``mamba_attn``), projections as (in, out), the query, key and value
projections side by side in one, the convolution's taps as (d_conv,
channels) with the LAST tap on the current input.

``make(cfg, skip="ssm")`` and ``skip="attn"`` are controls: the same
forward with that branch left out.
"""

import math
import types

import jax
import jax.numpy as jnp
import numpy as np

from .init import Spec
from .numerics import F32

PAD_TO = 256          # a request's length is padded up to a multiple of this
HEAD_BLOCK = 32640    # columns of the output head cast to float32 at once


class PerHead(tuple):
    """One constant a head, the same in every layer, as a ``Spec``'s
    ``const``: hashable (a Spec is a static argument of the jitted maker)
    and an array to ``jnp.full``."""

    def __jax_array__(self):
        return jnp.asarray(tuple(self), jnp.float32)


def dims(cfg: dict) -> dict:
    Hs, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    assert Hs * P == cfg["mamba_d_ssm"], "heads x head size is d_ssm"
    return {"D": cfg["hidden_size"], "F": cfg["intermediate_size"],
            "V": cfg["vocab_size"], "H": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "Dh": cfg["head_dim"],
            "eps": cfg["rms_norm_eps"], "theta": float(cfg["rope_theta"]),
            "L": cfg["num_layers"], "Hs": Hs, "P": P, "G": G, "N": N,
            "K": cfg["mamba_d_conv"], "d_ssm": Hs * P,
            "conv_dim": Hs * P + 2 * G * N,
            "proj_dim": 2 * Hs * P + 2 * G * N + Hs}


def leaf_specs(cfg: dict):
    """How every weight starts (``assumed.weights`` in the configuration's
    file says why): normal(0, std) with one std a tensor from the
    configuration's ``weights`` block; every norm weight 1; ``A_log`` =
    log(1 .. heads), ``D`` = 1, ``dt_bias`` the inverse softplus of step
    sizes spaced geometrically over the heads from ``dt_min`` to
    ``dt_max``; the convolution's bias 0."""
    d, w = dims(cfg), cfg["weights"]
    D, F, V, n = d["D"], d["F"], d["V"], d["L"]
    delta = np.exp(np.linspace(math.log(w["dt_min"]), math.log(w["dt_max"]),
                               d["Hs"]))
    dt_bias = PerHead(float(v) for v in delta + np.log(-np.expm1(-delta)))
    a_log = PerHead(math.log(h) for h in range(1, d["Hs"] + 1))
    return {
        "embed": {"wte": Spec((V, D), w["embed"])},
        "final_norm": {"scale": Spec((D,), const=1.0)},
        "lm_head": Spec((D, V), w["lm_head"]),
        "mamba_attn": {
            "ln1": Spec((n, D), const=1.0), "ln2": Spec((n, D), const=1.0),
            "wqkv": Spec((n, D, (d["H"] + 2 * d["Hkv"]) * d["Dh"]), w["wqkv"]),
            "wo": Spec((n, d["H"] * d["Dh"], D), w["wo"]),
            "ssm": {"w_in": Spec((n, D, d["proj_dim"]), w["ssm_in"]),
                    "conv_w": Spec((n, d["K"], d["conv_dim"]), w["conv"]),
                    "conv_b": Spec((n, d["conv_dim"]), const=0.0),
                    "dt_bias": Spec((n, d["Hs"]), const=dt_bias),
                    "A_log": Spec((n, d["Hs"]), const=a_log),
                    "D": Spec((n, d["Hs"]), const=1.0),
                    "norm": Spec((n, d["d_ssm"]), const=1.0),
                    "w_out": Spec((n, d["d_ssm"], D), w["ssm_out"])},
            "mlp": {"w_gate": Spec((n, D, F), w["mlp_gate"]),
                    "w_up": Spec((n, D, F), w["mlp_up"]),
                    "w_down": Spec((n, F, D), w["mlp_down"])}}}


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def rotary(x, pos, theta):
    """x: (T, H, Dh) at positions ``pos`` (T,); the half-split form over
    all of Dh, angles in float32."""
    half = x.shape[-1] // 2
    freq = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def make(cfg: dict, num=F32, skip: str = None):
    """The model's parts over one sequence x (T, D). ``skip`` ("ssm" or
    "attn") leaves that branch out of every layer: a control."""
    d = dims(cfg)
    D, H, Hkv, Dh, eps = d["D"], d["H"], d["Hkv"], d["Dh"], d["eps"]
    Hs, P, G, N, K = d["Hs"], d["P"], d["G"], d["N"], d["K"]
    mz, mx, mb, mc, mdt = cfg["ssm_multipliers"]
    m_gate, m_down = cfg["mlp_multipliers"]

    def embed(outer, ids):
        return jnp.take(outer["embed"]["wte"].astype(jnp.float32), ids,
                        axis=0) * cfg["embedding_multiplier"]

    def state_space(p, u):
        T = u.shape[0]
        proj = num.dot(u * cfg["ssm_in_multiplier"], p["w_in"])
        ds, gn = d["d_ssm"], G * N
        z = proj[:, :ds] * mz
        xbc = jnp.concatenate([proj[:, ds:2 * ds] * mx,
                               proj[:, 2 * ds:2 * ds + gn] * mb,
                               proj[:, 2 * ds + gn:2 * ds + 2 * gn] * mc], -1)
        dt = proj[:, 2 * ds + 2 * gn:] * mdt
        # the depthwise causal convolution: tap j meets the input K-1-j back
        ext = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
        xbc = jax.nn.silu(sum(p["conv_w"][j] * ext[j:j + T] for j in range(K))
                          + p["conv_b"])
        x = xbc[:, :ds].reshape(T, Hs, P)
        Bm = jnp.repeat(xbc[:, ds:ds + gn].reshape(T, G, N), Hs // G, axis=1)
        Cm = jnp.repeat(xbc[:, ds + gn:].reshape(T, G, N), Hs // G, axis=1)
        delta = jax.nn.softplus(dt + p["dt_bias"])            # (T, Hs)
        a = jnp.exp(-delta * jnp.exp(p["A_log"]))

        def step(S, t):
            x_t, B_t, C_t, dl_t, a_t = t
            S = a_t[:, None, None] * S \
                + (dl_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
            return S, jnp.sum(S * C_t[:, None, :], -1)

        _, y = jax.lax.scan(step, jnp.zeros((Hs, P, N), jnp.float32),
                            (x, Bm, Cm, delta, a))
        y = (y + p["D"][:, None] * x).reshape(T, ds) * jax.nn.silu(z)
        y = rms_norm(y.reshape(T, G, ds // G), 1.0, eps).reshape(T, ds) * p["norm"]
        return num.dot(y, p["w_out"]) * cfg["ssm_out_multiplier"]

    def attention(p, u, pos):
        T = u.shape[0]
        qkv = num.dot(u * cfg["attention_in_multiplier"], p["wqkv"])
        q = rotary(qkv[:, :H * Dh].reshape(T, H, Dh), pos, d["theta"])
        k = rotary((qkv[:, H * Dh:(H + Hkv) * Dh] * cfg["key_multiplier"]
                    ).reshape(T, Hkv, Dh), pos, d["theta"])
        v = qkv[:, (H + Hkv) * Dh:].reshape(T, Hkv, Dh)
        qg = jnp.swapaxes(q.reshape(T, Hkv, H // Hkv, Dh), 0, 1)  # (Hkv, T, g, Dh)
        kh, vh = jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1)     # (Hkv, T, Dh)
        see = pos[None, :] <= pos[:, None]

        def group(a):
            qj, kj, vj = a                                       # one key head
            s = num.dot(jnp.swapaxes(qj, 0, 1), kj.T) / math.sqrt(Dh)  # (g, T, T)
            s = jnp.where(see[None], s, -jnp.inf)
            return num.dot(jax.nn.softmax(s, axis=-1), vj)       # (g, T, Dh)

        ctx = jax.lax.map(group, (qg, kh, vh))                   # (Hkv, g, T, Dh)
        ctx = jnp.moveaxis(ctx, 2, 0).reshape(T, H * Dh)
        return num.dot(ctx, p["wo"]) * cfg["attention_out_multiplier"]

    def layer(p, x):
        """``p`` may hold the served dtype: it is cast here."""
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        u = rms_norm(x, p["ln1"], eps)
        pos = jnp.arange(x.shape[0], dtype=jnp.float32)
        if skip != "ssm":
            x = x + state_space(p["ssm"], u)
        if skip != "attn":
            x = x + attention(p, u, pos)
        m = rms_norm(x, p["ln2"], eps)
        h = num.dot(m, p["mlp"]["w_up"]) * jax.nn.silu(
            num.dot(m, p["mlp"]["w_gate"]) * m_gate)
        return x + num.dot(h, p["mlp"]["w_down"]) * m_down

    def final_norm(outer, x):
        return rms_norm(x, outer["final_norm"]["scale"].astype(jnp.float32), eps)

    def head_block(x, w):
        return num.dot(x, w) * cfg["lm_head_multiplier"]

    return types.SimpleNamespace(embed=embed, layer=layer, final_norm=final_norm,
                                 head_block=head_block, n_layers=d["L"])


class Forward:
    """A model's logits for one request, a layer at a time: each layer's
    weights are cast to float32 when it runs (the whole tree in float32
    would be 21 GB), the output head in blocks of columns; each is one
    jitted program a length, under ``highest`` matmul precision."""

    def __init__(self, model):
        self.model = model
        self._layer = jax.jit(
            lambda stack, i, x: model.layer(
                jax.tree.map(lambda a: a[i], stack), x), donate_argnums=2)
        self._embed = jax.jit(model.embed)
        self._norm = jax.jit(model.final_norm)
        self._head = jax.jit(model.head_block)

    def logits(self, params, tokens, first: int):
        """Logits at positions first-1 .. len(tokens)-2, those that predict
        tokens[first:]. Right padding cannot reach them (causal)."""
        T = len(tokens)
        ids = np.zeros((-(-T // PAD_TO) * PAD_TO,), np.int32)
        ids[:T] = tokens
        outer = {k: v for k, v in params.items() if k != "mamba_attn"}
        with jax.default_matmul_precision("highest"):
            x = self._embed(outer, jnp.asarray(ids))
            for i in range(self.model.n_layers):
                x = self._layer(params["mamba_attn"], i, x)
            x = self._norm(outer, x[first - 1:T - 1])
            head = params["lm_head"]
            return jnp.concatenate(
                [self._head(x, head[:, c:c + HEAD_BLOCK])
                 for c in range(0, head.shape[1], HEAD_BLOCK)], -1)


def served_gaps(forward: Forward, params, requests, controls=None) -> dict:
    """requests: [{"prompt": [...], "output": [...]}]. The widest gap by
    which a served (greedy) token's reference logit lies below the
    reference's best and, for each of ``controls`` ({name: Forward}, the
    reference put in the program's place), the widest gap of the tokens
    the control puts first."""
    out = {"widest_gap": 0.0, "tokens": 0, "logit_std": 0.0,
           "controls": {name: 0.0 for name in (controls or {})}}
    for r in requests:
        toks, first = list(r["prompt"]) + list(r["output"]), len(r["prompt"])
        ref = forward.logits(params, toks, first)
        best = jnp.max(ref, axis=-1)
        gap = lambda picked: float(jnp.max(best - jnp.take_along_axis(
            ref, picked[:, None], axis=-1)[:, 0]))
        out["widest_gap"] = max(out["widest_gap"],
                                gap(jnp.asarray(r["output"], jnp.int32)))
        out["tokens"] += len(r["output"])
        out["logit_std"] = float(jnp.std(ref))
        for name, low in (controls or {}).items():
            picked = jnp.argmax(low.logits(params, toks, first), axis=-1)
            out["controls"][name] = max(out["controls"][name], gap(picked))
    return out
