"""EvaByte (huggingface.co/EvaByte/EvaByte), the plain forward in float32:
a byte-level decoder of identical layers, each EVA attention and a gated
SiLU feed-forward; RMSNorm that scales by ``1 + w``; no bias; the residual
stream in float32; a head of ``num_pred_heads x vocab`` columns.

With ``norm(x; w) = x / sqrt(mean(x^2) + eps) (1 + w)``, ``s = 1 /
sqrt(head_dim)``, positions ``i`` from 0, ``W = window_size``, ``c =
chunk_size``, ``w(i) = i // W``, chunk ``m`` = positions ``c m .. c m + c
- 1``:

    u = norm(x; w_1); per head h: q_i, k_i, v_i the head's columns of
    u W_q, u W_k, u W_v; rotary (half-split over all of head_dim, theta,
    angles in float32) on q_i and k_i at position i
    summaries, from the ROTATED keys, for every chunk m whose c positions
    exist:  kbar_m = sum_{j in m} softmax_{j in m}(s k_j . mu_h) k_j
            vbar_m = sum_{j in m} softmax_{j in m}(s k_j . phi_h) v_j
    E_i = {j : W w(i) <= j <= i}        the window's exact keys
    S_i = {m : m < (W / c) w(i)}        every chunk of every window before
    o_i = softmax over E_i and S_i together of (s q_i . k_j, s q_i . kbar_m)
          applied to (v_j, vbar_m)
    x <- x + concat_h(o_i) W_o;  m = norm(x; w_2)
    x <- x + W_down(SiLU(W_gate m) * (W_up m))
    x_0 = E[byte];  logits_i = norm(x_L; w_f) W_head, block p of the
    columns scoring byte i + 1 + p

HERE EVERY QUERY'S SETS ARE BUILT OUTRIGHT from the whole sequence's keys
and summaries: ``E_i`` by a mask over ALL the keys of the query's window
(a slice of the sequence: the rule leaves no key outside it), ``S_i`` by a
mask over the summaries of the WHOLE sequence; no pages, no reused rows,
no chunked prefill, no kernels. Queries go in blocks of one window's
positions so that 32,768 positions fit. Departures and assumed constants
are listed in ``configs/evabyte-6.5b.json`` (``assumed``). Parameters use
the layout the system under test is handed: per-layer tensors stacked on
a leading axis (``eva``), projections as (in, out), the query, key and
value projections side by side in one.

``make(cfg, control="nosum")`` (``S_i`` empty: a program that forgot its
summaries) and ``control="flatpool"`` (``mu = phi = 0``: plain means in
place of the learned pooling) are controls.
"""

import math
import types

import jax
import jax.numpy as jnp
import numpy as np

from .init import Spec
from .numerics import F32

Q_BLOCK = 256     # queries that meet their window's keys and every summary at once


def dims(cfg: dict) -> dict:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    assert cfg["num_key_value_heads"] == H, "one query a key head"
    return {"D": D, "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "H": H, "Dh": cfg.get("head_dim") or D // H,
            "eps": cfg["rms_norm_eps"], "theta": float(cfg["rope_theta"]),
            "L": cfg["num_layers"], "P": cfg["num_pred_heads"],
            "W": cfg["window_size"], "c": cfg["chunk_size"]}


def leaf_specs(cfg: dict):
    """How every weight starts (``assumed.weights`` in the configuration's
    file says why): normal(0, std) with one std a tensor from the
    configuration's ``weights`` block; every norm weight 0 (the norm adds
    the unit offset)."""
    d, w = dims(cfg), cfg["weights"]
    D, F, n, HD = d["D"], d["F"], d["L"], d["H"] * d["Dh"]
    return {
        "embed": {"wte": Spec((d["V"], D), w["embed"])},
        "final_norm": {"scale": Spec((D,), const=0.0)},
        "lm_head": Spec((D, d["P"] * d["V"]), w["lm_head"]),
        "eva": {
            "ln1": Spec((n, D), const=0.0), "ln2": Spec((n, D), const=0.0),
            "wqkv": Spec((n, D, 3 * HD), w["wqkv"]),
            "wo": Spec((n, HD, D), w["wo"]),
            "mu": Spec((n, d["H"], d["Dh"]), w["mu"]),
            "phi": Spec((n, d["H"], d["Dh"]), w["phi"]),
            "mlp": {"w_gate": Spec((n, D, F), w["mlp_gate"]),
                    "w_up": Spec((n, D, F), w["mlp_up"]),
                    "w_down": Spec((n, F, D), w["mlp_down"])}}}


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * (1.0 + w)


def rotary(x, pos, theta):
    """x: (T, H, Dh) at positions ``pos`` (T,); the half-split form over
    all of Dh, angles in float32."""
    half = x.shape[-1] // 2
    freq = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def make(cfg: dict, num=F32, control: str = None):
    """The model's parts over one sequence x (T, D). ``control``: "nosum"
    leaves every ``S_i`` empty, "flatpool" pools by plain means."""
    d = dims(cfg)
    H, Dh, eps, W, c = d["H"], d["Dh"], d["eps"], d["W"], d["c"]
    s = 1.0 / math.sqrt(Dh)

    def embed(outer, ids):
        return jnp.take(outer["embed"]["wte"].astype(jnp.float32), ids, axis=0)

    def attention(p, u):
        T = u.shape[0]
        pos = jnp.arange(T, dtype=jnp.int32)
        qkv = num.dot(u, p["wqkv"])
        q, k, v = (qkv[:, i * H * Dh:(i + 1) * H * Dh].reshape(T, H, Dh)
                   for i in range(3))
        q = rotary(q, pos.astype(jnp.float32), d["theta"])
        k = rotary(k, pos.astype(jnp.float32), d["theta"])
        # one summary a whole chunk and head
        n = T // c
        kc, vc = (t[:n * c].reshape(n, c, H, Dh) for t in (k, v))
        mu, phi = ((0.0 * p[name] if control == "flatpool" else p[name])
                   for name in ("mu", "phi"))
        pool = lambda vec: jax.nn.softmax(
            s * jnp.sum(kc * vec, -1), axis=1)[..., None]      # (n, c, H, 1)
        kbar = jnp.sum(pool(mu) * kc, 1)                        # (n, H, Dh)
        vbar = jnp.sum(pool(phi) * vc, 1)
        kh, vh = jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1)  # (H, T, Dh)
        kbh, vbh = jnp.swapaxes(kbar, 0, 1), jnp.swapaxes(vbar, 0, 1)
        chunk = jnp.arange(n, dtype=jnp.int32)

        def block(a):
            qb, pb = a                              # (B, H, Dh), (B,): one window's
            qh = jnp.swapaxes(qb, 0, 1)                         # (H, B, Dh)
            wq = pb // W
            # the keys of the block's window, all W of them, and of those
            # the ones at or before each query
            start = W * wq[0]
            kw = jax.lax.dynamic_slice_in_dim(kh, start, W, 1)
            vw = jax.lax.dynamic_slice_in_dim(vh, start, W, 1)
            at = start + jnp.arange(W, dtype=jnp.int32)
            exact = at[None, :] <= pb[:, None]                 # E_i (B, W)
            behind = chunk[None, :] < ((W // c) * wq)[:, None]  # S_i (B, n)
            if control == "nosum":
                behind = jnp.zeros_like(behind)
            se = jnp.where(exact[None], s * num.dot(qh, jnp.swapaxes(kw, 1, 2)),
                           -jnp.inf)
            ss = jnp.where(behind[None], s * num.dot(qh, jnp.swapaxes(kbh, 1, 2)),
                           -jnp.inf)
            pr = jax.nn.softmax(jnp.concatenate([se, ss], -1), axis=-1)
            o = num.dot(pr[..., :W], vw) + num.dot(pr[..., W:], vbh)
            return jnp.swapaxes(o, 0, 1)                        # (B, H, Dh)

        B = min(Q_BLOCK, W)
        assert T % W == 0 and W % B == 0, (T, W, B)
        o = jax.lax.map(block, (q.reshape(T // B, B, H, Dh),
                                pos.reshape(T // B, B)))
        return num.dot(o.reshape(T, H * Dh), p["wo"])

    def layer(p, x):
        """``p`` may hold the served dtype: it is cast here."""
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        x = x + attention(p, norm(x, p["ln1"], eps))
        m = norm(x, p["ln2"], eps)
        h = jax.nn.silu(num.dot(m, p["mlp"]["w_gate"])) \
            * num.dot(m, p["mlp"]["w_up"])
        return x + num.dot(h, p["mlp"]["w_down"])

    def head(outer, x):
        x = norm(x, outer["final_norm"]["scale"].astype(jnp.float32), eps)
        return num.dot(x, outer["lm_head"].astype(jnp.float32))

    return types.SimpleNamespace(embed=embed, layer=layer, head=head,
                                 n_layers=d["L"], vocab=d["V"], window=W)


class Forward:
    """A model's logits for one request, a layer at a time: each layer's
    weights are cast to float32 when it runs; each part is one jitted
    program a length, under ``highest`` matmul precision."""

    def __init__(self, model):
        self.model = model
        self._layer = jax.jit(
            lambda stack, i, x: model.layer(
                jax.tree.map(lambda a: a[i], stack), x), donate_argnums=2)
        self._embed = jax.jit(model.embed)
        self._head = jax.jit(model.head)

    def logits(self, params, tokens, first: int):
        """Logits (all ``num_pred_heads x vocab`` columns) at positions
        first-1 .. len(tokens)-2, those whose first block predicts
        tokens[first:]. Right padding cannot reach them: a padded
        position is seen, exactly or through its chunk's summary, only by
        positions after it."""
        T, W = len(tokens), self.model.window
        ids = np.zeros((-(-T // W) * W,), np.int32)       # whole windows
        ids[:T] = tokens
        outer = {k: v for k, v in params.items() if k != "eva"}
        with jax.default_matmul_precision("highest"):
            x = self._embed(outer, jnp.asarray(ids))
            for i in range(self.model.n_layers):
                x = self._layer(params["eva"], i, x)
            return self._head(outer, x[first - 1:T - 1])


def served_gaps(forward: Forward, params, requests, controls=None) -> dict:
    """requests: [{"prompt": [...], "output": [...]}]. The widest gap by
    which a served (greedy) byte's reference logit lies below the
    reference's best in the head's FIRST block (the served one) and, for
    each of ``controls`` ({name: Forward}, the reference put in the
    program's place), the widest gap of the bytes the control puts
    first."""
    V = forward.model.vocab
    out = {"widest_gap": 0.0, "tokens": 0, "logit_std": 0.0,
           "controls": {name: 0.0 for name in (controls or {})}}
    for r in requests:
        toks, first = list(r["prompt"]) + list(r["output"]), len(r["prompt"])
        ref = forward.logits(params, toks, first)[:, :V]
        best = jnp.max(ref, axis=-1)
        gap = lambda picked: float(jnp.max(best - jnp.take_along_axis(
            ref, picked[:, None], axis=-1)[:, 0]))
        out["widest_gap"] = max(out["widest_gap"],
                                gap(jnp.asarray(r["output"], jnp.int32)))
        out["tokens"] += len(r["output"])
        out["logit_std"] = float(jnp.std(ref))
        for name, low in (controls or {}).items():
            picked = jnp.argmax(low.logits(params, toks, first)[:, :V], axis=-1)
            out["controls"][name] = max(out["controls"][name], gap(picked))
    return out
