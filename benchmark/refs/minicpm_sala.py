"""MiniCPM-SALA (openbmb, huggingface.co/openbmb/MiniCPM-SALA), the plain
forward in float32: a stack of ``minicpm4`` layers (MiniCPM4's InfLLM-v2
block-sparse attention) and ``lightning-attn`` layers (decayed linear
attention), RMSNorm, a gated SiLU feed-forward, muP scalings, no bias.

    x_0 = scale_emb E[tok];  x <- x + r Mixer(RMSNorm(x));  x <- x + r FFN(RMSNorm(x))
    r = scale_depth / sqrt(32) with the PUBLISHED depth, whatever is held here
    logits = W_head RMSNorm(x_L) / (hidden_size / dim_model_base)

``lightning-attn``: q, k, v = 32 heads of 128; q, k RMS-normed per head,
then rotary (theta 10000, the half-split form); per head h with decay
exp(-s_h), s_h = 2^(-8 (h+1) / 32): ``S_t = lam S_{t-1} + k_t^T v_t``,
``o_t = (q_t / sqrt(128)) S_t``, computed HERE AS THE RECURRENCE, one
position at a time; o RMS-normed per head; y = W_o (o * sigmoid(W_g u)).

``minicpm4``: 32 query heads in 2 groups of 16, one key head a group; q, k
RMS-normed per head, no rotary. For the query at position t: pooled keys
``Kbar_j = mean(K[16 j : 16 j + 32])`` over the windows that lie wholly at
or before t; ``p_h = softmax_j(q_h . Kbar_j / sqrt(128))``; group score
``a_j = sum_h p_h[j]``; block score ``b_m = max a_j`` over the windows that
touch tokens 64 m .. 64 m + 63 (j = 4 m - 1 .. 4 m + 3); block 0 and the 32
blocks that end at t's own are forced in, the best of b fill the rest of 64;
causal softmax attention of the group's heads over the tokens of those
blocks; y = W_o (o * sigmoid(W_g u)). ONE causal rule: a query that sees
``dense_len`` tokens or fewer attends to all of them. Selection is PER
POSITION here, in blocks of positions so that a 33k-token request fits.

Departures and assumed constants are listed in
``configs/minicpm-sala.json`` (``assumed``). Parameters use the layout the
system under test is handed: per-layer tensors stacked BY KIND on a leading
axis (``sparse``, ``lightning``), projections as (in, out), the query,
key and value projections side by side in one (all query heads, then the
key heads, then the value heads).
"""

import math
import types

import jax
import jax.numpy as jnp
import numpy as np

from .init import Spec
from .numerics import F32

KIND = {"minicpm4": "sparse", "lightning-attn": "lightning"}
ROWS_MOST = 128       # positions a block of the sparse layer's queries holds, at most
PAD_TO = 1024         # a request's length is padded up to a multiple of this


def rows_for(d: dict) -> int:
    """Query positions a block holds: no block straddles ``dense_len``."""
    return math.gcd(ROWS_MOST, d["dense_len"])


def pad_to_for(d: dict) -> int:
    """Whole blocks of rows and whole pages; at the published sizes 1024,
    so that a run's requests share few program shapes."""
    least = math.lcm(rows_for(d), d["bs"])
    return PAD_TO if PAD_TO % least == 0 and d["dense_len"] >= PAD_TO else least


def dims(cfg: dict) -> dict:
    sc = cfg["sparse_config"]
    return {"D": cfg["hidden_size"], "F": cfg["intermediate_size"],
            "V": cfg["vocab_size"], "H": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "Dh": cfg["head_dim"],
            "eps": cfg["rms_norm_eps"], "theta": float(cfg["rope_theta"]),
            "kinds": [KIND[m] for m in cfg["mixer_types"]],
            "scale_emb": float(cfg["scale_emb"]),
            "r": cfg["scale_depth"] / math.sqrt(cfg["num_hidden_layers"]),
            "logit_div": cfg["hidden_size"] / cfg["dim_model_base"],
            "bs": sc["block_size"], "topk": sc["topk"], "ks": sc["kernel_size"],
            "st": sc["kernel_stride"], "init": sc["init_blocks"],
            "local": sc["window_size"] // sc["block_size"],
            "dense_len": sc["dense_len"]}


def leaf_specs(cfg: dict):
    """How every weight starts (``assumed.weights`` in the configuration's
    file says why): normal(0, 0.02); the sparse layers' projection of q,
    k and v wider (q and k are normed, so this widens v alone) and their
    q/k norm gains above one, so that their attention is peaked and
    weighs in the stream; the other norms at one."""
    d, w = dims(cfg), cfg["weights"]
    D, F, V, H, Hkv, Dh = d["D"], d["F"], d["V"], d["H"], d["Hkv"], d["Dh"]
    std = w["std"]

    def kind(n, kv, sparse):
        p = {"ln1": Spec((n, D), const=1.0), "ln2": Spec((n, D), const=1.0),
             "wqkv": Spec((n, D, (H + 2 * kv) * Dh),
                          w["sparse_qkv_std"] if sparse else std),
             "wg": Spec((n, D, H * Dh), std), "wo": Spec((n, H * Dh, D), std),
             "q_norm": Spec((n, Dh), const=w["sparse_qk_gain"] if sparse else 1.0),
             "k_norm": Spec((n, Dh), const=w["sparse_qk_gain"] if sparse else 1.0),
             "mlp": {"w_gate": Spec((n, D, F), std), "w_up": Spec((n, D, F), std),
                     "w_down": Spec((n, F, D), std)}}
        if not sparse:
            p["o_norm"] = Spec((n, H * Dh), const=1.0)
        return p

    return {"embed": {"wte": Spec((V, D), std)},
            "final_norm": {"scale": Spec((D,), const=1.0)},
            "lm_head": Spec((D, V), std),
            "sparse": kind(d["kinds"].count("sparse"), Hkv, True),
            "lightning": kind(d["kinds"].count("lightning"), H, False)}


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def rotary(x, pos, theta):
    """x: (T, H, Dh) at positions ``pos`` (T,); the half-split form over
    all of Dh."""
    half = x.shape[-1] // 2
    freq = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def make(cfg: dict, num=F32, selection: str = "topk", probe=None):
    """The model's parts over one sequence x (T, D), T a multiple of
    ``pad_to_for``.
    ``selection='first'`` is the control that skips the selection (every
    query beyond ``dense_len`` reads the first ``topk`` blocks and its own
    local ones). ``probe(q (R, H, Dh), kbar (Hkv, J, Dh), pos (R,)) ->
    blocks (R, Hkv, topk)``, if given, is another selector, fed this
    forward's own q and pooled keys: the layer then also returns how many
    (position, group) selections it makes differently."""
    d = dims(cfg)
    D, H, Hkv, Dh, eps = d["D"], d["H"], d["Hkv"], d["Dh"], d["eps"]
    G = H // Hkv
    bs, st, ks, topk = d["bs"], d["st"], d["ks"], d["topk"]
    w = bs // st
    ROWS = rows_for(d)

    def embed(outer, ids):
        return jnp.take(outer["embed"]["wte"].astype(jnp.float32), ids,
                        axis=0) * d["scale_emb"]

    def ffn(p, u):
        h = jax.nn.silu(num.dot(u, p["w_gate"])) * num.dot(u, p["w_up"])
        return num.dot(h, p["w_down"])

    def in_blocks(fn, *rows):
        """fn over blocks of ``pad_to`` positions (a 33k-token request's
        feed-forward intermediate would be 2 GB whole)."""
        B = pad_to_for(d)
        split = lambda a: a.reshape(a.shape[0] // B, B, *a.shape[1:])
        out = jax.lax.map(lambda a: fn(*a), tuple(split(a) for a in rows))
        return jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), out)

    def project(p, x, kv, rotate):
        """q, k (normed per head, rotated by position if ``rotate``), v."""
        def rows(xb, pos):
            B = xb.shape[0]
            qkv = num.dot(rms_norm(xb, p["ln1"], eps), p["wqkv"])
            q = rms_norm(qkv[:, :H * Dh].reshape(B, H, Dh), p["q_norm"], eps)
            k = rms_norm(qkv[:, H * Dh:(H + kv) * Dh].reshape(B, kv, Dh),
                         p["k_norm"], eps)
            if rotate:
                q, k = rotary(q, pos, d["theta"]), rotary(k, pos, d["theta"])
            return q, k, qkv[:, (H + kv) * Dh:].reshape(B, kv, Dh)

        return in_blocks(rows, x, jnp.arange(x.shape[0], dtype=jnp.float32))

    def lightning(p, x):
        T = x.shape[0]
        q, k, v = project(p, x, H, True)
        lam = jnp.exp(-jnp.exp2(-8.0 * jnp.arange(1, H + 1) / H))[:, None, None]

        def step(S, qkv):
            q_t, k_t, v_t = qkv                              # (H, Dh)
            S = lam * S + k_t[:, :, None] * v_t[:, None, :]
            return S, jnp.sum((q_t / math.sqrt(Dh))[:, :, None] * S, axis=1)

        _, o = jax.lax.scan(step, jnp.zeros((H, Dh, Dh), jnp.float32), (q, k, v))
        o = rms_norm(o, p["o_norm"].reshape(H, Dh), eps)
        return o.reshape(T, H * Dh), jnp.zeros((2,), jnp.int32)

    def attend(qb, k_sel, v_sel, see):
        """qb (R, Hkv, G, Dh) over its own keys (R, Hkv, K, Dh)."""
        s = num.dot(qb, jnp.swapaxes(k_sel, -1, -2)) / math.sqrt(Dh)
        s = jnp.where(see[:, :, None, :], s, -jnp.inf)
        return num.dot(jax.nn.softmax(s, axis=-1), v_sel)

    def sparse(p, x):
        T = x.shape[0]
        q, k, v = project(p, x, Hkv, False)
        n_dense = min(T, d["dense_len"]) // ROWS
        qb = q.reshape(T // ROWS, ROWS, Hkv, G, Dh)
        pos = jnp.arange(T, dtype=jnp.int32).reshape(T // ROWS, ROWS)
        kh, vh = jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1)   # (Hkv, T, Dh)

        def dense_rows(a):
            qr, pr = a
            kd, vd = kh[:, :n_dense * ROWS], vh[:, :n_dense * ROWS]
            see = jnp.arange(n_dense * ROWS)[None, :] <= pr[:, None]
            return attend(qr, kd[None], vd[None],
                          jnp.broadcast_to(see[:, None, :], (ROWS, Hkv, see.shape[1])))

        out = [jax.lax.map(dense_rows, (qb[:n_dense], pos[:n_dense]))]
        differ = jnp.zeros((2,), jnp.int32)
        if T // ROWS > n_dense:
            M = T // bs
            half = jnp.sum(k.reshape(T // st, st, Hkv, Dh), axis=1)
            kbar = (half[:-1] + half[1:]) / ks                   # (T/st - 1, ...)
            J = M * w
            kbar = jnp.swapaxes(jnp.pad(kbar, ((0, J - kbar.shape[0]), (0, 0), (0, 0))), 0, 1)
            kb, vb = kh.reshape(Hkv, M, bs, Dh), vh.reshape(Hkv, M, bs, Dh)
            m_ids = jnp.arange(M, dtype=jnp.int32)
            heads = jnp.arange(Hkv)[None, :, None]

            def select(qr, pr):
                """blocks (R, Hkv, topk) and whether each counts."""
                bt = (pr // bs)[:, None, None]
                past = m_ids[None, None, :] <= bt
                forced = (m_ids[None, None, :] < d["init"]) | (
                    past & (m_ids[None, None, :] > bt - d["local"]))
                if selection == "first":
                    score = jnp.broadcast_to(jnp.where(
                        past, -m_ids[None, None, :].astype(jnp.float32), -jnp.inf),
                        (ROWS, Hkv, M))
                else:
                    vis = (jnp.arange(J) * st + ks - 1)[None, :] <= pr[:, None]
                    s = jnp.einsum("rhgd,hjd->rhgj", qr, kbar,
                                   precision="highest") / math.sqrt(Dh)
                    a = jnp.sum(jnp.where(
                        vis[:, None, None, :],
                        jax.nn.softmax(jnp.where(vis[:, None, None, :], s, -jnp.inf), -1),
                        0.0), axis=2)
                    a = jnp.where(vis[:, None, :], a, -1.0)       # (R, Hkv, J)
                    own = jnp.max(a.reshape(ROWS, Hkv, M, w), -1)
                    before = jnp.concatenate(
                        [jnp.full((ROWS, Hkv, 1), -1.0), a[..., w - 1::w][..., :-1]], -1)
                    score = jnp.where(past, jnp.maximum(own, before), -jnp.inf)
                score = jnp.where(forced, jnp.inf, score)
                vals, idx = jax.lax.top_k(score, topk)
                return idx, vals > -jnp.inf

            def sparse_rows(a):
                qr, pr = a
                idx, ok = select(qr, pr)
                k_sel = kb[heads, idx].reshape(ROWS, Hkv, topk * bs, Dh)
                v_sel = vb[heads, idx].reshape(ROWS, Hkv, topk * bs, Dh)
                key_pos = (idx[..., None] * bs + jnp.arange(bs)).reshape(ROWS, Hkv, -1)
                see = jnp.repeat(ok, bs, axis=-1) & (key_pos <= pr[:, None, None])
                n_diff = jnp.zeros((2,), jnp.int32)
                if probe is not None:
                    other = probe(qr.reshape(ROWS, H, Dh), kbar, pr)
                    mine = jnp.sort(jnp.where(ok, idx, -1), -1)
                    same = jnp.all(mine == jnp.sort(other, -1), -1)
                    n_diff = jnp.stack([jnp.sum(~same), jnp.int32(same.size)])
                return attend(qr, k_sel, v_sel, see), n_diff

            o_sp, n_diff = jax.lax.map(sparse_rows, (qb[n_dense:], pos[n_dense:]))
            out.append(o_sp)
            differ = jnp.sum(n_diff, axis=0)
        o = jnp.concatenate(out, 0).reshape(T, Hkv, G, Dh)
        return o.reshape(T, H * Dh), differ

    def layer(kind, p, x):
        """-> (x_out, [selections that differ from the probe's, made]).
        ``p`` may hold the served dtype: a weight is cast where it is
        used."""
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        o, differ = (sparse if kind == "sparse" else lightning)(p, x)

        def rows(xb, ob):
            u = rms_norm(xb, p["ln1"], eps)
            y = num.dot(ob * jax.nn.sigmoid(num.dot(u, p["wg"])), p["wo"])
            xb = xb + d["r"] * y
            return xb + d["r"] * ffn(p["mlp"], rms_norm(xb, p["ln2"], eps))

        return in_blocks(rows, x, o), differ

    def head_logits(outer, x):
        x = rms_norm(x, outer["final_norm"]["scale"].astype(jnp.float32), eps)
        return num.dot(x, outer["lm_head"]) / d["logit_div"]

    return types.SimpleNamespace(embed=embed, layer=layer,
                                 head_logits=head_logits, kinds=d["kinds"],
                                 pad_to=pad_to_for(d))


class Forward:
    """A model's logits for one request, a layer at a time: each layer's
    weights are cast to float32 when it runs (the whole tree in float32
    would be 20 GB) and each layer is one jitted program a (kind, length)."""

    def __init__(self, model):
        self.model = model
        self._layer = jax.jit(
            lambda kind, stack, i, x: model.layer(
                kind, jax.tree.map(lambda a: a[i], stack), x),
            static_argnums=0, donate_argnums=3)
        self._embed = jax.jit(model.embed)
        self._head = jax.jit(model.head_logits)
        self.differ = np.zeros(2, np.int64)

    def logits(self, params, tokens, first: int):
        """Logits at positions first-1 .. len(tokens)-2, those that predict
        tokens[first:]. Right padding cannot reach them (causal)."""
        T = len(tokens)
        pad = self.model.pad_to
        ids = np.zeros((-(-T // pad) * pad,), np.int32)
        ids[:T] = tokens
        outer = {k: v for k, v in params.items() if k not in ("sparse", "lightning")}
        x = self._embed(outer, jnp.asarray(ids))
        seen = {"sparse": 0, "lightning": 0}
        for kind in self.model.kinds:
            x, differ = self._layer(kind, params[kind], seen[kind], x)
            self.differ += np.asarray(differ)
            seen[kind] += 1
        return self._head(outer, x[first - 1:T - 1])


def served_gaps(forward: Forward, params, requests, controls=None) -> dict:
    """requests: [{"prompt": [...], "output": [...]}]. The widest gap by
    which a served (greedy) token's reference logit lies below the
    reference's best and, for each of ``controls`` ({name: Forward}, the
    reference put in the program's place), the widest gap of the tokens
    the control puts first."""
    out = {"widest_gap": 0.0, "tokens": 0,
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
        for name, low in (controls or {}).items():
            picked = jnp.argmax(low.logits(params, toks, first), axis=-1)
            out["controls"][name] = max(out["controls"][name], gap(picked))
    out["selections_differ"], out["selections"] = (int(n) for n in forward.differ)
    return out
