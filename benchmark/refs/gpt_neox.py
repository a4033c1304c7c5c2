"""GPT-NeoX decoder (Black et al. 2022, github.com/EleutherAI/gpt-neox):
token embedding, L blocks of ``y = x + attn(ln1(x)); y + mlp(ln2(y))``
(or, with ``use_parallel_residual``, ``x + attn(ln1(x)) + mlp(ln2(x))``)
with rotary position embedding on the first ``rotary_pct`` of each head, a final
layer norm and an untied output projection; causal language-model loss.

Parameters use the layout the system under test is handed (per-layer
tensors stacked on a leading axis; the fused qkv projection holds all
query heads, then all key heads, then all value heads).
"""

import math

import jax
import jax.numpy as jnp
from .init import Spec
from .numerics import F32


def dims(cfg: dict) -> dict:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"D": D, "H": H, "Dh": D // H, "F": cfg["intermediate_size"],
            "L": cfg["num_hidden_layers"], "V": cfg["vocab_size"],
            "rot": int(cfg["rotary_pct"] * (D // H)) // 2 * 2,
            "eps": cfg["layer_norm_eps"],
            "parallel": bool(cfg["use_parallel_residual"]),
            "tanh_gelu": cfg["hidden_act"] != "gelu"}


def leaf_specs(cfg: dict):
    """How every weight starts: normal(0, 0.02), output projections scaled
    by 1/sqrt(2L), unit layer norms, zero biases."""
    d = dims(cfg)
    D, F, L, V = d["D"], d["F"], d["L"], d["V"]
    std, out_std = 0.02, 0.02 / math.sqrt(2.0 * L)
    ones = lambda *s: Spec(s, const=1.0)
    zeros = lambda *s: Spec(s)
    return {
        "embed": {"wte": Spec((V, D), std)},
        "layers": {
            "ln1_scale": ones(L, D), "ln1_bias": zeros(L, D),
            "ln2_scale": ones(L, D), "ln2_bias": zeros(L, D),
            "attn": {"wqkv": Spec((L, D, 3 * D), std), "bqkv": zeros(L, 3 * D),
                     "wo": Spec((L, D, D), out_std), "bo": zeros(L, D)},
            "mlp": {"wi": Spec((L, D, F), std), "bi": zeros(L, F),
                    "wo": Spec((L, F, D), out_std), "bo": zeros(L, D)},
        },
        "final_ln": {"scale": ones(D), "bias": zeros(D)},
        "lm_head": Spec((D, V), std),
    }


def layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def rotary(x, rot):
    """x: (R, H, S, Dh); rotate the first ``rot`` dims of each head by
    position (the half-split form of GPT-NeoX)."""
    S, half = x.shape[2], rot // 2
    freq = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def make(cfg: dict, num=F32):
    """(embed, layer, head_loss, head_logits) over the layout above."""
    d = dims(cfg)
    D, H, Dh = d["D"], d["H"], d["Dh"]

    def embed(outer, batch):
        """batch = (tokens of seq+1 ids,): the inputs are all but the last."""
        return jnp.take(outer["embed"]["wte"].astype(jnp.float32),
                        batch[0][:, :-1], axis=0)

    def layer(p, x):
        R, S, _ = x.shape
        a_in = layer_norm(x, p["ln1_scale"], p["ln1_bias"], d["eps"])
        qkv = num.dot(a_in, p["attn"]["wqkv"]) + p["attn"]["bqkv"]
        q, k, v = (t.reshape(R, S, H, Dh).transpose(0, 2, 1, 3)
                   for t in jnp.split(qkv, 3, axis=-1))
        q, k = rotary(q, d["rot"]), rotary(k, d["rot"])
        s = num.dot(q, jnp.swapaxes(k, -1, -2)) / math.sqrt(Dh)
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
        ctx = num.dot(jax.nn.softmax(s, axis=-1), v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(R, S, D)
        attn = num.dot(ctx, p["attn"]["wo"]) + p["attn"]["bo"]
        if not d["parallel"]:
            x = x + attn
        m_in = layer_norm(x, p["ln2_scale"], p["ln2_bias"], d["eps"])
        h = jax.nn.gelu(num.dot(m_in, p["mlp"]["wi"]) + p["mlp"]["bi"],
                        approximate=d["tanh_gelu"])
        mlp = num.dot(h, p["mlp"]["wo"]) + p["mlp"]["bo"]
        return x + attn + mlp if d["parallel"] else x + mlp

    def head_logits(outer, x):
        x = layer_norm(x, outer["final_ln"]["scale"], outer["final_ln"]["bias"],
                       d["eps"])
        return num.dot(x, outer["lm_head"])

    def head_loss(outer, x, batch):
        """(sum of the next-token negative log-likelihoods, their count)."""
        targets = batch[0][:, 1:]
        logp = jax.nn.log_softmax(head_logits(outer, x), axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return jnp.sum(nll), jnp.float32(nll.size)

    return embed, layer, head_loss, head_logits
