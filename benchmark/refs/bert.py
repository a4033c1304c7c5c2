"""BERT encoder (Devlin et al. 2019) with the masked-language-model head:
word + position + segment embeddings and a layer norm; L post-layer-norm
blocks ``x = ln(x + attn(x)); x = ln(x + mlp(x))`` with erf GELU; the head
is dense + GELU + layer norm + a decoder tied to the word embeddings plus
a bias. The loss is the mean negative log-likelihood over the scored
positions (label != -100).

Parameters use the layout the system under test is handed.
"""

import math

import jax
import jax.numpy as jnp

from .gpt_neox import layer_norm
from .init import Spec
from .numerics import F32


def dims(cfg: dict) -> dict:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"D": D, "H": H, "Dh": D // H, "F": cfg["intermediate_size"],
            "L": cfg["num_hidden_layers"], "V": cfg["vocab_size"],
            "P": cfg["max_position_embeddings"], "T": cfg["type_vocab_size"],
            "eps": cfg["layer_norm_eps"]}


def leaf_specs(cfg: dict):
    """normal(0, initializer_range) matrices, unit norms, zero biases."""
    d = dims(cfg)
    D, F, L, V = d["D"], d["F"], d["L"], d["V"]
    std = cfg.get("initializer_range", 0.02)
    n = lambda *s: Spec(s, std)
    ones = lambda *s: Spec(s, const=1.0)
    zeros = lambda *s: Spec(s)
    return {
        "embed": {"word": n(V, D), "pos": n(d["P"], D), "type": n(d["T"], D),
                  "ln_w": ones(D), "ln_b": zeros(D)},
        "layers": {
            "attn_qkvw": n(L, D, 3 * D), "attn_qkvb": zeros(L, 3 * D),
            "attn_ow": n(L, D, D), "attn_ob": zeros(L, D),
            "attn_nw": ones(L, D), "attn_nb": zeros(L, D),
            "inter_w": n(L, D, F), "inter_b": zeros(L, F),
            "output_w": n(L, F, D), "output_b": zeros(L, D),
            "norm_w": ones(L, D), "norm_b": zeros(L, D),
        },
        "pooler": {"w": n(D, D), "b": zeros(D)},
        "mlm": {"w": n(D, D), "b": zeros(D), "ln_w": ones(D), "ln_b": zeros(D),
                "bias": zeros(V)},
    }


def make(cfg: dict, num=F32):
    d = dims(cfg)
    D, H, Dh = d["D"], d["H"], d["Dh"]

    def embed(outer, batch):
        ids = batch[0]
        e = outer["embed"]
        x = (jnp.take(e["word"].astype(jnp.float32), ids, axis=0)
             + e["pos"][: ids.shape[1]] + e["type"][0])
        return layer_norm(x, e["ln_w"], e["ln_b"], d["eps"])

    def layer(p, x):
        R, S, _ = x.shape
        qkv = num.dot(x, p["attn_qkvw"]) + p["attn_qkvb"]
        q, k, v = (t.reshape(R, S, H, Dh).transpose(0, 2, 1, 3)
                   for t in jnp.split(qkv, 3, axis=-1))
        s = num.dot(q, jnp.swapaxes(k, -1, -2)) / math.sqrt(Dh)
        ctx = num.dot(jax.nn.softmax(s, axis=-1), v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(R, S, D)
        x = layer_norm(x + num.dot(ctx, p["attn_ow"]) + p["attn_ob"],
                       p["attn_nw"], p["attn_nb"], d["eps"])
        h = jax.nn.gelu(num.dot(x, p["inter_w"]) + p["inter_b"], approximate=False)
        return layer_norm(x + num.dot(h, p["output_w"]) + p["output_b"],
                          p["norm_w"], p["norm_b"], d["eps"])

    def head_logits(outer, x):
        m = outer["mlm"]
        h = jax.nn.gelu(num.dot(x, m["w"]) + m["b"], approximate=False)
        h = layer_norm(h, m["ln_w"], m["ln_b"], d["eps"])
        return num.dot(h, outer["embed"]["word"].astype(jnp.float32).T) + m["bias"]

    def head_loss(outer, x, batch):
        labels = batch[1]
        scored = labels != -100
        logp = jax.nn.log_softmax(head_logits(outer, x), axis=-1)
        nll = -jnp.take_along_axis(logp, jnp.where(scored, labels, 0)[..., None],
                                   axis=-1)[..., 0]
        return jnp.sum(jnp.where(scored, nll, 0.0)), jnp.sum(scored).astype(jnp.float32)

    return embed, layer, head_loss, head_logits
