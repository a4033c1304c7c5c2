"""Fused BERT-style transformer layer, TPU-native.

Capability parity with the reference's CUDA fused transformer
(/root/reference/deepspeed/ops/transformer/transformer.py:
`DeepSpeedTransformerConfig` :95, `DeepSpeedTransformerLayer` :470,
`DeepSpeedTransformerFunction` :155, backed by
csrc/transformer/ds_transformer_cuda.cpp). The CUDA version hand-fuses QKV
gemm / softmax / dropout / layernorm / gelu into per-op kernels and keeps a
per-layer C++ object registry keyed by ``layer_id``.

TPU design: one functional layer whose fwd is written so XLA fuses the
elementwise chain into the matmuls on the MXU, with the attention core
optionally running the Pallas flash kernel (O(S) memory instead of the
(B,H,S,S) scores tensor). The reference's memory-saving knobs map onto
rematerialisation instead of buffer juggling:

  normalize_invertible / attn_dropout_checkpoint / gelu_checkpoint
      -> `jax.checkpoint` around attention / FFN sub-blocks (recompute in
         backward rather than saving intermediates)
  stochastic_mode -> progressive-layer-drop gate: the whole layer is skipped
      with prob 1-theta per call (see runtime/progressive_layer_drop.py)

The per-layer "registry" becomes a jitted-function cache keyed by the config
(`transformer_layer_fn`), which is the XLA-native meaning of "create the
layer object once, reuse across steps".

Param names mirror the reference layer's attributes (attn_qkvw, attn_qkvb,
attn_ow, attn_ob, attn_nw, attn_nb, inter_w, inter_b, output_w, output_b,
norm_w, norm_b — transformer.py:502-525) so checkpoints and module injection
map 1:1. Weight orientation is (in, out) as used by `x @ w`.
"""

import dataclasses
import json
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..pallas.flash_attention import (attention_dispatch, flash_attention,
                                      is_available)
from ..pallas.fused_blocks import add_layer_norm, bias_gelu, layer_norm


class TransformerConfig:
    """Base config (reference transformer.py:18)."""

    def __init__(self, batch_size=-1, hidden_size=-1, intermediate_size=-1,
                 heads=-1, attn_dropout_ratio=-1, hidden_dropout_ratio=-1,
                 num_hidden_layers=-1, initializer_range=-1):
        self.layer_id = -1
        self.batch_size = batch_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.heads = heads
        self.attn_dropout_ratio = attn_dropout_ratio
        self.hidden_dropout_ratio = hidden_dropout_ratio
        self.num_hidden_layers = num_hidden_layers
        self.initializer_range = initializer_range


class DeepSpeedTransformerConfig(TransformerConfig):
    """Reference transformer.py:95 with TPU-relevant extensions.

    fp16 selects bf16 compute here (the TPU half precision); attn_impl picks
    'flash' (Pallas), 'xla' (dense scores — required when an additive
    attention mask is supplied), or 'auto'.
    """

    def __init__(self, batch_size=-1, max_seq_length=-1, hidden_size=-1,
                 intermediate_size=-1, heads=-1, attn_dropout_ratio=-1,
                 hidden_dropout_ratio=-1, num_hidden_layers=-1,
                 initializer_range=-1, local_rank=-1, seed=-1, fp16=False,
                 pre_layer_norm=True, normalize_invertible=False,
                 gelu_checkpoint=False, adjust_init_range=True,
                 attn_dropout_checkpoint=False, stochastic_mode=False,
                 huggingface=False, training=True, attn_impl="auto",
                 interpret=False, layernorm_eps=1e-12):
        super().__init__(
            batch_size,
            hidden_size,
            (intermediate_size if intermediate_size > 0 else 4 * hidden_size),
            heads,
            attn_dropout_ratio,
            hidden_dropout_ratio,
            num_hidden_layers,
            initializer_range,
        )
        self.max_seq_length = max_seq_length
        self.local_rank = local_rank
        self.seed = seed
        self.fp16 = fp16
        self.pre_layer_norm = pre_layer_norm
        self.normalize_invertible = normalize_invertible
        self.gelu_checkpoint = gelu_checkpoint
        self.adjust_init_range = adjust_init_range
        self.attn_dropout_checkpoint = attn_dropout_checkpoint
        self.stochastic_mode = stochastic_mode
        self.huggingface = huggingface
        self.training = training
        self.attn_impl = attn_impl
        self.interpret = interpret  # pallas interpret mode (CPU testing)
        self.layernorm_eps = layernorm_eps

    @property
    def compute_dtype(self):
        return jnp.bfloat16 if self.fp16 else jnp.float32

    @classmethod
    def from_dict(cls, json_object):
        config = cls()
        for key, value in json_object.items():
            config.__dict__[key] = value
        if "intermediate_size" not in json_object and config.hidden_size > 0:
            config.intermediate_size = 4 * config.hidden_size
        return config

    @classmethod
    def from_json_file(cls, json_file):
        with open(json_file, "r", encoding="utf-8") as reader:
            return cls.from_dict(json.loads(reader.read()))

    def _cache_key(self):
        # layer_id is a per-instance stamp (reference keys its C++ registry
        # by it); identical configs must share one compiled executable, so
        # it is excluded here
        return tuple(
            sorted((k, str(v)) for k, v in self.__dict__.items() if k != "layer_id")
        )


def init_transformer_params(rng, config: DeepSpeedTransformerConfig):
    """Initialize one layer's params (reference transformer.py:502-525).

    Output projections get the 1/sqrt(2*num_layers) shrink the reference
    applies when adjust_init_range is set (transformer.py:527-534).
    """
    H, I = config.hidden_size, config.intermediate_size
    std = config.initializer_range if config.initializer_range > 0 else 0.02
    out_std = std
    if config.adjust_init_range and config.num_hidden_layers > 0:
        out_std = std / (2.0 * config.num_hidden_layers) ** 0.5
    ks = jax.random.split(rng, 4)
    f32 = jnp.float32
    return {
        "attn_qkvw": jax.random.normal(ks[0], (H, 3 * H), f32) * std,
        "attn_qkvb": jnp.zeros((3 * H,), f32),
        "attn_ow": jax.random.normal(ks[1], (H, H), f32) * out_std,
        "attn_ob": jnp.zeros((H,), f32),
        "attn_nw": jnp.ones((H,), f32),
        "attn_nb": jnp.zeros((H,), f32),
        "inter_w": jax.random.normal(ks[2], (H, I), f32) * std,
        "inter_b": jnp.zeros((I,), f32),
        "output_w": jax.random.normal(ks[3], (I, H), f32) * out_std,
        "output_b": jnp.zeros((H,), f32),
        "norm_w": jnp.ones((H,), f32),
        "norm_b": jnp.zeros((H,), f32),
    }


def _layer_norm(x, w, b, eps=1e-12):
    # dispatches through the "kernels" config block (ops/kernel_config.py);
    # the XLA fallback is the exact fp32-stats math this function used to
    # inline
    return layer_norm(x, w, b, eps)


def _dropout(x, ratio, rng):
    if rng is None or ratio <= 0.0:
        return x
    keep = jax.random.bernoulli(rng, 1.0 - ratio, x.shape)
    return jnp.where(keep, x / (1.0 - ratio), jnp.zeros_like(x))


def _flash_ok(q, config) -> bool:
    if not (is_available(q) or config.interpret):
        return False
    S = q.shape[1]
    return S % min(128, S) == 0


def _attention_core(q, k, v, config, attention_mask, drop_rng=None):
    """(B, S, nH, Dh) -> (B, S, nH, Dh). Flash path when no mask and no
    attention dropout (flash never materializes the probs tensor)."""
    impl = config.attn_impl
    needs_probs = attention_mask is not None or drop_rng is not None
    if impl == "auto":
        # short sequences: flash's grid runs one k-block per (batch, head,
        # q-block) and the dynamic-loop scalar overhead dominates (~1.7 TF
        # at S=128 vs XLA's batched-GEMM path — hardware-measured, BERT
        # seq128 +27% end-to-end); the dense scores tensor is tiny there —
        # unless the "kernels" config routes the geometry to the dense
        # super-tile kernel, which packs short sequences into MXU-sized
        # tiles and closes exactly that gap
        short = q.shape[1] <= 256
        B_, S_, nh_, dh_ = q.shape
        supertile = (not needs_probs) and attention_dispatch(
            (B_, nh_, S_, dh_), q.dtype.itemsize, causal=False,
            interpret=config.interpret,
        ) == "supertile"
        impl = ("flash" if (not needs_probs and
                            (supertile
                             or (not short and _flash_ok(q, config))))
                else "xla")
    if impl == "flash" and needs_probs:
        raise ValueError(
            "flash attn_impl supports neither attention_mask nor attention "
            "dropout (the probs tensor is never materialized); use "
            "attn_impl='xla' (or 'auto') for masked/prob-dropout batches"
        )
    if impl == "flash":
        return flash_attention(q, k, v, causal=False,
                               interpret=config.interpret)
    dh = q.shape[-1]
    # operands stay in the input dtype (bf16 MXU passes); only the
    # ACCUMULATION is fp32 — upcasting q/k first would run the matmul as a
    # ~6x-slower multi-pass fp32 MXU op
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) / jnp.sqrt(
                       jnp.float32(dh))
    if attention_mask is not None:
        # additive mask, broadcastable to (B, nH, Sq, Sk) — HF convention
        s = s + attention_mask.astype(jnp.float32)
    p = jax.nn.softmax(s, axis=-1)
    # dropout on the softmax probabilities, matching reference/HF semantics
    p = _dropout(p, config.attn_dropout_ratio, drop_rng)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _transformer_forward(params, x, config: DeepSpeedTransformerConfig,
                         attention_mask=None, rng=None, pld_theta=None):
    """One BERT layer: attn -> add&norm -> gelu MLP -> add&norm, pre- or
    post-LN (reference DeepSpeedTransformerFunction.forward :155).
    With stochastic_mode (progressive layer drop) the whole layer is kept
    with probability ``pld_theta``, identity otherwise."""
    B, S, H = x.shape
    nh = config.heads
    dh = H // nh
    dtype = config.compute_dtype
    x = x.astype(dtype)
    p = {k: v.astype(dtype) for k, v in params.items()}
    r1 = r2 = r3 = gate_rng = None
    if rng is not None and config.training:
        r1, r2, r3, gate_rng = jax.random.split(rng, 4)

    eps = config.layernorm_eps

    @jax.named_scope("ds.attn")
    def attn_block(x):
        h = _layer_norm(x, p["attn_nw"], p["attn_nb"], eps) if config.pre_layer_norm else x
        qkv = h @ p["attn_qkvw"] + p["attn_qkvb"]
        # named for selective remat (BertConfig.remat_policy='matmuls'):
        # save the big matmul outputs so the backward recomputes only the
        # cheap elementwise tail, not the MXU work
        qkv = checkpoint_name(qkv, "bert_qkv")
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shp = (B, S, nh, dh)
        ctx = _attention_core(q.reshape(shp), k.reshape(shp), v.reshape(shp),
                              config, attention_mask,
                              drop_rng=(r1 if config.attn_dropout_ratio > 0 else None))
        ctx = checkpoint_name(ctx, "bert_ctx")
        out = ctx.reshape(B, S, H) @ p["attn_ow"] + p["attn_ob"]
        return _dropout(out, config.hidden_dropout_ratio, r2)

    @jax.named_scope("ds.mlp")
    def ffn_block(x):
        h = _layer_norm(x, p["norm_w"], p["norm_b"], eps) if config.pre_layer_norm else x
        # saved pre-bias so the fused kernel owns the bias add; the XLA
        # fallback (gelu(x + b)) is the exact pre-fusion math
        pre = checkpoint_name(h @ p["inter_w"], "bert_mlp_pre")
        inter = bias_gelu(pre, p["inter_b"], approximate=False)
        out = inter @ p["output_w"] + p["output_b"]
        return _dropout(out, config.hidden_dropout_ratio, r3)

    # the reference's memory knobs (normalize_invertible drops the LN input,
    # attn_dropout_checkpoint / gelu_checkpoint recompute those outputs in
    # backward) all become remat of the sub-block
    if config.normalize_invertible or config.attn_dropout_checkpoint:
        attn_block = jax.checkpoint(attn_block)
    if config.normalize_invertible or config.gelu_checkpoint:
        ffn_block = jax.checkpoint(ffn_block)

    def full_layer(x):
        if config.pre_layer_norm:
            x = x + attn_block(x)
            return x + ffn_block(x)
        # post-LN add&norm fuses the residual add into the LN kernel
        x = add_layer_norm(attn_block(x), x, p["attn_nw"], p["attn_nb"], eps)
        return add_layer_norm(ffn_block(x), x, p["norm_w"], p["norm_b"], eps)

    if config.stochastic_mode and pld_theta is not None and gate_rng is not None:
        gate = jax.random.bernoulli(gate_rng, pld_theta).astype(dtype)
        return gate * full_layer(x) + (1 - gate) * x
    return full_layer(x)


_LAYER_FN_CACHE = {}


def transformer_layer_fn(config: DeepSpeedTransformerConfig):
    """Jitted forward for a config — the XLA analog of the reference's
    per-layer C++ object registry (create_transformer_layer :446): one
    compiled executable shared by every layer with this config. mask/rng are
    traced arguments (None is an empty pytree, so masked, dropout, and plain
    calls all reuse this one jitted function)."""
    key = config._cache_key()
    fn = _LAYER_FN_CACHE.get(key)
    if fn is None:
        def ds_transformer_layer(params, x, attention_mask=None, rng=None,
                                 pld_theta=None):
            return _transformer_forward(params, x, config, attention_mask,
                                        rng, pld_theta)

        fn = jax.jit(ds_transformer_layer)
        _LAYER_FN_CACHE[key] = fn
    return fn


def clear_layer_fn_cache():
    _LAYER_FN_CACHE.clear()


# --- torch/numpy -> param-pytree conversion (shared with module_inject) ----
# Reference weight order (transformer.py:487-500): q, k, v, attn_out,
# attn_norm, intermediate, output, norm — torch tensors in (out, in)
# orientation; ours is (in, out).


def to_numpy_f32(t) -> np.ndarray:
    if hasattr(t, "detach"):
        t = t.detach().cpu().numpy()
    return np.asarray(t, dtype=np.float32)


def weights_to_params(weights) -> dict:
    qw, kw, vw, ow, nw1, iw, out_w, nw2 = [to_numpy_f32(w) for w in weights]
    return {
        "attn_qkvw": jnp.asarray(np.concatenate([qw.T, kw.T, vw.T], axis=1)),
        "attn_ow": jnp.asarray(ow.T),
        "attn_nw": jnp.asarray(nw1),
        "inter_w": jnp.asarray(iw.T),
        "output_w": jnp.asarray(out_w.T),
        "norm_w": jnp.asarray(nw2),
    }


def biases_to_params(biases) -> dict:
    qb, kb, vb, ob, nb1, ib, out_b, nb2 = [to_numpy_f32(b) for b in biases]
    return {
        "attn_qkvb": jnp.asarray(np.concatenate([qb, kb, vb])),
        "attn_ob": jnp.asarray(ob),
        "attn_nb": jnp.asarray(nb1),
        "inter_b": jnp.asarray(ib),
        "output_b": jnp.asarray(out_b),
        "norm_b": jnp.asarray(nb2),
    }


class DeepSpeedTransformerLayer:
    """Reference transformer.py:470. Functional layer (init/apply) usable
    directly or in a PipelineModule layer list."""

    layer_id = 0

    def __init__(self, config: DeepSpeedTransformerConfig,
                 initial_weights=None, initial_biases=None):
        self.config = config
        self.config.layer_id = DeepSpeedTransformerLayer.layer_id
        DeepSpeedTransformerLayer.layer_id += 1
        self._initial = (initial_weights, initial_biases)

    def init(self, rng):
        params = init_transformer_params(rng, self.config)
        weights, biases = self._initial
        if weights is not None:
            params.update(weights_to_params(weights))
        if biases is not None:
            params.update(biases_to_params(biases))
        return params

    def apply(self, params, x, rng=None, attention_mask=None, pld_theta=None):
        return transformer_layer_fn(self.config)(
            params, x, attention_mask=attention_mask, rng=rng,
            pld_theta=(None if pld_theta is None else jnp.float32(pld_theta)),
        )

    __call__ = apply
