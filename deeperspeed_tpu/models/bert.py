"""BERT encoder family, TPU-native.

The reference framework's headline results are BERT pretraining (SURVEY §6:
64 TFLOPS/GPU seq128 — docs/_posts/2020-05-28-fastest-bert-training.md) and
its kernel tests compare against HF BERT layers (tests/unit/modeling.py).
This module is the rebuild's BERT: embeddings + a scan over fused
transformer layers (ops/transformer) + pooler + tied MLM head.

Design mirrors models/gpt.py: params are a pytree with per-layer tensors
stacked on a leading axis so the encoder is one `lax.scan` (O(1) compile in
depth, per-layer gather under ZeRO-3), remat per layer, TP/sequence sharding
via PartitionSpecs over the same mesh axes.

`params_from_hf(model)` imports a huggingface BertModel checkpoint wholesale
(embeddings + every layer via module_inject), giving bit-compatible
fine-tuning starts.
"""

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..ops.transformer import DeepSpeedTransformerConfig, init_transformer_params
from ..ops.transformer.transformer import (
    _layer_norm,
    _transformer_forward,
    to_numpy_f32,
)
from ..parallel.topology import DATA_AXIS, MODEL_AXIS, SEQ_AXIS
from .gpt import _shard_act, pick_ce_chunk
from ..utils import hooks


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    d_ff: int = 0  # 0 => 4 * d_model
    max_seq: int = 512
    type_vocab_size: int = 2
    layernorm_eps: float = 1e-12
    initializer_range: float = 0.02
    pre_layer_norm: bool = False  # classic BERT is post-LN
    remat: bool = True
    # 'full' recomputes the whole layer in backward (min memory, ~+33%
    # matmul flops); 'matmuls' saves the qkv / attention-ctx / pre-gelu
    # matmul outputs so only the elementwise tail recomputes — the same
    # selective policy the GPT flagship benches with (gpt.py remat_policy)
    remat_policy: str = "full"
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"
    attn_dropout: float = 0.0
    hidden_dropout: float = 0.0
    # MLM-loss sequence chunk (streaming CE, no (B,S,V) fp32 logits);
    # 0 disables chunking
    ce_chunk: int = 64
    # when > 0, the MLM head runs only on scored positions: the (B*S)
    # hidden rows are stably ordered scored-first and the head consumes the
    # first ceil(frac*B*S) (lane-aligned) rows — at 15% masking the
    # vocab-width matmul drops ~4x in flops. frac must upper-bound the true
    # scored fraction: positions past the cut are silently unscored (the
    # loss normalizer counts only gathered positions), so keep a margin
    # (0.25 for standard 15% MLM). 0 = score every position (exact).
    mlm_gather_frac: float = 0.0

    def __post_init__(self):
        if self.remat_policy not in ("full", "matmuls", "dots_all"):
            raise ValueError(
                f"remat_policy must be 'full', 'matmuls' or 'dots_all', "
                f"got {self.remat_policy!r}")
        if not 0.0 <= self.mlm_gather_frac <= 1.0:
            raise ValueError("mlm_gather_frac must be in [0, 1]")

    @property
    def ffn_dim(self):
        return self.d_ff if self.d_ff else 4 * self.d_model

    def layer_config(self) -> DeepSpeedTransformerConfig:
        return DeepSpeedTransformerConfig(
            batch_size=-1,
            max_seq_length=self.max_seq,
            hidden_size=self.d_model,
            intermediate_size=self.ffn_dim,
            heads=self.n_head,
            attn_dropout_ratio=self.attn_dropout,
            hidden_dropout_ratio=self.hidden_dropout,
            num_hidden_layers=self.n_layer,
            initializer_range=self.initializer_range,
            fp16=self.dtype == jnp.bfloat16,
            pre_layer_norm=self.pre_layer_norm,
            layernorm_eps=self.layernorm_eps,
            attn_impl=self.attn_impl,
        )


def init_params(rng, cfg: BertConfig):
    ks = jax.random.split(rng, cfg.n_layer + 5)
    std = cfg.initializer_range
    f32 = jnp.float32
    layer_cfg = cfg.layer_config()
    per_layer = [init_transformer_params(ks[i], layer_cfg)
                 for i in range(cfg.n_layer)]
    layers = {k: jnp.stack([p[k] for p in per_layer]) for k in per_layer[0]}
    D = cfg.d_model
    return {
        "embed": {
            "word": jax.random.normal(ks[-4], (cfg.vocab_size, D), f32) * std,
            "pos": jax.random.normal(ks[-3], (cfg.max_seq, D), f32) * std,
            "type": jax.random.normal(ks[-2], (cfg.type_vocab_size, D), f32) * std,
            "ln_w": jnp.ones((D,), f32),
            "ln_b": jnp.zeros((D,), f32),
        },
        "layers": layers,
        "pooler": {
            "w": jax.random.normal(ks[-1], (D, D), f32) * std,
            "b": jnp.zeros((D,), f32),
        },
        "mlm": {  # transform dense + LN; decoder tied to word embeddings
            "w": jax.random.normal(ks[-5], (D, D), f32) * std,
            "b": jnp.zeros((D,), f32),
            "ln_w": jnp.ones((D,), f32),
            "ln_b": jnp.zeros((D,), f32),
            "bias": jnp.zeros((cfg.vocab_size,), f32),
        },
    }


def param_specs(cfg: BertConfig):
    """TP sharding over the 'model' axis, matching gpt.param_specs: QKV/FFN
    columns sharded, output rows sharded, embeddings vocab-sharded."""
    from jax.sharding import PartitionSpec as P

    L = P  # brevity
    return {
        # word embedding sharded over d_model, not vocab — XLA's gather from
        # a vocab-sharded table falls back to full replication (see the same
        # note in gpt.param_specs)
        "embed": {"word": L(None, MODEL_AXIS), "pos": L(), "type": L(),
                  "ln_w": L(), "ln_b": L()},
        "layers": {
            "attn_qkvw": L(None, None, MODEL_AXIS),
            "attn_qkvb": L(None, MODEL_AXIS),
            "attn_ow": L(None, MODEL_AXIS, None),
            "attn_ob": L(None, None),
            "attn_nw": L(None, None), "attn_nb": L(None, None),
            "inter_w": L(None, None, MODEL_AXIS),
            "inter_b": L(None, MODEL_AXIS),
            "output_w": L(None, MODEL_AXIS, None),
            "output_b": L(None, None),
            "norm_w": L(None, None), "norm_b": L(None, None),
        },
        "pooler": {"w": L(), "b": L()},
        "mlm": {"w": L(), "b": L(), "ln_w": L(), "ln_b": L(),
                "bias": L()},
    }


def make_bert(cfg: BertConfig, mesh=None):
    """Returns (init_fn, apply_fn, mlm_loss_fn, specs).

    apply_fn(params, input_ids, token_type_ids=None, attention_mask=None)
        -> (sequence_output, pooled_output)
    mlm_loss_fn(params, batch) with batch = (input_ids, labels) where
        labels == -100 marks unscored positions (HF convention).
    """
    layer_cfg = cfg.layer_config()

    def apply_fn(params, input_ids, token_type_ids=None, attention_mask=None,
                 rng=None):
        cdt = cfg.dtype
        B, S = input_ids.shape
        with jax.named_scope("ds.embed"):
            e = params["embed"]
            x = jnp.take(e["word"].astype(cdt), input_ids, axis=0)
            x = x + e["pos"][:S].astype(cdt)
            if token_type_ids is None:
                token_type_ids = jnp.zeros_like(input_ids)
            x = x + jnp.take(e["type"].astype(cdt), token_type_ids, axis=0)
            x = _layer_norm(x, e["ln_w"].astype(cdt), e["ln_b"].astype(cdt),
                            cfg.layernorm_eps)
            # context-parallel long sequences: activations sharded over the
            # 'seq' axis (as in make_gpt)
            from jax.sharding import PartitionSpec as P

            x = _shard_act(x, mesh, P(DATA_AXIS, SEQ_AXIS, None))

        additive = None
        if attention_mask is not None:
            additive = (1.0 - attention_mask[:, None, None, :].astype(jnp.float32)) * -1e4

        def block(h, layer_params, layer_rng):
            return _transformer_forward(layer_params, h, layer_cfg,
                                        attention_mask=additive,
                                        rng=layer_rng)

        if cfg.remat:
            policy = {
                "full": None,
                "matmuls": jax.checkpoint_policies.save_only_these_names(
                    "bert_qkv", "bert_ctx", "bert_mlp_pre"
                ),
                # save every dot output: the backward replays only
                # elementwise ops (no matmul recompute) at far less
                # memory than remat=False, which misses HBM by ~16MB at
                # the mb64/seq128 bench point
                "dots_all": jax.checkpoint_policies.dots_saveable,
            }[cfg.remat_policy]
            step = jax.checkpoint(block, prevent_cse=False, policy=policy)
        else:
            step = block

        def scan_body(carry, xs):
            layer_params, idx = xs
            layer_rng = None if rng is None else jax.random.fold_in(rng, idx)
            out = step(carry, layer_params, layer_rng)
            out = hooks.record_layer_output("bertlayer", out, idx)
            return out, None

        layer_ids = jnp.arange(cfg.n_layer, dtype=jnp.int32)
        x, _ = jax.lax.scan(scan_body, x, (params["layers"], layer_ids))

        pooled = jnp.tanh(x[:, 0] @ params["pooler"]["w"].astype(cdt)
                          + params["pooler"]["b"].astype(cdt))
        return x, pooled

    def mlm_logits(params, sequence_output):
        cdt = cfg.dtype
        m = params["mlm"]
        from ..ops.pallas.fused_blocks import bias_gelu

        h = bias_gelu(sequence_output @ m["w"].astype(cdt),
                      m["b"].astype(cdt), approximate=False)
        h = _layer_norm(h, m["ln_w"], m["ln_b"], cfg.layernorm_eps)
        return h @ params["embed"]["word"].astype(cdt).T + m["bias"].astype(cdt)

    def _chunk_nll(params, seq_chunk, labels_chunk):
        """Masked-LM nll over one sequence chunk WITHOUT materializing the
        fp32 log-softmax (nll = logsumexp - target logit); rematerialized in
        the backward — the same streaming trick as gpt.py's chunked CE (the
        reference's fused fp16 softmax-xent kernel served this role,
        csrc/transformer/softmax_kernels.cu)."""
        logits = mlm_logits(params, seq_chunk).astype(jnp.float32)
        valid = labels_chunk != -100
        safe = jnp.where(valid, labels_chunk, 0)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        nll = lse - tgt
        return jnp.sum(jnp.where(valid, nll, 0.0)), jnp.sum(valid)

    def mlm_loss_fn(params, batch, rng=None):
        input_ids, labels = batch[0], batch[1]
        attention_mask = batch[2] if len(batch) > 2 else None
        seq_out, _ = apply_fn(params, input_ids, attention_mask=attention_mask,
                              rng=rng)
        with jax.named_scope("ds.loss"):
            B, S, D = seq_out.shape
            if cfg.mlm_gather_frac:
                # run the vocab-width head only on scored positions: stable
                # argsort orders scored rows first, the head consumes a
                # lane-aligned prefix (see mlm_gather_frac docstring for the
                # upper-bound contract)
                BS = B * S
                K = min(BS, int(math.ceil(cfg.mlm_gather_frac * BS / 128)) * 128)
                flat_lab = labels.reshape(BS)
                n_scored = jnp.sum(flat_lab != -100)
                order = jnp.argsort(flat_lab == -100, stable=True)[:K]
                seq_out = seq_out.reshape(BS, D)[order][None]
                labels = flat_lab[order][None]
                # overflow telemetry (MoE dropped_frac analog): positions past
                # the cut are silently unscored, so surface the count to layer-
                # output collectors instead of hiding it
                hooks.record_layer_output(
                    "mlm_dropped", jnp.maximum(n_scored - K, 0))
                B, S = 1, K
            chunk = pick_ce_chunk(S, cfg.ce_chunk)
            if chunk and S > chunk:
                n = S // chunk
                xs = jnp.moveaxis(seq_out.reshape(B, n, chunk, D), 1, 0)
                ls = jnp.moveaxis(labels.reshape(B, n, chunk), 1, 0)
                ck = jax.checkpoint(lambda xc, lc: _chunk_nll(params, xc, lc))

                def body(carry, xt):
                    tot, cnt = carry
                    t, c = ck(*xt)
                    return (tot + t, cnt + c), None

                (total, count), _ = jax.lax.scan(
                    body, (jnp.float32(0.0), jnp.int32(0)), (xs, ls)
                )
            else:
                total, count = _chunk_nll(params, seq_out, labels)
            return total / jnp.maximum(count, 1)

    def init_fn(rng):
        return init_params(rng, cfg)

    apply_fn.mlm_logits = mlm_logits
    return init_fn, apply_fn, mlm_loss_fn, param_specs(cfg)


def make_bert_qa(cfg: BertConfig, mesh=None):
    """SQuAD-class span-extraction fine-tuning (the reference's
    BingBertSquad leg: tests/model/BingBertSquad + the 1.5x fine-tune
    claim in docs/_posts/2020-05-28-fastest-bert-training.md:105-121).

    Returns (init_fn, apply_fn, qa_loss_fn, specs). The QA head is the
    standard 2-wide span projection; ``qa_loss_fn(params, batch, rng)``
    takes batch = (input_ids, start_positions, end_positions[,
    attention_mask]) and averages start/end cross-entropy, with the rng
    threading dropout through every layer (fine-tuning runs the 0.1
    dropout the MLM pretraining benches disable)."""
    init_fn, apply_fn, _, specs = make_bert(cfg, mesh=mesh)

    def qa_init_fn(rng):
        k1, k2 = jax.random.split(rng)
        params = init_fn(k1)
        D = cfg.d_model
        params["qa"] = {
            "w": jax.random.normal(k2, (D, 2), jnp.float32)
            * cfg.initializer_range,
            "b": jnp.zeros((2,), jnp.float32),
        }
        return params

    def qa_loss_fn(params, batch, rng=None):
        input_ids, start_pos, end_pos = batch[0], batch[1], batch[2]
        attention_mask = batch[3] if len(batch) > 3 else None
        seq_out, _ = apply_fn(params, input_ids,
                              attention_mask=attention_mask, rng=rng)
        cdt = cfg.dtype
        logits = (seq_out @ params["qa"]["w"].astype(cdt)
                  + params["qa"]["b"].astype(cdt)).astype(jnp.float32)
        if attention_mask is not None:
            logits = jnp.where(attention_mask[..., None] > 0, logits, -1e9)

        def span_nll(lg, pos):
            lse = jax.scipy.special.logsumexp(lg, axis=-1)
            tgt = jnp.take_along_axis(lg, pos[:, None], axis=-1)[:, 0]
            return jnp.mean(lse - tgt)

        return 0.5 * (span_nll(logits[..., 0], start_pos)
                      + span_nll(logits[..., 1], end_pos))

    qa_specs = dict(specs)
    from jax.sharding import PartitionSpec as P

    qa_specs["qa"] = {"w": P(), "b": P()}
    return qa_init_fn, apply_fn, qa_loss_fn, qa_specs


def params_from_hf(model, cfg: Optional[BertConfig] = None):
    """Import a huggingface BertModel/BertForMaskedLM checkpoint into the
    stacked param pytree (embeddings + all layers via module_inject)."""
    from ..module_inject import replace_transformer_layer

    bert = getattr(model, "bert", model)
    hf_cfg = model.config
    if cfg is None:
        cfg = BertConfig(
            vocab_size=hf_cfg.vocab_size,
            n_layer=hf_cfg.num_hidden_layers,
            n_head=hf_cfg.num_attention_heads,
            d_model=hf_cfg.hidden_size,
            d_ff=hf_cfg.intermediate_size,
            max_seq=hf_cfg.max_position_embeddings,
            type_vocab_size=hf_cfg.type_vocab_size,
            layernorm_eps=hf_cfg.layer_norm_eps,
            dtype=jnp.float32,
        )
    _, _, stacked = replace_transformer_layer(model=bert, fp16=False,
                                              attn_impl=cfg.attn_impl)
    emb = bert.embeddings
    params = init_params(jax.random.PRNGKey(0), cfg)
    params["layers"] = stacked
    params["embed"] = {
        "word": jnp.asarray(to_numpy_f32(emb.word_embeddings.weight)),
        "pos": jnp.asarray(to_numpy_f32(emb.position_embeddings.weight)),
        "type": jnp.asarray(to_numpy_f32(emb.token_type_embeddings.weight)),
        "ln_w": jnp.asarray(to_numpy_f32(emb.LayerNorm.weight)),
        "ln_b": jnp.asarray(to_numpy_f32(emb.LayerNorm.bias)),
    }
    if getattr(bert, "pooler", None) is not None:
        params["pooler"] = {
            "w": jnp.asarray(to_numpy_f32(bert.pooler.dense.weight).T),
            "b": jnp.asarray(to_numpy_f32(bert.pooler.dense.bias)),
        }
    # MLM head (BertForMaskedLM / BertForPreTraining: cls.predictions)
    cls = getattr(model, "cls", None)
    predictions = getattr(cls, "predictions", None) if cls is not None else None
    if predictions is not None:
        tr = predictions.transform
        params["mlm"] = {
            "w": jnp.asarray(to_numpy_f32(tr.dense.weight).T),
            "b": jnp.asarray(to_numpy_f32(tr.dense.bias)),
            "ln_w": jnp.asarray(to_numpy_f32(tr.LayerNorm.weight)),
            "ln_b": jnp.asarray(to_numpy_f32(tr.LayerNorm.bias)),
            "bias": jnp.asarray(to_numpy_f32(predictions.decoder.bias)),
        }
    return cfg, params
