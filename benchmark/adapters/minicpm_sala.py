"""MiniCPM-SALA through the program's ``models/mixers.py`` and ``serving/``
(served only: the program has no training block for these layers)."""

import math

# a program without mixed stacks (this cell's parent) ends here, with an
# ImportError, before a weight is made
from deeperspeed_tpu.models.gpt import SparseAttnConfig  # noqa: F401

from ..refs import minicpm_sala as reference  # noqa: F401  (the runners use it)

CAUSAL = True
MIXER = {"minicpm4": "minicpm4", "lightning-attn": "lightning"}


def model_config(config: dict, **overrides):
    import jax.numpy as jnp

    from deeperspeed_tpu.models.gpt import GPTConfig

    kw = dict(
        vocab_size=config["vocab_size"], n_layer=config["num_layers"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"], d_model=config["hidden_size"],
        d_ff=config["intermediate_size"],
        max_seq=config["max_position_embeddings"], rotary=True,
        layernorm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"],
        mixer_types=tuple(MIXER[m] for m in config["mixer_types"]),
        scale_emb=float(config["scale_emb"]),
        residual_scale=config["scale_depth"] / math.sqrt(config["num_hidden_layers"]),
        logit_scale=config["dim_model_base"] / config["hidden_size"],
        sparse=SparseAttnConfig(**config["sparse_config"]),
        # the published model is served in bfloat16; a toy on the CPU says
        dtype=jnp.dtype(config.get("compute_dtype", "bfloat16")))
    assert kw["d_model"] // kw["n_head"] == config["head_dim"]
    return GPTConfig(**{**kw, **config.get("program", {}), **overrides})


def serving_engine(config: dict, params, serving: dict, **overrides):
    from deeperspeed_tpu.serving import ServingConfig, ServingEngine

    return ServingEngine(model_config(config, **overrides), params,
                         ServingConfig.from_dict(serving))


def selector_probe(config: dict):
    """The PROGRAM's selector (models/mixers.py) on operands rounded to
    the served dtype, for the reference to count how many selections it
    makes differently on the same queries and pooled keys."""
    import jax.numpy as jnp

    from deeperspeed_tpu.models import mixers

    cfg = model_config(config)
    sp = cfg.sparse

    def probe(q, kbar, pos):
        b = mixers.block_scores(q.astype(cfg.dtype), kbar.astype(cfg.dtype),
                                mixers.visible_windows(pos, kbar.shape[1], sp), sp)
        blocks, valid = mixers.select_blocks(b, pos // sp.block_size, sp)
        return jnp.where(valid, blocks, -1)

    return probe
