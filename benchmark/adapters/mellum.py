"""Mellum 2 through the program's ``models/mixers.py`` and ``serving/``
(served only: the program has no training block for these layers)."""

# a program without the grouped-attention layers of two page rules (this
# cell's parent) ends here, with an ImportError, before a weight is made
from deeperspeed_tpu.models.gpt import GroupedAttnConfig, RopeScaling

from ..refs import mellum as reference  # noqa: F401  (the runners use it)

CAUSAL = True
KINDS = {"sliding_attention": "window_attn", "full_attention": "full_attn"}


def rope(section: dict) -> RopeScaling:
    """One section of the config's ``rope_parameters``."""
    if section["rope_type"] == "default":
        return RopeScaling(theta=float(section["rope_theta"]))
    assert section["rope_type"] == "yarn", section
    return RopeScaling(
        theta=float(section["rope_theta"]), factor=float(section["factor"]),
        original_positions=section["original_max_position_embeddings"],
        beta_fast=float(section["beta_fast"]),
        beta_slow=float(section["beta_slow"]),
        attention_factor=float(section["attention_factor"]))


def model_config(config: dict, **overrides):
    import jax.numpy as jnp

    from deeperspeed_tpu.models.gpt import GPTConfig

    n = config["num_layers"]
    assert set(config["mlp_layer_types"]) == {"sparse"}, "every layer routes"
    assert not config["attention_bias"] and config["hidden_act"] == "silu"
    ropes = config["rope_parameters"]
    kw = dict(
        vocab_size=config["vocab_size"], n_layer=n,
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        d_model=config["hidden_size"], head_size=config["head_dim"],
        d_ff=config["moe_intermediate_size"],
        max_seq=config["max_position_embeddings"], rotary=True,
        layernorm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"],
        # the first ``num_layers`` of the published pattern: whole periods
        mixer_types=tuple(KINDS[t] for t in config["layer_types"][:n]),
        gqa=GroupedAttnConfig(
            window=config["sliding_window"], qk_norm=True,
            full_rope=rope(ropes["full_attention"]),
            window_rope=rope(ropes["sliding_attention"])),
        moe_num_experts=config["num_experts"],
        moe_top_k=config["num_experts_per_tok"],
        moe_normalize_gates=config["norm_topk_prob"],
        fp32_logits=True,
        # the published model is served in bfloat16; a toy on the CPU says
        dtype=jnp.dtype(config.get("compute_dtype", "bfloat16")))
    return GPTConfig(**{**kw, **config.get("program", {}), **overrides})


def serving_engine(config: dict, params, serving: dict, **overrides):
    from deeperspeed_tpu.serving import ServingConfig, ServingEngine

    return ServingEngine(model_config(config, **overrides), params,
                         ServingConfig.from_dict(serving))
