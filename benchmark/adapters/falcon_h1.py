"""Falcon-H1 through the program's ``models/mixers.py`` and ``serving/``
(served only: the program has no training block for this layer)."""

# a program without the mamba_attn layer (this cell's parent) ends here,
# with an ImportError, before a weight is made
from deeperspeed_tpu.models.gpt import MambaAttnConfig  # noqa: F401

from ..refs import falcon_h1 as reference  # noqa: F401  (the runners use it)

CAUSAL = True


def model_config(config: dict, **overrides):
    import jax.numpy as jnp

    from deeperspeed_tpu.models.gpt import GPTConfig

    gate, down = config["mlp_multipliers"]
    kw = dict(
        vocab_size=config["vocab_size"], n_layer=config["num_layers"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"], d_model=config["hidden_size"],
        head_size=config["head_dim"], d_ff=config["intermediate_size"],
        max_seq=config["max_position_embeddings"], rotary=True,
        rope_theta=float(config["rope_theta"]),
        layernorm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"],
        mixer_types=("mamba_attn",) * config["num_layers"],
        scale_emb=float(config["embedding_multiplier"]),
        logit_scale=float(config["lm_head_multiplier"]),
        ssm=MambaAttnConfig(
            n_heads=config["mamba_n_heads"], head_dim=config["mamba_d_head"],
            d_state=config["mamba_d_state"], n_groups=config["mamba_n_groups"],
            d_conv=config["mamba_d_conv"], chunk=config["mamba_chunk_size"],
            ssm_in=float(config["ssm_in_multiplier"]),
            ssm_mult=tuple(float(m) for m in config["ssm_multipliers"]),
            ssm_out=float(config["ssm_out_multiplier"]),
            attn_in=float(config["attention_in_multiplier"]),
            attn_out=float(config["attention_out_multiplier"]),
            key=float(config["key_multiplier"]),
            mlp_gate=float(gate), mlp_out=float(down)),
        # the published model is served in bfloat16; a toy on the CPU says
        dtype=jnp.dtype(config.get("compute_dtype", "bfloat16")))
    assert kw["ssm"].d_ssm == config["mamba_d_ssm"]
    return GPTConfig(**{**kw, **config.get("program", {}), **overrides})


def serving_engine(config: dict, params, serving: dict, **overrides):
    from deeperspeed_tpu.serving import ServingConfig, ServingEngine

    return ServingEngine(model_config(config, **overrides), params,
                         ServingConfig.from_dict(serving))
