"""Solar Open 2 through the program's ``models/mixers.py`` and ``serving/``
(served only: the program has no training block for these layers), as ONE
CHIP'S SHARE of a layer: the experts the configuration's file says are
held here, routed over all of them."""

# a program without the gated-delta-rule layer and the share of experts
# (this cell's parent) ends here, with an ImportError, before a weight is
# made
from deeperspeed_tpu.models.gpt import GroupedAttnConfig, KdaConfig

from ..refs import solar_open2 as reference  # noqa: F401  (the runners use it)

CAUSAL = True


def model_config(config: dict, **overrides):
    import jax.numpy as jnp

    from deeperspeed_tpu.models.gpt import GPTConfig

    n, lin, share = (config["num_layers"], config["linear_attn_config"],
                     config["share"])
    assert not config["use_rope"] and config["use_gqa_gate"]
    assert config["kda_allow_neg_eigval"] and not config["kda_use_full_proj"]
    assert config["first_k_dense_replace"] == 0, "every layer routes"
    assert config["routed_scaling_factor"] == 1
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
        mixer_types=tuple("full_attn" if i in config["gqa_layers"] else "kda"
                          for i in range(n)),
        gqa=GroupedAttnConfig(qk_norm=False, rotary=False, out_gate=True),
        kda=KdaConfig(n_heads=lin["num_heads"], head_k=lin["head_dim"],
                      head_v=lin["head_dim"],
                      d_conv=lin["short_conv_kernel_size"],
                      low_rank=config["kda_low_rank"], beta_scale=2.0),
        # the router scores all of the layer's experts; this chip holds
        # ``n_routed_experts`` of them from ``first_expert`` on
        moe_num_experts=share["experts_routed_over"],
        moe_held=(share["first_expert"], config["n_routed_experts"]),
        moe_shared=config["n_shared_experts"],
        moe_rule="sigmoid_bias",
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
