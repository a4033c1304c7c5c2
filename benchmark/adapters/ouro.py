"""Ouro through the program's ``models/mixers.py`` and ``serving/`` (served
only: the program trains no looped stack): ONE stack of ``full_attn``
layers, sandwich-normed, run ``total_ut_steps`` times a token."""

from deeperspeed_tpu.models.gpt import GPTConfig, GroupedAttnConfig, \
    RopeScaling

from ..refs import ouro as reference  # noqa: F401  (the runners use it)

# a program that cannot loop its stack (this cell's parent) ends here,
# before a weight is made
if "loop_steps" not in GPTConfig.__dataclass_fields__:
    raise ImportError("this program's GPTConfig has no loop_steps: it "
                      "cannot run a looped stack")

CAUSAL = True


def model_config(config: dict, **overrides):
    import jax.numpy as jnp

    n = config["num_hidden_layers"]
    assert set(config["layer_types"]) == {"full_attention"}
    assert len(config["layer_types"]) == n and config["hidden_act"] == "silu"
    assert config["rope_scaling"] is None and not config["use_sliding_window"]
    # threshold 1: the exit distribution's mass reaches it at the last
    # pass alone, so every token runs every pass
    assert config["early_exit_threshold"] == 1
    kw = dict(
        vocab_size=config["vocab_size"], n_layer=n,
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        d_model=config["hidden_size"], head_size=config["head_dim"],
        d_ff=config["intermediate_size"],
        max_seq=config["max_position_embeddings"], rotary=True,
        layernorm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"],
        mixer_types=("full_attn",) * n,
        loop_steps=config["total_ut_steps"],
        gqa=GroupedAttnConfig(
            qk_norm=False, sandwich=True,
            full_rope=RopeScaling(theta=float(config["rope_theta"]))),
        fp32_logits=True,
        # the published model is served in bfloat16; a toy on the CPU says
        dtype=jnp.dtype(config.get("compute_dtype", "bfloat16")))
    return GPTConfig(**{**kw, **config.get("program", {}), **overrides})


def serving_engine(config: dict, params, serving: dict, **overrides):
    from deeperspeed_tpu.serving import ServingConfig, ServingEngine

    return ServingEngine(model_config(config, **overrides), params,
                         ServingConfig.from_dict(serving))
