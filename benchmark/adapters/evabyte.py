"""EvaByte through the program's ``models/mixers.py`` and ``serving/``
(served only: the program has no training block for this layer)."""

# a program without the eva layer (this cell's parent) ends here, with an
# ImportError, before a weight is made
from deeperspeed_tpu.models.gpt import EvaAttnConfig  # noqa: F401

from ..refs import evabyte as reference  # noqa: F401  (the runners use it)

CAUSAL = True


def model_config(config: dict, **overrides):
    import jax.numpy as jnp

    from deeperspeed_tpu.models.gpt import GPTConfig

    n = config["num_layers"]
    assert config["num_key_value_heads"] == config["num_attention_heads"]
    kw = dict(
        vocab_size=config["vocab_size"], n_layer=n,
        n_head=config["num_attention_heads"], d_model=config["hidden_size"],
        d_ff=config["intermediate_size"],
        max_seq=config["max_position_embeddings"], rotary=True,
        rope_theta=float(config["rope_theta"]),
        layernorm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"],
        mixer_types=("eva",) * n,
        eva=EvaAttnConfig(window=config["window_size"],
                          chunk=config["chunk_size"]),
        norm_offset=1.0 if config["norm_add_unit_offset"] else 0.0,
        fp32_stream=config["fp32_skip_add"], n_pred=config["num_pred_heads"],
        # the published model is served in bfloat16; a toy on the CPU says
        dtype=jnp.dtype(config.get("compute_dtype", "bfloat16")))
    assert config["fp32_logits"] == config["fp32_skip_add"], \
        "the program keeps the stream and the logits in one precision"
    return GPTConfig(**{**kw, **config.get("program", {}), **overrides})


def serving_engine(config: dict, params, serving: dict, **overrides):
    from deeperspeed_tpu.serving import ServingConfig, ServingEngine

    return ServingEngine(model_config(config, **overrides), params,
                         ServingConfig.from_dict(serving))
