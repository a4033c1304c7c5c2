"""GPT-NeoX through the program's ``models/gpt.py`` and ``serving/``."""

from ..refs import gpt_neox as reference  # noqa: F401  (the runners use it)

CAUSAL = True


def model_config(config: dict, **overrides):
    from deeperspeed_tpu.models.gpt import GPTConfig

    kw = dict(
        vocab_size=config["vocab_size"], n_layer=config["num_hidden_layers"],
        n_head=config["num_attention_heads"], d_model=config["hidden_size"],
        d_ff=config["intermediate_size"],
        max_seq=config["max_position_embeddings"], rotary=True,
        rotary_pct=config["rotary_pct"],
        parallel_residual=config["use_parallel_residual"],
        layernorm_eps=config["layer_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"])
    return GPTConfig(**{**kw, **config.get("program", {}), **overrides})


def train_loss_fn(config: dict, seq: int):
    from deeperspeed_tpu.models.gpt import make_gpt

    return make_gpt(model_config(config, max_seq=seq))[2]


def feed(batch):
    """The benchmark's (tokens,) as the program's loss takes it."""
    return batch[0]


def matmul_params(config: dict) -> int:
    """Parameters that sit in a matrix multiplication (not the embedding
    gather, layer norms or biases)."""
    D, F, L, V = (config["hidden_size"], config["intermediate_size"],
                  config["num_hidden_layers"], config["vocab_size"])
    return L * (3 * D * D + D * D + 2 * D * F) + D * V


def serving_engine(config: dict, params, serving: dict):
    from deeperspeed_tpu.serving import ServingConfig, ServingEngine

    return ServingEngine(model_config(config), params,
                         ServingConfig.from_dict(serving))
