"""BERT through the program's ``models/bert.py`` (masked-LM loss)."""

from ..refs import bert as reference  # noqa: F401  (the runners use it)

CAUSAL = False


def model_config(config: dict, **overrides):
    from deeperspeed_tpu.models.bert import BertConfig

    kw = dict(
        vocab_size=config["vocab_size"], n_layer=config["num_hidden_layers"],
        n_head=config["num_attention_heads"], d_model=config["hidden_size"],
        d_ff=config["intermediate_size"],
        max_seq=config["max_position_embeddings"],
        type_vocab_size=config["type_vocab_size"],
        layernorm_eps=config["layer_norm_eps"],
        initializer_range=config["initializer_range"])
    return BertConfig(**{**kw, **config.get("program", {}), **overrides})


def train_loss_fn(config: dict, seq: int):
    from deeperspeed_tpu.models.bert import make_bert

    if seq > config["max_position_embeddings"]:
        raise ValueError(f"seq {seq} over the position table")
    return make_bert(model_config(config))[2]


def feed(batch):
    return tuple(batch)


def matmul_params(config: dict) -> int:
    """Layer matrices, the head's dense layer and the tied decoder (the
    word embedding counts once, as the decoder; the pooler is unused)."""
    D, F, L, V = (config["hidden_size"], config["intermediate_size"],
                  config["num_hidden_layers"], config["vocab_size"])
    return L * (3 * D * D + D * D + 2 * D * F) + D * D + D * V
