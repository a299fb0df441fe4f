"""Family ``bert``: bidirectional encoder with a tied masked-LM head,
``horovod_tpu.models.bert.Bert``.

Keys read from the configuration are the published BERT ones:
``num_hidden_layers``, ``hidden_size``, ``num_attention_heads``,
``intermediate_size``, ``vocab_size``, ``max_position_embeddings``.
"""

from benchmark import flops

CAUSAL = False


def build(config):
    from horovod_tpu.models.bert import Bert

    return Bert(vocab_size=config["vocab_size"],
                hidden_size=config["hidden_size"],
                num_layers=config["num_hidden_layers"],
                num_heads=config["num_attention_heads"],
                mlp_dim=config["intermediate_size"],
                max_len=config["max_position_embeddings"])


def loss(model, params, batch):
    """Mean cross-entropy over this rank's scored positions, predicting
    the token at each (the job of ``bench.py``'s ``_setup_bert``). The
    stream draws one position more than the model reads."""
    import jax.numpy as jnp
    import optax

    tokens, scored = batch["tokens"][:, :-1], batch["scored"]
    logits = model.apply({"params": params}, tokens)
    per_token = optax.softmax_cross_entropy_with_integer_labels(
        logits, tokens)
    return (per_token * scored).sum() / jnp.maximum(scored.sum(), 1.0)


def train_flops_per_token(config, seq_len):
    return flops.transformer_train_flops_per_token(
        config["num_hidden_layers"], config["hidden_size"],
        config["intermediate_size"], config["vocab_size"], seq_len,
        causal=CAUSAL)


def attention_calls(config, rows, seq_len):
    return {"calls": config["num_hidden_layers"], "batch": rows,
            "heads": config["num_attention_heads"], "seq_len": seq_len,
            "head_dim": config["hidden_size"]
            // config["num_attention_heads"],
            "causal": CAUSAL}
