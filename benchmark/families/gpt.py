"""Family ``gpt``: decoder-only causal LM, ``horovod_tpu.models.GPT``.

Keys read from the configuration are the published GPT-2 ones: ``n_layer``,
``n_embd``, ``n_head``, ``n_inner``, ``vocab_size``.
"""

from benchmark import flops

CAUSAL = True


def build(config):
    from horovod_tpu.models import GPT

    return GPT(num_layers=config["n_layer"], hidden=config["n_embd"],
               num_heads=config["n_head"], mlp_dim=config["n_inner"],
               vocab_size=config["vocab_size"])


def loss(model, params, batch):
    """Mean next-token cross-entropy over this rank's rows: the loss of
    the README quick start and ``chip_smoke.py``."""
    import optax

    tokens = batch["tokens"]
    logits = model.apply({"params": params}, tokens[:, :-1])
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, tokens[:, 1:]).mean()


def train_flops_per_token(config, seq_len):
    return flops.transformer_train_flops_per_token(
        config["n_layer"], config["n_embd"], config["n_inner"],
        config["vocab_size"], seq_len, causal=CAUSAL)


def attention_calls(config, rows, seq_len):
    """The flash-attention calls of one step on one chip holding ``rows``
    sequences: one a layer, forward and backward."""
    return {"calls": config["n_layer"], "batch": rows,
            "heads": config["n_head"], "seq_len": seq_len,
            "head_dim": config["n_embd"] // config["n_head"],
            "causal": CAUSAL}
