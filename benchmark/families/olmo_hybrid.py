"""Family ``olmo_hybrid``: decoder-only causal LM whose token mixer is a
scalar-gated delta rule (Gated DeltaNet) or full softmax attention
without positions by a pattern, a dense SwiGLU feed-forward in every
layer, Olmo's norm on each branch's output and an untied head;
``horovod_tpu.models.OlmoHybridLM``, trained on the mean next-token
cross-entropy.

Keys read from the configuration are the published ones of
``allenai/Olmo-Hybrid-7B`` (``model_type`` ``olmo_hybrid``):
``num_hidden_layers``, ``hidden_size``, ``layer_types``,
``num_attention_heads``, ``num_key_value_heads``, ``intermediate_size``,
``linear_num_key_heads``, ``linear_num_value_heads``,
``linear_key_head_dim``, ``linear_value_head_dim``,
``linear_conv_kernel_dim``, ``linear_allow_neg_eigval``,
``rope_parameters``, ``rms_norm_eps``, ``vocab_size``; and one the source
does not have, ``gdn_chunk_size`` (the chunk the program cuts a sequence
into, the file's ``assumed``). The head width is ``hidden_size /
num_attention_heads``; ``layer_types`` is the published list, read up to
the depth held.
"""

from benchmark import olmo_hybrid_cost

CAUSAL = True


def _layer_kinds(config):
    """``(full-attention layers, linear layers)`` of the depth held."""
    linear = olmo_hybrid_cost.linear_layers(config)
    return config["num_hidden_layers"] - linear, linear


def build(config):
    from horovod_tpu.models import OlmoHybridLM

    if config["rope_parameters"]["rope_theta"] is not None:
        raise ValueError("the family has no positions to give: rope_theta "
                         f"{config['rope_parameters']['rope_theta']!r}")
    if config["num_key_value_heads"] != config["num_attention_heads"] \
            or config["linear_num_key_heads"] \
            != config["linear_num_value_heads"]:
        raise ValueError("the family groups no heads: as many K/V heads as "
                         "query heads, as many key heads as value heads")
    if config["tie_word_embeddings"] or config["attention_bias"]:
        raise ValueError("the family's head is untied and bias-free")
    return OlmoHybridLM(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        hidden=config["hidden_size"],
        layer_types=tuple(config["layer_types"]),
        num_heads=config["num_attention_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        mlp_dim=config["intermediate_size"],
        linear_heads=config["linear_num_value_heads"],
        linear_key_dim=config["linear_key_head_dim"],
        linear_value_dim=config["linear_value_head_dim"],
        conv_taps=config["linear_conv_kernel_dim"],
        allow_neg_eigval=config["linear_allow_neg_eigval"],
        chunk=config["gdn_chunk_size"],
        norm_eps=config["rms_norm_eps"])


def loss(model, params, batch):
    """Mean next-token cross-entropy over this rank's positions."""
    from horovod_tpu.models import olmo_hybrid_loss

    return olmo_hybrid_loss(model, params, batch["tokens"])


def _linear_weights(config):
    """The weights a token is multiplied by in one linear layer's mixer:
    q, k and v, the two scalars a head, the gate and the output."""
    hidden, heads = config["hidden_size"], config["linear_num_value_heads"]
    keys = heads * config["linear_key_head_dim"]
    values = heads * config["linear_value_head_dim"]
    return hidden * (2 * keys + values + 2 * heads + 2 * values)


def train_flops_per_token(config, seq_len):
    """Forward + backward FLOPs one token needs, by ``benchmark/flops.py``'s
    convention (6 for every weight a token is multiplied by, nothing
    recomputed, causal attention and the recurrence's in-chunk triangle
    counted as half), for the share of the model held here.

    A linear layer: q, k and v, hidden x H (2 d_k + d_v); the decay's and
    the write strength's projections, hidden x H each; the gate and the
    output, hidden x H d_v each; its recurrence by
    ``benchmark/olmo_hybrid_cost.py`` (3 x the forward's products). A full
    layer: q, k, v and o, hidden x hidden each; attention 6 S w, w = heads
    x head_dim. Every layer's feed-forward 3 x hidden x
    intermediate_size. The untied head vocab x hidden over the slice
    held. The embedding is a gather; norms, the convolution and its SiLU,
    the L2 norms, the decays and the gate's SiLU are not counted.

    At the cell's size: three linear layers 3 x 88,704,000; one full layer
    58,982,400; four feed-forwards 4 x 126,812,160; the head 48,168,960:
    880,512,000 weights x 6 = 5,283,072,000; attention 6 x 8192 x 3840 =
    188,743,680; the recurrences 3 x 13,824,000 = 41,472,000:
    5,513,287,680 FLOPs a token."""
    hidden = config["hidden_size"]
    full, linear = _layer_kinds(config)
    weights = linear * _linear_weights(config) \
        + full * 4 * hidden * hidden \
        + config["num_hidden_layers"] * 3 * hidden \
        * config["intermediate_size"] \
        + config["vocab_size"] * hidden
    return float(
        6 * weights + 6 * full * seq_len * hidden
        + 3 * linear * olmo_hybrid_cost.gdn_flops_per_token_forward(config))


def attention_calls(config, rows, seq_len):
    """The flash-attention calls of one step on one chip holding ``rows``
    sequences: one a full-attention layer, forward and backward."""
    hidden = config["hidden_size"]
    return {"calls": _layer_kinds(config)[0], "batch": rows,
            "heads": config["num_attention_heads"], "seq_len": seq_len,
            "head_dim": hidden // config["num_attention_heads"],
            "causal": CAUSAL}
