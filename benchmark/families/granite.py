"""Family ``granite``: decoder-only causal LM whose token mixer is a
Mamba-2 state-space layer or grouped-query attention without positions
by a pattern, a dense SwiGLU feed-forward in every layer, Granite's four
multipliers and a tied head; ``horovod_tpu.models.GraniteHybridLM``,
trained on the mean next-token cross-entropy.

Keys read from the configuration are the published ones of
``ibm-granite/granite-4.0-h-micro`` (``model_type``
``granitemoehybrid``, no experts): ``num_hidden_layers``,
``hidden_size``, ``layer_types``, ``num_attention_heads``,
``num_key_value_heads``, ``shared_intermediate_size``, ``mamba_n_heads``,
``mamba_d_head``, ``mamba_d_state``, ``mamba_n_groups``,
``mamba_d_conv``, ``mamba_chunk_size``, ``embedding_multiplier``,
``attention_multiplier``, ``residual_multiplier``, ``logits_scaling``,
``rms_norm_eps``, ``vocab_size``. The head width is ``hidden_size /
num_attention_heads``; ``layer_types`` is the published list, read up to
the depth held.
"""

from benchmark import granite_cost

CAUSAL = True


def _layer_kinds(config):
    """``(attention layers, state-space layers)`` of the depth held."""
    ssm = granite_cost.ssm_layers(config)
    return config["num_hidden_layers"] - ssm, ssm


def build(config):
    from horovod_tpu.models import GraniteHybridLM

    if config["position_embedding_type"] != "nope":
        raise ValueError("the family has no positions to give: "
                         f"{config['position_embedding_type']!r}")
    return GraniteHybridLM(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        hidden=config["hidden_size"],
        layer_types=tuple(config["layer_types"]),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        mlp_dim=config["shared_intermediate_size"],
        ssm_heads=config["mamba_n_heads"],
        ssm_head_dim=config["mamba_d_head"],
        ssm_state=config["mamba_d_state"],
        ssm_groups=config["mamba_n_groups"],
        conv_taps=config["mamba_d_conv"],
        chunk=config["mamba_chunk_size"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        attention_multiplier=float(config["attention_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        norm_eps=config["rms_norm_eps"])


def loss(model, params, batch):
    """Mean next-token cross-entropy over this rank's positions."""
    from horovod_tpu.models import granite_loss

    return granite_loss(model, params, batch["tokens"])


def train_flops_per_token(config, seq_len):
    """Forward + backward FLOPs one token needs, by ``benchmark/flops.py``'s
    convention (6 for every weight a token is multiplied by, nothing
    recomputed, causal attention and the scan's in-chunk triangle counted
    as half), for the share of the model held here.

    A state-space layer: the in-projection hidden x (2 d_inner + 2 G N +
    heads) and the out-projection d_inner x hidden, d_inner = heads x
    head width; its scan by ``benchmark/granite_cost.py`` (3 x the
    forward's four matmul families). An attention layer: q and o, hidden
    x (heads x head_dim) each, k and v hidden x (kv heads x head_dim);
    attention 6 S w, w = heads x head_dim. Every layer's feed-forward 3 x
    hidden x shared_intermediate_size. The tied head vocab x hidden over
    the slice held. The embedding is a gather; norms, the convolution
    with its bias and SiLU, the gate, the step size and the decays are
    not counted.

    At the cell's size: nine state-space layers 9 x (17,432,576 +
    8,388,608); one attention layer 10,485,760; ten feed-forwards 10 x
    50,331,648; the head 51,380,224: 797,573,120 weights x 6 =
    4,785,438,720; attention 6 x 8192 x 2048 = 100,663,296; the scans 9 x
    9,535,488 = 85,819,392: 4,971,921,408 FLOPs a token."""
    hidden = config["hidden_size"]
    attention, ssm = _layer_kinds(config)
    head_dim = hidden // config["num_attention_heads"]
    wide = config["num_attention_heads"] * head_dim
    narrow = config["num_key_value_heads"] * head_dim
    inner = config["mamba_n_heads"] * config["mamba_d_head"]
    in_proj = 2 * inner + 2 * config["mamba_n_groups"] \
        * config["mamba_d_state"] + config["mamba_n_heads"]
    weights = ssm * hidden * (in_proj + inner) \
        + attention * hidden * (2 * wide + 2 * narrow) \
        + config["num_hidden_layers"] * 3 * hidden \
        * config["shared_intermediate_size"] \
        + config["vocab_size"] * hidden
    return float(6 * weights + 6 * attention * seq_len * wide
                 + 3 * ssm * granite_cost.ssd_flops_per_token_forward(config))


def attention_calls(config, rows, seq_len):
    """The flash-attention calls of one step on one chip holding ``rows``
    sequences: one an attention layer, forward and backward, over the
    query heads (the K/V heads are fewer: the bytes are counted as if
    each query head read its own)."""
    hidden = config["hidden_size"]
    return {"calls": _layer_kinds(config)[0], "batch": rows,
            "heads": config["num_attention_heads"], "seq_len": seq_len,
            "head_dim": hidden // config["num_attention_heads"],
            "causal": CAUSAL}
