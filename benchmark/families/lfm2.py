"""Family ``lfm2``: decoder-only causal LM whose token mixer is a gated
short convolution or rotary grouped-query attention by a pattern, and
whose feed-forward is a dense SwiGLU in the leading layers and a sparse
mixture of experts, routed by a sigmoid with a selection bias, in the
rest; ``horovod_tpu.models.Lfm2LM``, trained on the mean next-token
cross-entropy over a tied head.

Keys read from the configuration are the published ones of
``LiquidAI/LFM2-8B-A1B``: ``num_hidden_layers``, ``hidden_size``,
``layer_types``, ``num_attention_heads``, ``num_key_value_heads``,
``conv_L_cache``, ``num_dense_layers``, ``intermediate_size``,
``num_experts`` (the experts HELD here), ``num_experts_per_tok``,
``moe_intermediate_size``, ``routed_scaling_factor``, ``rope_theta``,
``norm_eps``, ``vocab_size``; and two the published config lacks:
``router_width`` (the router's outputs: the published ``num_experts``)
and ``held_experts_first``. The head width is ``hidden_size /
num_attention_heads``.
"""

from benchmark import lfm2_cost

CAUSAL = True


def _layer_kinds(config):
    """``(attention layers, convolution layers)`` of the depth held."""
    conv = lfm2_cost.conv_layers(config)
    return config["num_hidden_layers"] - conv, conv


def build(config):
    from horovod_tpu.models import Lfm2LM

    return Lfm2LM(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        hidden=config["hidden_size"],
        layer_types=tuple(config["layer_types"]),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        conv_taps=config["conv_L_cache"],
        num_dense_layers=config["num_dense_layers"],
        mlp_dim=config["intermediate_size"],
        num_experts=config["router_width"],
        held_experts=(config["held_experts_first"], config["num_experts"]),
        top_k=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"],
        routed_scale=float(config["routed_scaling_factor"]),
        rope_base=float(config["rope_theta"]),
        norm_eps=config["norm_eps"])


def loss(model, params, batch):
    """Mean next-token cross-entropy over this rank's positions."""
    from horovod_tpu.models import lfm2_loss

    return lfm2_loss(model, params, batch["tokens"])


def train_flops_per_token(config, seq_len):
    """Forward + backward FLOPs one token needs, by ``benchmark/flops.py``'s
    convention (6 for every weight a token is multiplied by, nothing
    recomputed, causal attention counted as half), for the share of the
    model held here.

    A convolution layer: the in-projection hidden x 3 hidden and the
    out-projection hidden x hidden. An attention layer: q and o, hidden x
    (heads x head_dim) each, k and v hidden x (kv heads x head_dim);
    attention 6 S w, w = heads x head_dim. A dense layer's feed-forward 3
    x hidden x intermediate_size; an expert layer's the router hidden x
    router_width and of the routed experts what a token meets HERE: top_k
    x held / router_width of an expert of 3 x hidden x width (4 x 8 / 32 =
    one expert's worth). The tied head vocab x hidden over the slice
    held. The embedding is a gather; norms, the convolution's gates and
    taps and the rotation are not counted.

    At the cell's size: layers 0-1 2 x (16,777,216 + 44,040,192); the four
    convolution-expert layers 4 x (16,777,216 + 65,536 + 11,010,048); the
    two attention-expert layers 2 x (10,485,760 + 65,536 + 11,010,048);
    the head 33,554,432: 309,723,136 weights x 6 + attention 2 x 6 x 8192
    x 2048 = 2,059,665,408 FLOPs a token."""
    hidden, layers = config["hidden_size"], config["num_hidden_layers"]
    attention, conv = _layer_kinds(config)
    head_dim = hidden // config["num_attention_heads"]
    wide = config["num_attention_heads"] * head_dim
    narrow = config["num_key_value_heads"] * head_dim
    dense = min(config["num_dense_layers"], layers)
    met = config["num_experts_per_tok"] * config["num_experts"] \
        / config["router_width"]
    weights = conv * 4 * hidden * hidden \
        + attention * hidden * (2 * wide + 2 * narrow) \
        + dense * 3 * hidden * config["intermediate_size"] \
        + (layers - dense) * (
            hidden * config["router_width"]
            + met * 3 * hidden * config["moe_intermediate_size"]) \
        + config["vocab_size"] * hidden
    return float(6 * weights + 6 * attention * seq_len * wide)


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
