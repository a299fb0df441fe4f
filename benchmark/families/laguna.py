"""Family ``laguna``: decoder-only mixture-of-experts LM whose layers are
full causal attention or attention over a sliding window by a pattern,
the two kinds at different query-head counts on the same K/V heads, each
head's output gated, each kind with a rotation of its own (a YaRN
rotation of half the head width beside a plain one of the whole); a dense
SwiGLU feed-forward by ``mlp_layer_types`` and a softmax-routed top-k
mixture of experts with a shared expert elsewhere; an untied head;
``horovod_tpu.models.LagunaLM``, trained on the mean next-token
cross-entropy.

Keys read from the configuration are the published ones of
``poolside/Laguna-S-2.1``: ``num_hidden_layers``, ``hidden_size``,
``layer_types``, ``num_attention_heads_per_layer``,
``num_key_value_heads``, ``head_dim``, ``sliding_window``,
``rope_parameters`` (both entries), ``mlp_layer_types``,
``intermediate_size``, ``num_experts`` (the experts HELD here),
``num_experts_per_tok``, ``moe_intermediate_size``,
``shared_expert_intermediate_size``, ``moe_routed_scaling_factor``,
``rms_norm_eps``, ``vocab_size``; and the ones the published config
lacks: ``router_width`` (the router's outputs: the published
``num_experts``), ``held_experts_first`` and, where it is there,
``whole_expert_blocks`` (the configuration's ``assumed``). The three
per-layer lists are the published ones, read up to the depth held.
"""

from benchmark import laguna_cost

CAUSAL = True


def _rotation(rope, head_dim):
    from horovod_tpu.ops.rope import Rotation

    width = int(rope["partial_rotary_factor"] * head_dim)
    plain = Rotation(base=float(rope["rope_theta"]),
                     width=None if width == head_dim else width)
    if rope["rope_type"] == "default":
        return plain
    return Rotation(
        base=plain.base, width=plain.width, factor=float(rope["factor"]),
        original_length=rope["original_max_position_embeddings"],
        beta_fast=float(rope["beta_fast"]),
        beta_slow=float(rope["beta_slow"]),
        scale=float(rope["attention_factor"]))


def build(config):
    from horovod_tpu.models import LagunaLM

    rope, width = config["rope_parameters"], config["head_dim"]
    return LagunaLM(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        hidden=config["hidden_size"],
        layer_types=tuple(config["layer_types"]),
        heads_per_layer=tuple(config["num_attention_heads_per_layer"]),
        num_kv_heads=config["num_key_value_heads"],
        head_dim=width,
        window=config["sliding_window"],
        full_rotation=_rotation(rope["full_attention"], width),
        window_rotation=_rotation(rope["sliding_attention"], width),
        mlp_layer_types=tuple(config["mlp_layer_types"]),
        mlp_dim=config["intermediate_size"],
        num_experts=config["router_width"],
        held_experts=(config["held_experts_first"], config["num_experts"]),
        top_k=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"],
        shared_dim=config["shared_expert_intermediate_size"],
        routed_scale=float(config["moe_routed_scaling_factor"]),
        norm_eps=config["rms_norm_eps"],
        whole_expert_blocks=config.get("whole_expert_blocks", False))


def loss(model, params, batch):
    """Mean next-token cross-entropy over this rank's positions."""
    from horovod_tpu.models import laguna_loss

    return laguna_loss(model, params, batch["tokens"])


def _weights_a_token(config):
    """The weights a token is multiplied by, for the share held here."""
    hidden, width = config["hidden_size"], config["head_dim"]
    narrow = config["num_key_value_heads"] * width
    met = config["num_experts_per_tok"] * config["num_experts"] \
        / config["router_width"]
    sparse = hidden * config["router_width"] \
        + 3 * hidden * config["shared_expert_intermediate_size"] \
        + met * 3 * hidden * config["moe_intermediate_size"]
    dense = 3 * hidden * config["intermediate_size"]
    total = config["vocab_size"] * hidden
    for (heads, _), mlp in zip(laguna_cost.layers(config),
                               config["mlp_layer_types"]):
        total += hidden * (2 * heads * width + 2 * narrow + heads) \
            + (dense if mlp == "dense" else sparse)
    return total


def train_flops_per_token(config, seq_len):
    """Forward + backward FLOPs one token needs, by ``benchmark/flops.py``'s
    convention (6 for every weight a token is multiplied by, nothing
    recomputed, attention 12 FLOPs a visible (query, key) pair and channel
    of a head), for the share of the model held here.

    A layer's mixer: q and o, hidden x (heads x head_dim) each at the
    LAYER'S head count, k and v hidden x (kv heads x head_dim), the gate
    hidden x heads. A dense feed-forward 3 x hidden x intermediate_size; a
    sparse one the router hidden x router_width, the shared expert 3 x
    hidden x its width, and of the routed experts what a token meets HERE:
    top_k x held / router_width of an expert of 3 x hidden x width (10 x 8
    / 256 = 0.3125 of one). The untied head vocab x hidden over the slice
    held. The embedding is a gather; norms, the rotations and the gates'
    sigmoids are not counted. Attention: a full layer's query sees S / 2
    keys on average (``flops.py``'s causal half), 6 S w, w = heads x
    head_dim; a window layer's P_w / S, P_w = window x S - window x
    (window - 1) / 2 (``laguna_cost.window_pairs``): 12 (P_w / S) w.

    At the cell's size: layer 0 (full, dense) 44,187,648 + 113,246,208;
    layers 1-3 (window, sparse) 3 x (63,135,744 + 786,432 + 9,437,184 +
    2,949,120); layer 4 (full, sparse) 44,187,648 + 13,172,736; the head
    38,535,168: 482,254,848 weights x 6 = 2,893,529,088, and attention 2 x
    6 x 8192 x 6144 + 3 x 12 x (4,063,488 / 8192) x 9216 = 603,979,776 +
    164,571,264: 3,662,080,128 FLOPs a token."""
    width = config["head_dim"]
    attention = 0.0
    for heads, window in laguna_cost.layers(config):
        pairs = laguna_cost.window_pairs(
            seq_len, config["sliding_window"]) if window \
            else seq_len * seq_len / 2
        attention += 12 * pairs / seq_len * heads * width
    return float(6 * _weights_a_token(config) + attention)


def attention_calls(config, rows, seq_len):
    """The flash-attention work of one step on one chip holding ``rows``
    sequences, in the terms ``benchmark/flops.py`` has, which are ONE
    shape: the causal call of a full layer (its heads, S, the head width),
    with ``calls`` (which is only ever multiplied) the number of such
    calls whose operations equal the step's: one a full layer, and a
    window layer as ``heads_l / heads x P_w / (S^2 / 2)`` of one, P_w the
    pairs its window allows (``laguna_cost.window_pairs``). At the cell's
    size 2 + 3 x 1.5 x 4,063,488 / 33,554,432 = 2.545. The bytes this
    counts are 2.545 x 48 heads' tensors where the calls move 2 x 48 + 3 x
    72 heads': fewer than the true bytes, so ``flash_roofline_pct`` reads
    the kernels of both kinds against the pairs the masks allow and can
    read low, never over (both kinds are compute-bound at ``peaks.json``'s
    numbers: ``tests/benchmark/test_benchmark_laguna.py``)."""
    kinds = laguna_cost.layers(config)
    full = next(heads for heads, window in kinds if not window)
    causal_pairs = seq_len * seq_len / 2
    window_pairs = laguna_cost.window_pairs(seq_len, config["sliding_window"])
    calls = sum(heads / full * (window_pairs / causal_pairs if window
                                else 1.0) for heads, window in kinds)
    return {"calls": calls, "batch": rows, "heads": full,
            "seq_len": seq_len, "head_dim": config["head_dim"],
            "causal": CAUSAL}
