"""Family ``sdar``: decoder-only mixture-of-experts LM (QK-normed rotary
grouped-query attention and a softmax-routed top-k mixture of SwiGLU
experts in every layer, an untied head) trained by block diffusion;
``horovod_tpu.models.SdarLM`` under ``sdar_loss``: one pass over a noisy
and a clean copy of every sequence under a block-structured attention
mask, the loss read off the noisy copy's masked tokens.

Keys read from the configuration are the published ones of
``JetLM/SDAR-30B-A3B-Chat``: ``num_hidden_layers``, ``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``num_experts`` (the experts HELD here), ``num_experts_per_tok``,
``moe_intermediate_size``, ``rope_theta``, ``rms_norm_eps``,
``vocab_size``; and six the published config lacks: ``router_width``
(the router's outputs: the published ``num_experts``),
``held_experts_first``, ``block_length``, ``mask_token_id``,
``noise_seed`` and, where it is there, ``whole_expert_blocks`` (the
configuration's ``assumed``).

The batch's ``tokens`` are (rows, L + 1): L tokens of data and, in column
L, the row's noise seed (the objective predicts no next token; the job
and the stream know nothing of this).
"""

# What ``benchmark/flops.py`` is told of the attention: the visible pairs
# are those of two causal calls a layer at L, whatever the kernels' own
# layout (one call over 2L).
CAUSAL = True


def build(config):
    from horovod_tpu.models import SdarLM

    return SdarLM(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        hidden=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        num_experts=config["router_width"],
        held_experts=(config["held_experts_first"], config["num_experts"]),
        top_k=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"],
        rope_base=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"],
        block=config["block_length"],
        mask_token=config["mask_token_id"],
        noise_seed=config["noise_seed"],
        whole_expert_blocks=config.get("whole_expert_blocks", False))


def loss(model, params, batch):
    """Block-diffusion training's loss over this rank's rows."""
    from horovod_tpu.models import sdar_loss

    return sdar_loss(model, params, batch["tokens"])


def _layer_weights(config):
    """The weights of one layer a row of the model's input meets: q and
    o, hidden x (heads x head_dim) each, k and v hidden x (kv heads x
    head_dim), the router hidden x router_width, and of the routed
    experts what a row meets HERE: top_k x held / router_width of an
    expert of 3 x hidden x width (8 x 16 / 128 = one expert's worth)."""
    hidden, width = config["hidden_size"], config["head_dim"]
    wide = config["num_attention_heads"] * width
    narrow = config["num_key_value_heads"] * width
    met = config["num_experts_per_tok"] * config["num_experts"] \
        / config["router_width"]
    return hidden * (2 * wide + 2 * narrow) \
        + hidden * config["router_width"] \
        + met * 3 * hidden * config["moe_intermediate_size"]


def train_flops_per_token(config, seq_len):
    """Forward + backward FLOPs one token OF DATA needs, by
    ``benchmark/flops.py``'s convention (6 for every weight a row is
    multiplied by, nothing recomputed), for the share of the model held
    here. A token of data is two rows through every layer (its noisy and
    its clean copy) and one through the head (the noisy copy's), so the
    layers' weights count twice and the head's slice once. Attention: the
    clean copy's rows see the clean keys up to their own block and the
    noisy copy's the clean keys before their block, L^2 / 2 pairs each:
    twice a causal model's 6 S w, w = heads x head_dim; the noisy copy's
    own blocks (L x block pairs, under a thousandth of L^2 at L = 4096)
    are left out. The embedding is a gather; norms, the rotation and the
    noise are not counted.

    At the cell's size: a layer 18,874,368 (q, k, v, o) + 262,144 (router)
    + 4,718,592 (one expert's worth) = 23,855,104; (2 x 6 x 23,855,104 +
    38,895,616) x 6 = 1,950,941,184 in weights and 6 layers x 2 x 6 x 4096
    x 4096 = 1,207,959,552 in attention: 3,158,900,736 FLOPs a token."""
    layers = config["num_hidden_layers"]
    wide = config["num_attention_heads"] * config["head_dim"]
    weights = 2 * layers * _layer_weights(config) \
        + config["vocab_size"] * config["hidden_size"]
    return float(6 * weights + 2 * 6 * layers * seq_len * wide)


def attention_calls(config, rows, seq_len):
    """The attention work of one step on one chip holding ``rows``
    sequences, in the terms ``benchmark/flops.py`` has: the pairs the
    mask allows are those of TWO causal calls a layer at L (the clean
    copy on itself, the noisy copy on the clean past), so ``calls`` is
    twice the layers, ``seq_len`` L and ``causal`` true, whatever the
    kernels' own layout (one call a layer over 2L, three quarters of
    whose tiles are empty): ``flash_roofline_pct`` reads the kernels
    against the pairs the mask allows, not against the square they skip.
    The bytes are counted as if each query head read its own K/V."""
    return {"calls": 2 * config["num_hidden_layers"], "batch": rows,
            "heads": config["num_attention_heads"], "seq_len": seq_len,
            "head_dim": config["head_dim"], "causal": CAUSAL}
