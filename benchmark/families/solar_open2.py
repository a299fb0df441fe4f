"""Family ``solar_open2``: decoder-only causal LM whose layers are softmax
grouped-query attention or gated delta-rule linear attention (KDA) by a
pattern, each followed by a sparse mixture of experts with a shared
expert; ``horovod_tpu.models.SolarLM``, trained on the mean next-token
cross-entropy.

Keys read from the configuration are the published ones of
``upstage/Solar-Open2-250B``: ``num_hidden_layers``, ``hidden_size``,
``gqa_layers``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``linear_attn_config`` (``num_heads``, ``head_dim``,
``short_conv_kernel_size``), ``n_routed_experts`` (the experts HELD
here), ``num_experts_per_tok``, ``moe_intermediate_size``,
``n_shared_experts``, ``routed_scaling_factor``, ``vocab_size``,
``rms_norm_eps``; and three the published config lacks: ``router_width``
(the router's outputs: the published ``n_routed_experts``),
``held_experts_first`` and ``kda_gate_rank`` (``assumed``).
"""

from benchmark import moe_kda_cost

CAUSAL = True


def _softmax_layers(config):
    return [i for i in config["gqa_layers"]
            if i < config["num_hidden_layers"]]


def build(config):
    from horovod_tpu.models import SolarLM

    linear = config["linear_attn_config"]
    return SolarLM(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        hidden=config["hidden_size"],
        gqa_layers=tuple(_softmax_layers(config)),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        kda_heads=linear["num_heads"], kda_head_dim=linear["head_dim"],
        conv_size=linear["short_conv_kernel_size"],
        gate_rank=config["kda_gate_rank"],
        num_experts=config["router_width"],
        held_experts=(config["held_experts_first"],
                      config["n_routed_experts"]),
        top_k=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"],
        shared_dim=config["n_shared_experts"]
        * config["moe_intermediate_size"],
        routed_scale=float(config["routed_scaling_factor"]),
        norm_eps=config["rms_norm_eps"],
        # the cell's counters (moe_load_max_over_mean, the routes of
        # moe_expert_roofline_pct) are read from the program's gauges
        publish_stats=True)


def loss(model, params, batch):
    """Mean next-token cross-entropy over this rank's positions."""
    from horovod_tpu.models import solar_loss

    return solar_loss(model, params, batch["tokens"])


def train_flops_per_token(config, seq_len):
    """Forward + backward FLOPs one token needs, by ``benchmark/flops.py``'s
    convention (6 for every weight a token is multiplied by, nothing
    recomputed, causal attention counted as half), for the share of the
    model held here.

    A softmax layer: q, gate and o, hidden x (heads x head_dim) each, k
    and v hidden x (kv heads x head_dim); attention 6 S w, w = heads x
    head_dim. A KDA layer: q, k, v and o, hidden x (heads x head_dim), the
    two low-rank gates (hidden x rank + rank x heads x head_dim each),
    beta hidden x heads; its recurrence as the chunked algorithm's
    products, chunks of 64 (``benchmark/moe_kda_cost.py``: the pair
    matrices A and P, 2 C d each a head; the unit triangular solve for W
    and U0, 2 C d; W S, Q S and K^T U against the d x d state, 2 d d each;
    P U, 2 C d), forward once and backward twice. Every
    layer: the router hidden x router_width, the shared expert 3 x hidden
    x width, and of the routed experts what a token meets HERE: top_k x
    held / router_width of an expert (8 x 8 / 320 = 0.2). The head vocab
    x hidden over the slice held. The embedding is a gather; norms,
    convolutions and gates' elementwise work are not counted."""
    hidden, d = config["hidden_size"], config["head_dim"]
    linear = config["linear_attn_config"]
    kda = moe_kda_cost.kda_layers(config)
    soft = config["num_hidden_layers"] - kda
    wide = config["num_attention_heads"] * d
    narrow = config["num_key_value_heads"] * d
    lin = linear["num_heads"] * linear["head_dim"]
    rank = config["kda_gate_rank"]
    expert = 3 * hidden * config["moe_intermediate_size"]
    met = config["num_experts_per_tok"] * config["n_routed_experts"] \
        / config["router_width"]
    weights = soft * hidden * (3 * wide + 2 * narrow) \
        + kda * (4 * hidden * lin + 2 * (hidden * rank + rank * lin)
                 + hidden * linear["num_heads"]) \
        + config["num_hidden_layers"] * (
            hidden * config["router_width"]
            + (config["n_shared_experts"] + met) * expert) \
        + config["vocab_size"] * hidden
    return float(6 * weights + 6 * soft * seq_len * wide
                 + 3 * kda * moe_kda_cost.kda_flops_per_token_forward(config))


def attention_calls(config, rows, seq_len):
    """The flash-attention calls of one step on one chip holding ``rows``
    sequences: one a softmax layer, forward and backward, over the query
    heads held here (the K/V heads are fewer: the bytes are counted as if
    each query head read its own)."""
    return {"calls": len(_softmax_layers(config)), "batch": rows,
            "heads": config["num_attention_heads"], "seq_len": seq_len,
            "head_dim": config["head_dim"], "causal": CAUSAL}
