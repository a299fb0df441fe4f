"""Family ``ouro``: looped decoder-only causal LM with an exit at every
pass, ``horovod_tpu.models.LoopLM``, trained on ``looplm_loss``.

Keys read from the configuration are the published ones of
``ByteDance/Ouro-2.6B``: ``num_hidden_layers``, ``hidden_size``,
``num_attention_heads``, ``head_dim``, ``intermediate_size``,
``vocab_size``, ``total_ut_steps``, ``rope_theta``, ``rms_norm_eps``; and
``exit_entropy_beta``, which the published config lacks (``assumed``).
"""

CAUSAL = True

# ``loss`` is given the model and not the configuration: what ``build``
# read of the loss's own parameter, by the model it built.
_BETA = {}


def build(config):
    from horovod_tpu.models import LoopLM

    model = LoopLM(vocab_size=config["vocab_size"],
                   num_layers=config["num_hidden_layers"],
                   hidden=config["hidden_size"],
                   num_heads=config["num_attention_heads"],
                   head_dim=config["head_dim"],
                   mlp_dim=config["intermediate_size"],
                   passes=config["total_ut_steps"],
                   rope_base=float(config["rope_theta"]),
                   norm_eps=config["rms_norm_eps"])
    _BETA[model] = config["exit_entropy_beta"]
    return model


def loss(model, params, batch):
    """Mean over this rank's positions of the exit-weighted next-token
    loss."""
    from horovod_tpu.models import looplm_loss

    return looplm_loss(model, params, batch["tokens"], _BETA[model])


def train_flops_per_token(config, seq_len):
    """Forward + backward FLOPs one token needs, by ``benchmark/flops.py``'s
    convention (6 for every weight a token is multiplied by, nothing
    recomputed, causal attention counted as half), written out because the
    stack runs ``total_ut_steps`` times and every pass has an exit.

    A layer's matmul weights: q, k, v and o, each hidden x (heads x
    head_dim), and the SwiGLU's three hidden x intermediate. A token
    meets them once a pass, and the head's vocab x hidden once an exit.
    Attention, a token, a layer application: q.k and p.v over S keys,
    forward 4 S w and backward 8 S w with w = heads x head_dim, half of
    it under the causal mask: 6 S w. The embedding is a gather; norms and
    the gate's matvec (hidden weights an exit) are not counted."""
    hidden, width = config["hidden_size"], \
        config["num_attention_heads"] * config["head_dim"]
    layer = 4 * hidden * width + 3 * hidden * config["intermediate_size"]
    applications = config["total_ut_steps"] * config["num_hidden_layers"]
    weights = applications * layer \
        + config["total_ut_steps"] * config["vocab_size"] * hidden
    return float(6 * weights + 6 * applications * seq_len * width)


def attention_calls(config, rows, seq_len):
    """The flash-attention calls of one step on one chip holding ``rows``
    sequences: one a layer application (layers x passes), forward and
    backward; the forward run again under rematerialisation is not a
    call the mathematics needs."""
    return {"calls": config["total_ut_steps"] * config["num_hidden_layers"],
            "batch": rows, "heads": config["num_attention_heads"],
            "seq_len": seq_len, "head_dim": config["head_dim"],
            "causal": CAUSAL}
