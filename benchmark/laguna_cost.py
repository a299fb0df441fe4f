"""Operations and bytes of attention over a sliding window
(``horovod_tpu/ops/flash_attention.py`` under ``SlidingWindowMask``), from
shapes: what ``families/laguna.py`` counts a token's attention by, and
the least a step's window layers' flash calls could cost, for
``swa_flash_roofline_pct``.

Convention, ``flops.py``'s for the flash kernels: one product over P
(query, key) pairs of a head of width d is 2 P d FLOPs; the forward needs
two (scores, values), the backward five (the scores again, dP, dV, dK,
dQ), nothing else recomputed; bytes are what must cross HBM at least
once, as if each query head read its own K/V: the forward reads q, k, v
and writes o and the fp32 log-sum-exp row, the backward reads q, k, v, o,
do and that row and writes dq, dk, dv. What differs from a causal call is
P alone: a query at position u sees min(u + 1, window) keys, so a head
has

    P_w = window x S - window x (window - 1) / 2          (window <= S)

pairs and not S^2 / 2 (``flops.py`` counts a causal head's as S^2 / 2,
the diagonal's half left out: the two conventions meet at window = S to
within S / 2 pairs). A share over 100% is a wrong count here.
"""

BYTES_A_VALUE = 2       # bf16 activations


def window_pairs(seq_len, window):
    """The (query, key) pairs one head's window attention sees."""
    window = min(window, seq_len)
    return window * seq_len - window * (window - 1) // 2


def layers(config):
    """``((query heads, is a window layer), ...)`` of the depth held."""
    held = config["num_hidden_layers"]
    return tuple(
        (heads, kind != "full_attention") for heads, kind in zip(
            config["num_attention_heads_per_layer"][:held],
            config["layer_types"][:held]))


def window_heads(config):
    """Query heads of all window layers of the depth held, together."""
    return sum(heads for heads, window in layers(config) if window)


def swa_step_cost(config, rows, seq_len):
    """``{"fwd": (FLOPs, bytes), "bwd": (FLOPs, bytes)}`` of a step's
    window-attention calls over ``rows`` sequences of ``seq_len``, every
    window layer together."""
    heads, d = rows * window_heads(config), config["head_dim"]
    product = 2.0 * heads * window_pairs(
        seq_len, config["sliding_window"]) * d
    tensor = float(heads * seq_len * d * BYTES_A_VALUE)
    row = float(heads * seq_len * 4)
    return {"fwd": (2 * product, 4 * tensor + row),
            "bwd": (5 * product, 8 * tensor + row)}
