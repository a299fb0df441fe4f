"""Operations and bytes the algorithms need, from shapes alone.

These are the benchmark's counts, not the executable's: recomputation,
padding and whatever else a program does beyond the mathematics are not
counted, so a change that does less of them shows as a gain and one that
does more as a loss.
"""

from __future__ import annotations


def transformer_train_flops_per_token(layers: int, hidden: int, mlp: int,
                                      vocab: int, seq_len: int,
                                      causal: bool) -> float:
    """Forward + backward FLOPs one token needs in a dense transformer
    with a (tied) vocabulary head: 6 for every weight a token is
    multiplied by (2 forward, 4 backward), plus attention's score and
    value products.

    Weights in matmuls: per layer 4 h^2 (q, k, v, out) + 2 h m (MLP),
    plus the head's vocab x h. The embedding lookup is a gather, not a
    matmul; norms, biases and position tables are not counted.

    Attention, per token and layer, forward: q.k over ``seq_len`` keys
    (2 S h) and probabilities times v (2 S h); backward twice that:
    12 S h in all. A causal model needs half: each query sees on average
    half the keys."""
    weights = layers * (4 * hidden * hidden + 2 * hidden * mlp) \
        + vocab * hidden
    attention = 12 * layers * seq_len * hidden
    if causal:
        attention //= 2
    return float(6 * weights + attention)


def flash_attention_cost(batch: int, heads: int, seq_len: int,
                         head_dim: int, causal: bool,
                         dtype_bytes: int = 2) -> dict:
    """``{"fwd": (ops, bytes), "bwd": (ops, bytes)}`` of one attention
    call on ``(batch, seq_len, heads, head_dim)``.

    One S x S x d product is 2 S^2 d FLOPs a head. Forward needs two
    (scores, values). Backward needs five: the scores again, dP, dV, dK
    and dQ (the flash algorithm keeps no S x S matrix, so recomputing the
    scores once is part of it; a kernel that recomputes them twice is
    charged for one). Causal needs half of each.

    Bytes are what must cross HBM at least once: forward reads q, k, v
    and writes o and the fp32 log-sum-exp row; backward reads q, k, v, o,
    do and that row and writes dq, dk, dv."""
    product = 2.0 * batch * heads * seq_len * seq_len * head_dim
    if causal:
        product /= 2
    tensor = float(batch * heads * seq_len * head_dim * dtype_bytes)
    row = float(batch * heads * seq_len * 4)
    return {"fwd": (2 * product, 4 * tensor + row),
            "bwd": (5 * product, 8 * tensor + row)}


def roofline_seconds(ops: float, nbytes: float, peaks: dict) -> tuple:
    """``(seconds, bound)``: the least time one chip could take for bf16
    operations, and whether ``"compute"`` or ``"memory"`` sets it."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def attention_step_roofline(attention: dict, peaks: dict) -> tuple:
    """``(seconds, {"fwd": bound, "bwd": bound})``: the least time one chip
    could take for a step's attention calls. ``attention`` is a family's
    ``attention_calls``: ``calls`` (one a layer, forward and backward
    each), ``batch``, ``heads``, ``seq_len``, ``head_dim``, ``causal``."""
    cost = flash_attention_cost(attention["batch"], attention["heads"],
                                attention["seq_len"], attention["head_dim"],
                                attention["causal"])
    least, which = 0.0, {}
    for part, (ops, nbytes) in cost.items():
        seconds, which[part] = roofline_seconds(ops, nbytes, peaks)
        least += attention["calls"] * seconds
    return least, which
