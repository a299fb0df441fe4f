"""Operations and bytes of the scalar-gated delta-rule recurrence
(``horovod_tpu/ops/linear_attention.py`` ``gated_delta_attention``: Gated
DeltaNet, one decay scalar a head, keys of d_k and values of d_v
channels), from shapes: the least a step's recurrences could cost, for
``gdn_roofline_pct``.

Convention, as ``flops.py``'s, ``moe_kda_cost.py``'s and
``granite_cost.py``'s: what the algorithm needs, forward once and backward
once, nothing recomputed (the layers are rematerialised: a forward run
again is the program's cost, not the mathematics'); a FLOP is a multiply
or an add of a matmul; bytes are what must cross HBM at least once,
operands and results at the width the program stores them (bf16
activations, fp32 decays and write strengths). The same count whatever
implements the recurrence, XLA's operations or a kernel, and whatever
chunk the program cuts the sequence into: the count is taken at chunks of
``CHUNK`` = 64 tokens. A share over 100% is a wrong count here.

**Operations**, a token, a head, forward, at chunks of C. Inside a chunk a
token meets the tokens up to itself: the triangle is counted HALF, as
``flops.py`` counts causal attention (C / 2 pairs a token; the program
multiplies the whole square and masks it). The keys' pair matrix ``K
K^T``: 2 d_k a pair: C d_k. The queries' ``Q K^T``: C d_k. The unit
triangular solve applied to ``beta [K e^g | V]``, d_k + d_v columns, a
multiply-add a pair a column: C (d_k + d_v). ``P U``: 2 d_v a pair: C d_v.
The three products with the d_k x d_v state (``W S``, ``Q~ S``, ``K-^T
U``): 2 d_k d_v each. Forward ``C (3 d_k + 2 d_v) + 6 d_k d_v``; the
backward twice that (each product has two transposes). The decay mask,
the running sums, the L2 norms and the carry's scaling of the state are
vector work: not counted.

**Bytes**, a token, a head: the forward reads q, k (d_k each) and v (d_v)
in bf16, the log decay and beta in fp32, and writes o (d_v, bf16): ``2 (2
d_k + 2 d_v) + 8``; the backward reads q, k, v and do and the two scalars
and writes dq, dk, dv and the two scalars' gradients: ``2 (4 d_k + 3 d_v)
+ 16``. ``A_log`` and ``dt_bias`` are H values a step: not counted. The
pair matrices, the solve and the states never need to leave the chip's
fast memory: a program that writes them to HBM pays for it in its share.

At the cell's size (H 30, d_k 96, d_v 192, C 64): 153,600 FLOPs a token a
head forward, 4,608,000 a layer, 13,824,000 with the backward; 3,096
bytes a token a head, 92,880 a layer. Three layers over 8,192 tokens:
0.340 TFLOP (1.72 ms at the bf16 peak) against 2.28 GB (2.79 ms at the HBM
peak): the floor is memory's.
"""

CHUNK = 64                      # the count's, whatever the program's
ATTENTION = "full_attention"    # a ``layer_types`` entry; any other: linear


def linear_layers(config):
    """The Gated DeltaNet layers of the depth held."""
    held = config["layer_types"][:config["num_hidden_layers"]]
    return sum(kind != ATTENTION for kind in held)


def _widths(config):
    return (config["linear_num_value_heads"], config["linear_key_head_dim"],
            config["linear_value_head_dim"])


def gdn_flops_per_token_forward(config, chunk=CHUNK):
    """Forward FLOPs a token of one layer's recurrence, all heads."""
    heads, dk, dv = _widths(config)
    return heads * (chunk * (3 * dk + 2 * dv) + 6 * dk * dv)


def gdn_bytes_per_token(config):
    """Bytes a token of one layer's recurrence moves, forward and
    backward, all heads."""
    heads, dk, dv = _widths(config)
    return heads * (2 * (2 * dk + 2 * dv) + 8 + 2 * (4 * dk + 3 * dv) + 16)


def gdn_step_cost(config, tokens):
    """``(FLOPs, bytes)`` of a step's recurrences over ``tokens`` tokens
    (all sequences together), every Gated DeltaNet layer, forward and
    backward."""
    calls = linear_layers(config) * tokens
    return (3.0 * calls * gdn_flops_per_token_forward(config),
            float(calls * gdn_bytes_per_token(config)))
