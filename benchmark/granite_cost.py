"""Operations and bytes of the selective state-space recurrence
(``horovod_tpu/ops/ssd.py``: Mamba-2's SSD, a scalar decay a head), from
shapes: the least a step's scans could cost, for ``ssd_roofline_pct``.

Convention, as ``flops.py``'s and ``moe_kda_cost.py``'s: what the
algorithm needs, forward once and backward once, nothing recomputed (the
layers are rematerialised: a forward run again is the program's cost, not
the mathematics'); a FLOP is a multiply or an add of a matmul; bytes are
what must cross HBM at least once, operands and results at the width the
program stores them (bf16 activations). The same count whatever
implements the scan, XLA's fusions or a kernel. A share over 100% is a
wrong count here.

**Operations**: the four matmul families of the chunked form at chunks of
Q = ``mamba_chunk_size``, a token, a layer, H heads of P channels, G
groups of B and C over a state of N. Inside a chunk a token meets the
tokens up to itself: the triangle is counted HALF, as ``flops.py`` counts
causal attention (Q (Q + 1) / 2 pairs of Q x Q; the program multiplies
the whole square and masks it). ``C B^T``: 2 N a pair a group, Q / 2
pairs a token: G N Q. The pair matrix times ``delta x``: 2 P a pair a
head: H P Q. A chunk's own state ``B^T (decay delta x)``: 2 N P a head.
``C`` times the state at the chunk's start: 2 N P a head. Forward ``G N Q
+ H P Q + 4 H N P``; the backward twice that (each product has two
transposes). The carry across chunks is one decay and one add of a state
a chunk, 2 H N P / Q a token: not counted, nor are the step size, the
decays and the ``D`` skip (vector work).

**Bytes**, a token, a layer: the forward reads x (H P), dt (H), B and C
(G N each) and writes y (H P); the backward reads them and dy again and
writes dx, d dt, dB and dC: ``5 H P + 3 H + 6 G N`` values of 2 bytes.
``A``, ``D`` and ``dt_bias`` are H values a step, not a token: not
counted. The pair matrices and the states never need to leave the chip's
fast memory: a program that writes them to HBM pays for it in its share.

At the cell's size (H 64, P 64, N 128, G 1, Q 256): 3,178,496 FLOPs a
token a layer forward, 9,535,488 with the backward; 42,880 bytes. Nine
layers over 8,192 tokens: 0.703 TFLOP (3.57 ms at the bf16 peak) against
3.16 GB (3.86 ms at the HBM peak): the floor is memory's, narrowly.
"""

BYTES_A_VALUE = 2           # bf16
ATTENTION = "attention"     # a ``layer_types`` entry; any other: mamba


def ssm_layers(config):
    """The state-space layers of the depth held."""
    held = config["layer_types"][:config["num_hidden_layers"]]
    return sum(kind != ATTENTION for kind in held)


def ssd_flops_per_token_forward(config):
    """Forward FLOPs a token of one layer's scan, all heads."""
    heads, width = config["mamba_n_heads"], config["mamba_d_head"]
    groups, n = config["mamba_n_groups"], config["mamba_d_state"]
    chunk = config["mamba_chunk_size"]
    return groups * n * chunk + heads * width * chunk \
        + 4 * heads * n * width


def ssd_bytes_per_token(config):
    """Bytes a token of one layer's scan moves, forward and backward."""
    heads, width = config["mamba_n_heads"], config["mamba_d_head"]
    groups, n = config["mamba_n_groups"], config["mamba_d_state"]
    return BYTES_A_VALUE * (5 * heads * width + 3 * heads + 6 * groups * n)


def ssd_step_cost(config, tokens):
    """``(FLOPs, bytes)`` of a step's scans over ``tokens`` tokens (all
    sequences together), every state-space layer, forward and
    backward."""
    calls = ssm_layers(config) * tokens
    return (3.0 * calls * ssd_flops_per_token_forward(config),
            float(calls * ssd_bytes_per_token(config)))
