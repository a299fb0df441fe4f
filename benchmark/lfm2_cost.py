"""Operations and bytes of the gated short convolution
(``horovod_tpu/ops/short_conv.py``), from shapes: the least a step's
convolution layers could cost, for ``short_conv_roofline_pct``.

Convention, as ``flops.py``'s and ``moe_kda_cost.py``'s: what the
algorithm needs, forward once and backward once, nothing recomputed (a
forward run again under the layer's rematerialisation is the program's
cost); bytes are what must cross HBM at least once, at the width the
program stores them (bf16 activations). A share over 100% is a wrong
count here.

``y = C * conv(B * X)`` over c channels has no matmul: two gates and the
taps are a handful of multiply-adds a channel on the vector unit, so the
FLOP count (a matmul's multiplies and adds) is 0 and the floor is
memory's. A token, a layer: the forward reads B, C and X and writes y, 4
c values; the backward reads B, C, X and dy and writes dB, dC and dX, 7 c
values: 22 c bytes in bf16 (45,056 at c = 2048). The taps and their
gradient are 2 x taps x c values a step, not a token: not counted. The
two projections around the chain are matmuls of the layer, not of the
convolution.
"""

BYTES_A_VALUE = 2           # bf16
VALUES_A_CHANNEL = 4 + 7    # forward + backward, above


def conv_layers(config):
    """The convolution layers of the depth held."""
    held = config["layer_types"][:config["num_hidden_layers"]]
    return sum(kind != "full_attention" for kind in held)


def short_conv_step_cost(config, tokens):
    """``(FLOPs, bytes)`` of a step's gated short convolutions over
    ``tokens`` tokens (all sequences together), every convolution layer,
    forward and backward."""
    return (0.0, float(conv_layers(config) * tokens * config["hidden_size"]
                       * VALUES_A_CHANNEL * BYTES_A_VALUE))
