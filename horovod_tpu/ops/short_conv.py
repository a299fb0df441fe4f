"""Short causal depthwise convolutions along the sequence: the plain one
(the taps of a linear-attention layer's q, k and v, ``models/solar.py``)
and the gated one that is a whole token mixer (``models/lfm2.py``):

    y = C * conv(B * x),    conv(z)_t = sum_j taps[j] z_{t - (n - 1) + j}

B, C and x are three slices of one projection of the layer's input; no
activation, no norm. There is no matmul in it: two gates and ``n`` taps a
channel, bound by the bytes of B, C, x and y (``benchmark/lfm2_cost.py``
counts them). XLA code; the chain carries the scope ``hvd_short_conv``
(``common/scopes.py``), and a kernel that takes its place is named with
the scope as its prefix, so that the readers of a trace find either.
"""

import jax
import jax.numpy as jnp

from ..common import scopes


def causal_conv(x, taps):
    """Depthwise causal convolution along S: ``y_t = sum_j taps[j]
    x_{t - (n - 1) + j}``, fp32. x: (B, S, C); taps: (n, C)."""
    n = taps.shape[0]
    x = jnp.pad(x.astype(jnp.float32), ((0, 0), (n - 1, 0), (0, 0)))
    s = x.shape[1] - (n - 1)
    return sum(x[:, j:j + s] * taps[j] for j in range(n))


def gated_short_conv(b, c, x, taps):
    """``c * causal_conv(b * x, taps)`` in x's dtype, the arithmetic in
    fp32. b, c, x: (B, S, C); taps: (n, C)."""
    with jax.named_scope(scopes.SHORT_CONV):
        z = b.astype(jnp.float32) * x.astype(jnp.float32)
        return (c.astype(jnp.float32) * causal_conv(z, taps)).astype(x.dtype)
