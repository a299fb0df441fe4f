"""What the delta-rule token mixers share (``models/solar.py`` ``KDA``,
``models/olmo_hybrid.py`` ``GatedDeltaNet``): the L2 norm of a head's
query and key, and the start of the decay's two parameters."""

import jax
import jax.numpy as jnp
import numpy as np


def unit(y):
    """``y / sqrt(sum y^2 + 1e-6)`` over the last axis (a head's
    channels)."""
    return y * jax.lax.rsqrt((y * y).sum(-1, keepdims=True) + 1e-6)


def decay_bias_init(key, shape, dtype=jnp.float32):
    """softplus^-1 of a step drawn log-uniformly from [1e-3, 1e-1]."""
    step = jnp.exp(jax.random.uniform(
        key, shape, dtype, np.log(1e-3), np.log(1e-1)))
    return step + jnp.log(-jnp.expm1(-step))


def decay_rate_init(key, shape, dtype=jnp.float32):
    """``A_log``: the log of a rate drawn uniformly from [1, 16]."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))
