"""Decoder-only LM whose token mixer is a state-space layer in most
layers (the Granite 4.0-H shape): Mamba-2's selective state-space
recurrence (SSD) where ``layer_types`` says ``"mamba"`` and grouped-query
softmax attention WITHOUT positions in the layers it calls
``"attention"``; a dense SwiGLU feed-forward in every layer; a final
RMSNorm and a TIED head; and Granite's four multipliers:

    h = embedding_multiplier * Embed(tokens)
    h <- h + residual_multiplier * Mix_l(RMSNorm(h))
    h <- h + residual_multiplier * FFN_l(RMSNorm(h))
    logits = RMSNorm(h) Embed^T / logits_scaling

and the attention's softmax scale is ``attention_multiplier``, not
``head_dim ** -0.5``.

- **Mamba-2 mixer** (``Mamba2Mixer``), u the layer's normed input,
  ``d_inner`` = heads x head width, G groups of B and C over a state of N:

      [z | xBC | dt] = W_in u                 widths d_inner | d_inner + 2 G N | heads
      xBC = silu(conv(xBC) + b_conv)          depthwise, causal, ``conv_taps`` taps
      [x | B | C] = xBC
      y = SSD(x, dt, A = -exp(A_log), B, C, D, dt_bias)      (``ops/ssd.py``)
      out = W_out (RMSNorm(y * silu(z)) * w)  the gate BEFORE the norm, a group at a time

  The projections, the gate and the gated norm lie under
  ``hvd_mixer_proj``, the convolution with its bias and SiLU under
  ``hvd_short_conv`` (``ops/short_conv.py`` ``causal_conv``), the
  recurrence under ``hvd_ssd``: on a TPU, at the published widths (heads
  of 64, a state of 128, one group, chunks of 256), the two Pallas kernels
  ``hvd_ssd_fwd`` / ``hvd_ssd_bwd``; at any other shape (the tiny preset)
  and off a TPU the chunked XLA code, picked by ``ssd_scan`` from what it
  sees in its operands.
- **Attention**: ``models/lfm2.py`` ``RotaryGQA`` with no rotation, no QK
  norm and ``scale = attention_multiplier``; causal flash attention.

``GraniteHybridLM`` owns no layer: the layer is ``Lfm2Layer`` (its
``residual_scale``) over one of the two mixers and ``DenseFFN``, the norm
and the head's arithmetic ``models/looplm.py``'s. Like the other cut
models it is written for ONE RANK OF A DEPLOYMENT: the vocabulary rows it
is given. Same TPU choices: bf16 compute / fp32 parameters, every layer
and the head rematerialised, the cross-entropy inside the head's call.
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..common import scopes
from ..ops.flash_attention import CAUSAL
# (causal_conv: the benchmark's fault tests reach it through this module)
from ..ops.short_conv import causal_conv, conv_act  # noqa: F401
from ..ops.ssd import CHUNK, ssd_scan
from .lfm2 import NO_ROTATION, DenseFFN, Lfm2Layer, RotaryGQA
from .looplm import RMSNorm, head_losses
from .solar import _dense, solar_loss

ATTENTION = "attention"     # a ``layer_types`` entry; any other: mamba
# The published pattern of granite-4.0-h-micro's 40 layers: attention in
# layers 5, 15, 25 and 35.
_PATTERN = tuple(ATTENTION if i % 10 == 5 else "mamba" for i in range(40))
# Mamba-2's start: the step size log-uniform in [1e-3, 0.1], A uniform in
# [-16, -1].
_DT_RANGE = (1e-3, 0.1)
_A_RANGE = (1.0, 16.0)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of a log-uniform step size."""
    low, high = (jnp.log(v) for v in _DT_RANGE)
    delta = jnp.exp(jax.random.uniform(key, shape, dtype, low, high))
    return delta + jnp.log(-jnp.expm1(-delta))


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, *_A_RANGE))


def _conv_bias_init(taps):
    """U(-1/sqrt(taps), 1/sqrt(taps)): a depthwise Conv1d's default."""
    bound = taps ** -0.5

    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    return init


def _conv_act(xbc, taps, bias):
    """``silu(conv(xbc) + bias)`` in xbc's dtype, the arithmetic in fp32:
    ``ops/short_conv.py`` ``conv_act``, which picks its path."""
    return conv_act(xbc, taps, bias)


def _gated_norm(y, z, scale, eps, groups):
    """``RMSNorm(y * silu(z)) * scale`` in fp32: the gate before the norm,
    the statistics over each of ``groups`` equal slices of the channels."""
    x = y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
    x = x.reshape(*x.shape[:-1], groups, -1)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x.reshape(y.shape) * scale


class GatedRMSNorm(nn.Module):
    """``_gated_norm`` with its scale vector (one value a channel)."""

    eps: float = 1e-5
    groups: int = 1
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, y, z):
        scale = self.param("scale", nn.initializers.ones, (y.shape[-1],),
                           jnp.float32)
        return _gated_norm(y, z, scale, self.eps, self.groups).astype(
            self.dtype)


class Mamba2Mixer(nn.Module):
    """The Mamba-2 block as a layer's token mixer (the module docstring
    has the equations)."""

    num_heads: int = 64
    head_dim: int = 64
    state: int = 128
    groups: int = 1
    conv_taps: int = 4
    chunk: int = CHUNK
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        batch, length, hidden = u.shape
        heads, groups, n = self.num_heads, self.groups, self.state
        inner = heads * self.head_dim
        conv_width = inner + 2 * groups * n
        dense = _dense(self.dtype)
        taps = self.param("conv", nn.initializers.lecun_normal(
            in_axis=0, out_axis=()), (self.conv_taps, conv_width),
            jnp.float32)
        conv_bias = self.param("conv_bias", _conv_bias_init(self.conv_taps),
                               (conv_width,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (heads,), jnp.float32)
        a_log = self.param("A_log", _a_log_init, (heads,), jnp.float32)
        skip = self.param("D", nn.initializers.ones, (heads,), jnp.float32)

        with jax.named_scope(scopes.MIXER_PROJ):
            z, xbc, dt = jnp.split(
                dense(inner + conv_width + heads, name="in_proj")(u),
                (inner, inner + conv_width), -1)
        xbc = _conv_act(xbc, taps, conv_bias)
        x, b, c = jnp.split(xbc, (inner, inner + groups * n), -1)
        with jax.named_scope(scopes.SSD):
            a = -jnp.exp(a_log)
        y = ssd_scan(x.reshape(batch, length, heads, -1), dt, a,
                     b.reshape(batch, length, groups, n),
                     c.reshape(batch, length, groups, n), skip, dt_bias,
                     self.chunk)
        with jax.named_scope(scopes.MIXER_PROJ):
            y = GatedRMSNorm(self.norm_eps, groups, self.dtype, name="norm")(
                y.reshape(batch, length, inner), z)
            return dense(hidden, name="out_proj")(y)


class GraniteHybridLM(nn.Module):
    """``apply(tokens)`` -> fp32 logits (B, S, vocab); ``apply(tokens,
    labels)`` -> the cross-entropy of each position (B, S), which is what
    training at a real size can hold. ``layer_types`` may be longer than
    ``num_layers`` (a published pattern read up to the depth held); the
    vocabulary is the rows HELD HERE."""

    vocab_size: int = 25088
    num_layers: int = 10
    hidden: int = 2048
    layer_types: Tuple[str, ...] = _PATTERN
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    mlp_dim: int = 8192
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 1
    conv_taps: int = 4
    chunk: int = CHUNK
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.015625
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    def layer_parts(self, i):
        """``(mixer, mixer_args, ffn, ffn_args)`` of layer i."""
        if self.layer_types[i] == ATTENTION:
            # causal, no rotation, no QK norm, no gate; Granite's scale
            mixer = (RotaryGQA, (
                self.num_heads, self.num_kv_heads, self.head_dim, 0.0,
                self.norm_eps, self.dtype, CAUSAL, NO_ROTATION, False,
                False, False, self.attention_multiplier))
        else:
            mixer = (Mamba2Mixer, (
                self.ssm_heads, self.ssm_head_dim, self.ssm_state,
                self.ssm_groups, self.conv_taps, self.chunk, self.norm_eps,
                self.dtype))
        return mixer + (DenseFFN, (self.mlp_dim, self.dtype))

    @nn.compact
    def __call__(self, tokens, labels=None):
        embed = nn.Embed(self.vocab_size, self.hidden,
                         param_dtype=jnp.float32, name="tok_emb")
        with jax.named_scope(scopes.EMBED):
            h = (embed(tokens) * self.embedding_multiplier).astype(
                self.dtype)
        for i in range(self.num_layers):
            h = nn.remat(Lfm2Layer)(
                *self.layer_parts(i), self.norm_eps, self.dtype,
                self.residual_multiplier, name=f"layer{i}")(h)
        with jax.named_scope(scopes.NORM):
            z = RMSNorm(self.norm_eps, self.dtype, name="final_norm")(h)
        head = jax.checkpoint(functools.partial(
            head_losses, dtype=self.dtype, tied=True,
            logit_scale=1.0 / self.logits_scaling))
        return head(z, embed.embedding, labels)


# Mean next-token cross-entropy of ``tokens`` (B, S + 1), weighted where
# ``weights`` (B, S) are given; no auxiliary loss: the expert model's,
# which asks of a model only ``apply(tokens, labels)``.
granite_loss = solar_loss
