"""Decoder-only LM whose token mixer is a scalar-gated delta rule in most
layers (the Olmo-Hybrid shape): Gated DeltaNet where ``layer_types`` says
``"linear_attention"`` and full multi-head softmax attention WITHOUT
positions in the layers it calls ``"full_attention"``; a dense SwiGLU
feed-forward in every layer; and Olmo's block, which norms a branch's
OUTPUT before it is added, where every other decoder here norms its
input:

    h = x + RMSNorm(Mix_l(x));   out = h + RMSNorm(FFN_l(h))
    logits = RMSNorm(h_L) W_head                      (an untied head)

- **Gated DeltaNet** (``GatedDeltaNet``), x the layer's input, H heads of
  d_k key channels and d_v value channels, no bias anywhere:

      [q | k | v] = silu(conv([x W_q | x W_k | x W_v]))     H d_k | H d_k | H d_v, depthwise, causal
      q_h = unit(q_h) d_k^-1/2,  k_h = unit(k_h)            (``models/delta_rule.py``)
      beta_t = 2 sigmoid(x_t W_b)                           a scalar a head (the 2: ``allow_neg_eigval``)
      g_t = -exp(A_log) softplus(x_t W_a + dt_bias)         a scalar a head
      S_t = (I - beta_t k_t k_t^T) exp(g_t) S_{t-1} + beta_t k_t v_t^T;   o_t = S_t^T q_t
      y_t = RMSNorm_head(o_t) * silu(x_t W_g)               the norm, THEN the gate
      out = y W_o

  The three projections are ONE matrix ``qkv`` (the columns in that
  order) and the convolution one call over its H (2 d_k + d_v) channels,
  ``ops/short_conv.py`` ``conv_act`` (depthwise, so the concatenation is
  exact): 11,520 channels at the published widths, whole 128-lane tiles,
  which its kernels take on a TPU, where 2,880 a projection are not. The
  projections, the norms, the decays and the gate lie under
  ``hvd_mixer_proj``, the convolution under ``hvd_short_conv``, the
  recurrence under ``hvd_gdn`` (``ops/linear_attention.py``
  ``gated_delta_attention``: chunked, XLA code).
- **Full attention**: ``models/lfm2.py`` ``RotaryGQA`` with no rotation,
  as many K/V heads as query heads, and the QK norm over the WHOLE
  projection (``WHOLE_PROJECTION``: one scale vector as wide as the
  hidden state for q and one for k, before the heads are cut); causal
  flash attention at ``head_dim ** -0.5``.

Like the other cut models it is written for ONE RANK OF A DEPLOYMENT: the
vocabulary rows it is given. Same TPU choices: bf16 compute / fp32
parameters, every layer and the head rematerialised, the cross-entropy
inside the head's call (``models/looplm.py`` ``_Head``).
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..common import scopes
from ..ops.flash_attention import CAUSAL
from ..ops.linear_attention import CHUNK, gated_delta_attention
# (causal_conv: the benchmark's fault tests reach it through this module)
from ..ops.short_conv import causal_conv, conv_act  # noqa: F401
from .delta_rule import decay_bias_init, decay_rate_init, unit
from .lfm2 import NO_ROTATION, WHOLE_PROJECTION, DenseFFN, RotaryGQA
from .looplm import RMSNorm, _Head
from .solar import _dense, solar_loss

ATTENTION = "full_attention"    # a ``layer_types`` entry; any other: linear
# The published pattern of Olmo-Hybrid-7B's 32 layers: full attention in
# layers 3, 7, ..., 31.
_PATTERN = tuple(ATTENTION if i % 4 == 3 else "linear_attention"
                 for i in range(32))


def _conv_act(x, taps):
    """``silu(conv(x))`` in x's dtype, the arithmetic in fp32, no bias:
    ``ops/short_conv.py`` ``conv_act``, which picks its path."""
    return conv_act(x, taps)


def _gated_norm(o, gate, scale, eps):
    """``RMSNorm(o) * scale * silu(gate)`` in fp32, the statistics over
    the last axis (a head's value channels): the norm before the gate."""
    o = o.astype(jnp.float32)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
    return o * scale * nn.silu(gate.astype(jnp.float32))


class GatedDeltaNet(nn.Module):
    """The scalar-gated delta rule as a layer's token mixer (the module
    docstring has the equations)."""

    num_heads: int = 30
    key_dim: int = 96
    value_dim: int = 192
    conv_taps: int = 4
    allow_neg_eigval: bool = True
    chunk: int = CHUNK
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, s, hidden = x.shape
        heads, dk, dv = self.num_heads, self.key_dim, self.value_dim
        keys, values = heads * dk, heads * dv
        dense = _dense(self.dtype)
        f32 = jnp.float32
        taps = self.param("conv", nn.initializers.lecun_normal(
            in_axis=0, out_axis=()), (self.conv_taps, 2 * keys + values), f32)
        rate = self.param("A_log", decay_rate_init, (heads,), f32)
        bias = self.param("dt_bias", decay_bias_init, (heads,), f32)
        scale = self.param("o_norm", nn.initializers.ones, (dv,), f32)

        with jax.named_scope(scopes.MIXER_PROJ):
            qkv = dense(2 * keys + values, name="qkv")(x)
        q, k, v = jnp.split(_conv_act(qkv, taps), (keys, 2 * keys), -1)
        # everything around the recurrence under the one name; the
        # recurrence itself under its own (``hvd_gdn``), outside it
        with jax.named_scope(scopes.MIXER_PROJ):
            q, k = (unit(y.reshape(b, s, heads, dk).astype(f32))
                    for y in (q, k))
            q = (q * dk ** -0.5).astype(self.dtype)
            log_decay = -jnp.exp(rate) * jax.nn.softplus(
                dense(heads, name="a")(x).astype(f32) + bias)
            beta = nn.sigmoid(dense(heads, name="b")(x).astype(f32))
            if self.allow_neg_eigval:
                beta = 2.0 * beta
        o = gated_delta_attention(q, k.astype(self.dtype),
                                  v.reshape(b, s, heads, dv), log_decay,
                                  beta, self.chunk)
        with jax.named_scope(scopes.MIXER_PROJ):
            gate = dense(values, name="gate")(x).reshape(b, s, heads, dv)
            y = _gated_norm(o, gate, scale, self.norm_eps).astype(self.dtype)
            return dense(hidden, name="o")(y.reshape(b, s, values))


class PostNormLayer(nn.Module):
    """``h = x + norm(Mix(x))`` then ``h + norm(FFN(h))``: ``models/
    lfm2.py`` ``Lfm2Layer``'s arguments and parameter names, the norms on
    the branches' outputs; the FFN's stats are left behind."""

    mixer: Any
    mixer_args: Tuple
    ffn: Any
    ffn_args: Tuple
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        norm = functools.partial(RMSNorm, self.norm_eps, self.dtype)
        y = self.mixer(*self.mixer_args, name="mixer")(x)
        with jax.named_scope(scopes.NORM):
            x = x + norm(name="op_norm")(y)
        y, _ = self.ffn(*self.ffn_args, name="ffn")(x)
        with jax.named_scope(scopes.NORM):
            return x + norm(name="ffn_norm")(y)


class OlmoHybridLM(nn.Module):
    """``apply(tokens)`` -> fp32 logits (B, S, vocab); ``apply(tokens,
    labels)`` -> the cross-entropy of each position (B, S), which is what
    training at a real size can hold. ``layer_types`` may be longer than
    ``num_layers`` (a published pattern read up to the depth held); the
    vocabulary is the rows HELD HERE."""

    vocab_size: int = 12544
    num_layers: int = 4
    hidden: int = 3840
    layer_types: Tuple[str, ...] = _PATTERN
    num_heads: int = 30
    head_dim: int = 128
    mlp_dim: int = 11008
    linear_heads: int = 30
    linear_key_dim: int = 96
    linear_value_dim: int = 192
    conv_taps: int = 4
    allow_neg_eigval: bool = True
    chunk: int = CHUNK
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    def layer_parts(self, i):
        """``(mixer, mixer_args, ffn, ffn_args)`` of layer i."""
        if self.layer_types[i] == ATTENTION:
            # MHA, causal, no rotation, the QK norm over the projection
            mixer = (RotaryGQA, (
                self.num_heads, self.num_heads, self.head_dim, 0.0,
                self.norm_eps, self.dtype, CAUSAL, NO_ROTATION,
                WHOLE_PROJECTION))
        else:
            mixer = (GatedDeltaNet, (
                self.linear_heads, self.linear_key_dim,
                self.linear_value_dim, self.conv_taps,
                self.allow_neg_eigval, self.chunk, self.norm_eps,
                self.dtype))
        return mixer + (DenseFFN, (self.mlp_dim, self.dtype))

    @nn.compact
    def __call__(self, tokens, labels=None):
        embed = nn.Embed(self.vocab_size, self.hidden,
                         param_dtype=jnp.float32, name="tok_emb")
        with jax.named_scope(scopes.EMBED):
            h = embed(tokens).astype(self.dtype)
        for i in range(self.num_layers):
            h = nn.remat(PostNormLayer)(
                *self.layer_parts(i), self.norm_eps, self.dtype,
                name=f"layer{i}")(h)
        with jax.named_scope(scopes.NORM):
            z = RMSNorm(self.norm_eps, self.dtype, name="final_norm")(h)
        return nn.remat(_Head)(self.vocab_size, self.dtype,
                               name="lm_head")(z, labels)


# Mean next-token cross-entropy of ``tokens`` (B, S + 1), weighted where
# ``weights`` (B, S) are given; no auxiliary loss: the expert model's,
# which asks of a model only ``apply(tokens, labels)``.
olmo_hybrid_loss = solar_loss
