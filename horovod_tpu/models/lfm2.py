"""Decoder-only LM whose token mixer and whose feed-forward both change
with depth (the LFM2-MoE shape): a gated short convolution in most layers
and rotary grouped-query attention with per-head RMSNorm on q and k in
the layers ``layer_types`` calls ``"full_attention"``; a dense SwiGLU MLP
in the first ``num_dense_layers`` layers and a sparse mixture of SwiGLU
experts, routed by a sigmoid with a selection bias, in the rest.

    x <- x + Mix_l(RMSNorm(x));   x <- x + FFN_l(RMSNorm(x))

- **Short convolution**: ``[B, C, X] = split(W_in u)``, ``out = W_out (C
  * conv(B * X))``, ``conv`` causal and depthwise over ``conv_taps``
  tokens (``ops/short_conv.py``; the gates and the taps under
  ``hvd_short_conv``, the two projections outside it). No activation, no
  norm inside.
- **Attention**: q over ``num_heads`` heads, k and v over ``num_kv_heads``;
  q and k through an RMSNorm over the head's channels (one scale vector
  each, shared by the heads), then rotary positions over the whole head
  width, a head at a time (``ops/rope.py`` ``rotate_heads``: XLA fuses
  the halves into the passes of the per-head norm before it; on packed
  rows, as ``models/gpt.py`` ``rope`` has it, the step held 0.41 GiB
  more, PERF.md PR 39); causal flash attention, which is
  handed the K/V heads as they are (``ops/flash_attention.py`` groups
  them); then ``W_o``.
- **Experts**: ``models/solar.py`` ``SparseExperts`` with ``score =
  "sigmoid"``, the selection bias and no shared expert: the top-k of
  ``sigmoid(W_r x) + b``, weighted by the unbiased scores of the chosen,
  normalised (``parallel/moe.py`` ``route_top_k``). ``b`` is a parameter
  no gradient reaches; nothing here moves it to balance the load.

Like ``models/solar.py`` the model is written for ONE RANK OF A
DEPLOYMENT: ``held_experts = (first, count)`` of the router's
``num_experts``, and the vocabulary rows it is given. The head is TIED:
the embedding's table is the head's kernel. Same TPU choices as
``models/looplm.py``, whose ``RMSNorm`` and head arithmetic
(``head_losses``, under ``hvd_lm_head``) it shares: bf16 compute / fp32
parameters, every layer rematerialised, the cross-entropy computed inside
the head's rematerialised call. The stats an expert layer returns (the
held experts' load, routes and drops) are not published from the step:
nothing reads them of this model yet, and the host callback that would
set the gauges (``moe.record_held_stats``) costs a program its place in
JAX's persistent compile cache.
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..common import scopes
from ..ops.flash_attention import CAUSAL, flash_attention
from ..ops.rope import Rotation, rotate, rotate_heads
from ..ops.short_conv import gated_short_conv
from .looplm import RMSNorm, head_losses
from .solar import SparseExperts, _dense, solar_loss

ATTENTION = "full_attention"    # a ``layer_types`` entry; any other: conv
NO_ROTATION = "nope"    # a ``RotaryGQA.rotation``: q and k as projected
# a ``RotaryGQA.qk_norm``: one RMSNorm over the whole projection of q and
# of k (a scale vector as wide as the projection), before the heads are cut
WHOLE_PROJECTION = "projection"
# The published pattern of LFM2-8B-A1B's 24 layers: attention in layers 2,
# 6, 10, 14, 18 and 21.
_PATTERN = tuple(ATTENTION if i in (2, 6, 10, 14, 18, 21) else "conv"
                 for i in range(24))


class ShortConv(nn.Module):
    """The gated short convolution as a layer's token mixer."""

    taps: int = 3
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        hidden = u.shape[-1]
        dense = _dense(self.dtype)
        taps = self.param("conv", nn.initializers.lecun_normal(
            in_axis=0, out_axis=()), (self.taps, hidden), jnp.float32)
        with jax.named_scope(scopes.MIXER_PROJ):
            b, c, x = jnp.split(dense(3 * hidden, name="in_proj")(u), 3, -1)
        y = gated_short_conv(b, c, x, taps)
        with jax.named_scope(scopes.MIXER_PROJ):
            return dense(hidden, name="out_proj")(y)


class RotaryGQA(nn.Module):
    """Softmax attention, ``num_heads`` query heads on ``num_kv_heads``
    K/V heads, q and k rotated by ``positions`` (1 or B, S; None: 0 …
    S-1), under ``mask_kind`` (``ops/flash_attention.py`` ``MaskKind``;
    causal unless told). The one rotary attention body of the model
    files; what differs between the families is data:

    - ``qk_norm``: q and k through an RMSNorm over the head's channels
      before the rotation (one scale vector each, shared by the heads);
      ``WHOLE_PROJECTION``: over all the heads' channels at once (Olmo's);
    - ``rotation``: an ``ops/rope.py`` ``Rotation`` (a rotary width under
      the head's, YaRN's frequencies, a scale); None is the plain one at
      ``rope_base``; ``NO_ROTATION``: no positions at all;
    - ``packed_rotation``: the rotation on the packed rows the flash
      kernels read (``rotate``) in place of a head at a time
      (``rotate_heads``, which XLA fuses into the per-head norm before
      it: PERF.md PR 39);
    - ``head_gate``: the output of head h times ``sigmoid(u W_g)_h``, one
      scalar a query head from the layer's input, before ``W_o``;
    - ``scale``: the softmax scale where it is not ``head_dim ** -0.5``
      (None). The kernels keep theirs; q takes the ratio of the two, in
      fp32 and back, which is exact where the ratio is a power of two
      (1/64 on heads of 64: 1/8)."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_base: float = 1e6
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    mask_kind: Any = CAUSAL
    rotation: Any = None
    qk_norm: Any = True
    packed_rotation: bool = False
    head_gate: bool = False
    scale: Any = None

    @nn.compact
    def __call__(self, u, positions=None):
        b, s, hidden = u.shape
        dense = _dense(self.dtype)
        norm = functools.partial(RMSNorm, self.norm_eps, self.dtype)
        if self.rotation == NO_ROTATION:
            def turn(x):
                return x
        else:
            turn = functools.partial(
                rotate if self.packed_rotation else rotate_heads,
                positions=positions,
                rotation=self.rotation or Rotation(self.rope_base))
        wide, narrow = (n * self.head_dim
                        for n in (self.num_heads, self.num_kv_heads))
        whole = self.qk_norm == WHOLE_PROJECTION

        def projected(name, width, heads, normed=False):
            x = dense(width, name=name)(u)
            if normed:
                x = norm(name=name + "_norm")(x)
            return x.reshape(b, s, heads, -1)

        with jax.named_scope(scopes.MIXER_PROJ):
            q = projected("q", wide, self.num_heads, whole)
            k = projected("k", narrow, self.num_kv_heads, whole)
            v = projected("v", narrow, self.num_kv_heads)
            q, k = (turn(norm(name=n)(x) if self.qk_norm and not whole
                         else x)
                    for n, x in (("q_norm", q), ("k_norm", k)))
            if self.scale is not None:
                q = (q.astype(jnp.float32)
                     * (self.scale * self.head_dim ** 0.5)).astype(q.dtype)
        o = flash_attention(q, k, v, mask_kind=self.mask_kind)
        with jax.named_scope(scopes.MIXER_PROJ):
            if self.head_gate:
                gate = nn.sigmoid(dense(self.num_heads, name="gate")(u))
                o = o * gate[..., None]
            return dense(hidden, name="o")(o.reshape(b, s, wide))


class DenseFFN(nn.Module):
    """``W_down(silu(W_gate x) * W_up x)``. Returns ``(y, None)``: what an
    expert layer returns, with no stats."""

    mlp_dim: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        dense = _dense(self.dtype)
        with jax.named_scope(scopes.MLP):
            y = nn.silu(dense(self.mlp_dim, name="gate")(x)) \
                * dense(self.mlp_dim, name="up")(x)
            return dense(x.shape[-1], name="down")(y), None


class Lfm2Layer(nn.Module):
    """``x + r Mix(norm(x))`` then ``x + r FFN(norm(x))``, r =
    ``residual_scale`` (1: the plain sum); the FFN's stats are left
    behind. ``mixer`` / ``ffn`` are the two classes and ``mixer_args`` /
    ``ffn_args`` their constructor arguments; what the layer is called
    with beside ``x`` (an attention mixer's positions) goes to the
    mixer."""

    mixer: Any
    mixer_args: Tuple
    ffn: Any
    ffn_args: Tuple
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    residual_scale: float = 1.0

    @nn.compact
    def __call__(self, x, *mixer_inputs):
        norm = functools.partial(RMSNorm, self.norm_eps, self.dtype)

        def branch(y):
            return y if self.residual_scale == 1.0 \
                else y * self.residual_scale

        with jax.named_scope(scopes.NORM):
            y = norm(name="op_norm")(x)
        x = x + branch(self.mixer(*self.mixer_args, name="mixer")(
            y, *mixer_inputs))
        with jax.named_scope(scopes.NORM):
            y = norm(name="ffn_norm")(x)
        y, _ = self.ffn(*self.ffn_args, name="ffn")(y)
        return x + branch(y)


class Lfm2LM(nn.Module):
    """``apply(tokens)`` -> fp32 logits (B, S, vocab); ``apply(tokens,
    labels)`` -> the cross-entropy of each position (B, S), which is what
    training at a real size can hold. ``layer_types`` may be longer than
    ``num_layers`` (a published pattern read up to the depth held); the
    experts and the vocabulary are those HELD HERE; ``num_experts`` and
    ``top_k`` are the router's own."""

    vocab_size: int = 16384
    num_layers: int = 8
    hidden: int = 2048
    layer_types: Tuple[str, ...] = _PATTERN
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    conv_taps: int = 3
    num_dense_layers: int = 2
    mlp_dim: int = 7168
    num_experts: int = 32
    held_experts: Tuple[int, int] = (0, 8)
    top_k: int = 4
    expert_dim: int = 1792
    routed_scale: float = 1.0
    rope_base: float = 1e6
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    def setup(self):
        self.tok_emb = nn.Embed(self.vocab_size, self.hidden,
                                param_dtype=jnp.float32)
        layer = nn.remat(Lfm2Layer)
        conv = (ShortConv, (self.conv_taps, self.dtype))
        attention = (RotaryGQA, (self.num_heads, self.num_kv_heads,
                                 self.head_dim, self.rope_base,
                                 self.norm_eps, self.dtype))
        dense = (DenseFFN, (self.mlp_dim, self.dtype))
        experts = (SparseExperts, (
            self.num_experts, tuple(self.held_experts), self.top_k,
            self.expert_dim, 0, self.routed_scale, self.dtype, "sigmoid",
            True))
        for i in range(self.num_layers):
            setattr(self, f"layer{i}", layer(
                *(attention if self.layer_types[i] == ATTENTION else conv),
                *(dense if i < self.num_dense_layers else experts),
                self.norm_eps, self.dtype))
        self.final_norm = RMSNorm(self.norm_eps, self.dtype)

    def __call__(self, tokens, labels=None):
        with jax.named_scope(scopes.EMBED):
            h = self.tok_emb(tokens).astype(self.dtype)
        for i in range(self.num_layers):
            h = getattr(self, f"layer{i}")(h)
        head = jax.checkpoint(functools.partial(
            head_losses, dtype=self.dtype, tied=True))
        with jax.named_scope(scopes.NORM):
            z = self.final_norm(h)
        return head(z, self.tok_emb.embedding, labels)


# Mean next-token cross-entropy of ``tokens`` (B, S + 1), weighted where
# ``weights`` (B, S) are given; no auxiliary loss: the expert model's,
# which asks of a model only ``apply(tokens, labels)``.
lfm2_loss = solar_loss
