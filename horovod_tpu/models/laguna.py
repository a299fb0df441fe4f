"""Decoder-only mixture-of-experts LM whose attention changes kind with
depth (the Laguna shape): full causal attention in the layers
``layer_types`` calls ``"full_attention"`` and attention over a sliding
window of the last ``window`` keys in the others, the two kinds at
DIFFERENT query-head counts (``heads_per_layer``) on the same K/V heads,
each head's output gated by a scalar, and each kind with a rotation of
its own; a dense SwiGLU feed-forward where ``mlp_layer_types`` says
``"dense"`` and a softmax-routed top-k mixture of SwiGLU experts with a
shared expert elsewhere; a final RMSNorm and an untied head.

    h <- h + Attn_l(RMSNorm(h));   h <- h + FF_l(RMSNorm(h))

``LagunaLM`` owns no layer: the layer is ``models/lfm2.py``'s
``Lfm2Layer`` over its ``RotaryGQA`` (no QK norm, the per-head gate, the
rotation on the packed rows the flash kernels read; the mask kind
``CAUSAL`` or ``SlidingWindowMask(window)``) and its ``DenseFFN`` or
``models/solar.py``'s ``SparseExperts`` (``score="softmax"``, a shared
expert, ``routed_scale``), the norm and the head ``models/looplm.py``'s.

- **Full layer**: ``heads_per_layer[l]`` query heads; ``full_rotation``
  (the published model: the first half of a head's channels turned, by
  YaRN's frequencies, cos and sin times its attention factor).
- **Window layer**: its own head count; ``window_rotation`` (the whole
  head width at a plain base); only the window's band of (query, key)
  tiles is visited, forward and backward, and the kernels' calls carry
  names of their own (``scopes.SWA_KERNELS``).

Like ``models/solar.py`` the model is written for ONE RANK OF A
DEPLOYMENT: ``held_experts = (first, count)`` of the router's
``num_experts``, and the vocabulary rows it is given. Same TPU choices as
the other expert models: bf16 compute / fp32 parameters, every layer and
the head rematerialised, the cross-entropy inside the head's call, the
head's backward after the forward's loss (``_loss_before_the_backward``).
"""

from __future__ import annotations

from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..common import scopes
from ..ops.flash_attention import CAUSAL, SlidingWindowMask
from ..ops.rope import Rotation
from .lfm2 import ATTENTION, DenseFFN, Lfm2Layer, RotaryGQA
from .looplm import RMSNorm, _Head
from .solar import SparseExperts, _loss_before_the_backward, solar_loss

DENSE = "dense"     # an ``mlp_layer_types`` entry; any other: experts
# The published pattern of Laguna-S-2.1: one full layer, three window
# layers; 48 query heads in a full layer and 72 in a window layer.
_PERIOD = (ATTENTION,) + ("sliding_attention",) * 3
# Its full layers' rotation: 64 of a head's 128 channels, YaRN over a
# trained length of 8192 stretched 128 times.
_YARN = Rotation(base=500000.0, width=64, factor=128.0, original_length=8192,
                 beta_fast=32.0, beta_slow=1.0, scale=1.4852030263919618)


class LagunaLM(nn.Module):
    """``apply(tokens)`` -> fp32 logits (B, S, vocab); ``apply(tokens,
    labels)`` -> the cross-entropy of each position (B, S), which is what
    training at a real size can hold. ``layer_types``, ``heads_per_layer``
    and ``mlp_layer_types`` may be longer than ``num_layers`` (published
    patterns read up to the depth held); the experts and the vocabulary
    are those HELD HERE; ``num_experts`` and ``top_k`` are the router's
    own; with ``whole_expert_blocks`` the held experts' blocks are worked
    whole (``moe.held_experts_layer``), else on its ladder."""

    vocab_size: int = 12544
    num_layers: int = 5
    hidden: int = 3072
    layer_types: Tuple[str, ...] = _PERIOD * 2
    heads_per_layer: Tuple[int, ...] = (48, 72, 72, 72) * 2
    num_kv_heads: int = 8
    head_dim: int = 128
    window: int = 512
    full_rotation: Rotation = _YARN
    window_rotation: Rotation = Rotation(base=10000.0)
    mlp_layer_types: Tuple[str, ...] = (DENSE,) + ("sparse",) * 7
    mlp_dim: int = 12288
    num_experts: int = 256
    held_experts: Tuple[int, int] = (0, 8)
    top_k: int = 10
    expert_dim: int = 1024
    shared_dim: int = 1024
    routed_scale: float = 2.5
    norm_eps: float = 1e-6
    whole_expert_blocks: bool = False
    dtype: Any = jnp.bfloat16

    def layer_parts(self, i):
        """``(mixer, mixer_args, ffn, ffn_args)`` of layer i."""
        full = self.layer_types[i] == ATTENTION
        rotation = self.full_rotation if full else self.window_rotation
        # no QK norm; the rotation on packed rows; a gate a head
        attention = (RotaryGQA, (
            self.heads_per_layer[i], self.num_kv_heads, self.head_dim,
            rotation.base, self.norm_eps, self.dtype,
            CAUSAL if full else SlidingWindowMask(self.window), rotation,
            False, True, True))
        if self.mlp_layer_types[i] == DENSE:
            return attention + (DenseFFN, (self.mlp_dim, self.dtype))
        return attention + (SparseExperts, (
            self.num_experts, tuple(self.held_experts), self.top_k,
            self.expert_dim, self.shared_dim, self.routed_scale, self.dtype,
            "softmax", False, self.whole_expert_blocks))

    @nn.compact
    def __call__(self, tokens, labels=None):
        with jax.named_scope(scopes.EMBED):
            h = nn.Embed(self.vocab_size, self.hidden,
                         param_dtype=jnp.float32,
                         name="tok_emb")(tokens).astype(self.dtype)
        for i in range(self.num_layers):
            h = nn.remat(Lfm2Layer)(*self.layer_parts(i), self.norm_eps,
                                    self.dtype, name=f"layer{i}")(h)
        with jax.named_scope(scopes.NORM):
            z = RMSNorm(self.norm_eps, self.dtype, name="final_norm")(h)
        head = nn.remat(_Head)(self.vocab_size, self.dtype, name="lm_head")
        if labels is None:
            return head(z)
        return _loss_before_the_backward(head, z, labels)


# Mean next-token cross-entropy of ``tokens`` (B, S + 1), weighted where
# ``weights`` (B, S) are given; no auxiliary loss: the expert model's,
# which asks of a model only ``apply(tokens, labels)``.
laguna_loss = solar_loss
