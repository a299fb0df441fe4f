"""Decoder-only mixture-of-experts LM trained by block diffusion (the
SDAR shape): every layer rotary grouped-query attention with per-head
RMSNorm on q and k, then a sparse mixture of SwiGLU experts routed by a
softmax, no shared expert; a final RMSNorm and an untied head.

    h <- h + Attn(RMSNorm(h));   h <- h + MoE(RMSNorm(h))

``SdarLM`` is a plain decoder over the ids, the positions and the mask
kind it is given (``ops/flash_attention.py`` ``MaskKind``; causal and 0 …
S-1 unless told). It owns no layer: the layer is ``models/lfm2.py``'s
``Lfm2Layer`` over its ``RotaryGQA`` and ``models/solar.py``'s
``SparseExperts`` (``score="softmax"``, no selection bias, no shared
expert), the norm and the head ``models/looplm.py``'s. Like those models
it is written for ONE RANK OF A DEPLOYMENT: ``held_experts = (first,
count)`` of the router's ``num_experts``, and the vocabulary rows it is
given. Same TPU choices: bf16 compute / fp32 parameters, every layer and
the head rematerialised, the cross-entropy inside the head's call.

``sdar_loss`` is block-diffusion training's objective, a function of the
model. A sequence x of L tokens is cut into blocks of ``block`` tokens;
each block draws a rate t and each of its tokens is replaced by the mask
token with probability t (the noisy copy, m_i = 1 where masked). The
model runs ONCE over ``[noisy ; x]``, 2L positions, both copies at
positions 0 … L-1, under ``BlockDiffusionMask(block)``: a noisy block
sees itself both ways and the clean blocks strictly before it, the clean
copy is block-causal. The loss is read off the noisy copy at the masked
positions, each token at its own position (no shift):

    sum_i m_i / t_{n(i)} * CE(head(norm(h_i)), x_i) / L

mean over the rows. The head runs over the noisy copy's L states alone
(``apply(..., labels)`` reads the first ``labels.shape[1]`` states), with
weight 0 where a token is not masked. The noise is drawn on the device,
inside the step, from seeds the batch itself carries (``block_noise``):
``tokens`` is (B, L + 1) and column L of a row is that row's noise seed
(the objective predicts no next token, so the column a next-token loss
would use as its last label is free).
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..common import scopes
from ..ops.flash_attention import CAUSAL, BlockDiffusionMask
from .lfm2 import Lfm2Layer, RotaryGQA
from .looplm import RMSNorm, _Head
from .solar import SparseExperts, _loss_before_the_backward

RATE_FLOOR = 1e-3       # t = RATE_FLOOR + (1 - RATE_FLOOR) u, u uniform


class SdarLM(nn.Module):
    """``apply(tokens, positions=None, mask_kind=CAUSAL)`` -> fp32 logits
    (B, S, vocab); with ``labels`` (B, R), R <= S, the cross-entropy of
    the first R positions (B, R), which is what training at a real size
    can hold. The experts and the vocabulary are those HELD HERE;
    ``num_experts`` and ``top_k`` are the router's own; with
    ``whole_expert_blocks`` the held experts' blocks are worked whole
    (``moe.held_experts_layer``), else on its ladder. ``block``,
    ``mask_token`` and ``noise_seed`` are :func:`sdar_loss`'s."""

    vocab_size: int = 18992
    num_layers: int = 6
    hidden: int = 2048
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    held_experts: Tuple[int, int] = (0, 16)
    top_k: int = 8
    expert_dim: int = 768
    rope_base: float = 1e6
    norm_eps: float = 1e-6
    block: int = 4
    mask_token: int = 18991
    noise_seed: int = 0
    whole_expert_blocks: bool = False
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, tokens, positions=None, mask_kind=CAUSAL,
                 labels=None):
        attention = (RotaryGQA, (self.num_heads, self.num_kv_heads,
                                 self.head_dim, self.rope_base,
                                 self.norm_eps, self.dtype, mask_kind))
        experts = (SparseExperts, (
            self.num_experts, tuple(self.held_experts), self.top_k,
            self.expert_dim, 0, 1.0, self.dtype, "softmax", False,
            self.whole_expert_blocks))
        with jax.named_scope(scopes.EMBED):
            h = nn.Embed(self.vocab_size, self.hidden,
                         param_dtype=jnp.float32,
                         name="tok_emb")(tokens).astype(self.dtype)
        for i in range(self.num_layers):
            h = nn.remat(Lfm2Layer)(*attention, *experts, self.norm_eps,
                                    self.dtype, name=f"layer{i}")(
                h, positions)
        if labels is not None:
            h = h[:, :labels.shape[1]]
        with jax.named_scope(scopes.NORM):
            z = RMSNorm(self.norm_eps, self.dtype, name="final_norm")(h)
        head = nn.remat(_Head)(self.vocab_size, self.dtype, name="lm_head")
        if labels is None:
            return head(z)
        return _loss_before_the_backward(head, z, labels)


def block_noise(seeds, length: int, block: int, noise_seed: int):
    """``(masked (B, L) bool, rates (B, L / block) fp32)`` of rows whose
    noise seeds are ``seeds`` (B,) int: a row's key is
    ``fold_in(PRNGKey(noise_seed), seed)``, split once; the first half
    draws the blocks' rates ``t = 1e-3 + (1 - 1e-3) u``, the second a
    uniform a token, masked where it lies under its block's rate."""
    def row(seed):
        rate_key, mask_key = jax.random.split(jax.random.fold_in(
            jax.random.PRNGKey(noise_seed), seed))
        rates = RATE_FLOOR + (1.0 - RATE_FLOOR) * jax.random.uniform(
            rate_key, (length // block,), jnp.float32)
        draws = jax.random.uniform(mask_key, (length,), jnp.float32)
        return draws < jnp.repeat(rates, block), rates

    return jax.vmap(row)(seeds)


def sdar_loss(model, params, tokens):
    """Block-diffusion training's loss of ``tokens`` (B, L + 1): L tokens
    of data and the row's noise seed in column L."""
    x, seeds = tokens[:, :-1], tokens[:, -1]
    rows, length = x.shape
    with jax.named_scope(scopes.BD_NOISE):
        masked, rates = block_noise(seeds, length, model.block,
                                    model.noise_seed)
        both = jnp.concatenate(
            [jnp.where(masked, model.mask_token, x), x], 1)
        positions = jnp.tile(jnp.arange(length), 2)[None]
        weights = masked / jnp.repeat(rates, model.block, axis=1)
    ce = model.apply({"params": params}, both, positions,
                     BlockDiffusionMask(model.block), x)
    with jax.named_scope(scopes.LOSS):
        return (ce * weights).sum() / (rows * length)
