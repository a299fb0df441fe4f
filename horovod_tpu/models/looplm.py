"""Looped decoder-only LM (Ouro / LoopLM style): one stack of layers run
``passes`` times over ONE parameter tree, with an exit at every pass.

    h <- Embed(tokens)
    for t = 1 .. passes:  h <- final_norm(layer_L(... layer_1(h)))   (= z_t)
        logits_t = W_head z_t          gate_t = sigmoid(w_g . z_t + b_g)

The normed state z_t is what the next pass starts from. A layer is
sandwich-normed (four RMSNorms), bias-free, with rotary positions and a
SwiGLU MLP:

    x <- x + n2(Attn(n1(x)));   x <- x + n4(W_down(silu(W_gate y) * W_up y)),  y = n3(x)

Same TPU choices as ``models/gpt.py``: bf16 compute / fp32 parameters,
the Pallas flash kernels (``causal=True``) on (B, S, H, D) where D is the
configuration's own head width (not ``hidden / heads`` by convention), an
untied vocabulary head with bf16 operands and fp32 accumulation under
``hvd_lm_head``. What the loop adds:

- the passes are ONE ``nn.scan`` with the parameters broadcast: the
  program holds the stack once, and the backward accumulates each
  weight's gradient over its ``passes`` uses in the scan's carry;
- every layer application, and every exit's head, is rematerialised
  (``nn.remat``): 32 applications at S2048 would otherwise save ~74 KB a
  token each, and four fp32 logit blocks do not fit beside the state;
- the gate, the exit distribution, its entropy and the weighting of the
  exits' losses carry the scope ``hvd_loop_exit`` (common/scopes.py).

``looplm_loss`` is the training objective, a function of the model:
``sum_t p_t CE_t - beta H(p)`` per position, with
``p_t = gate_t prod_{j<t} (1 - gate_j)`` and the last pass taking what is
left.
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..common import scopes
from ..ops.flash_attention import flash_attention
from .gpt import rope


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * scale``, statistics in fp32."""

    eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x = x.astype(jnp.float32)
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + self.eps)
        return (x * scale).astype(self.dtype)


class LoopLayer(nn.Module):
    """One sandwich-normed decoder layer; every projection bias-free."""

    num_heads: int
    head_dim: int
    mlp_dim: int
    rope_base: float = 1e6
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, s, hidden = x.shape
        heads, width = self.num_heads, self.head_dim
        dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype,
                                  param_dtype=jnp.float32)
        norm = functools.partial(RMSNorm, self.norm_eps, self.dtype)

        with jax.named_scope(scopes.NORM):
            y = norm(name="attn_norm")(x)
        with jax.named_scope(scopes.MIXER_PROJ):
            q, k, v = (dense(heads * width, name=n)(y).reshape(
                b, s, heads, width) for n in ("q", "k", "v"))
            q, k = rope(q, base=self.rope_base), rope(k, base=self.rope_base)
        o = flash_attention(q, k, v, causal=True)
        with jax.named_scope(scopes.MIXER_PROJ):
            o = dense(hidden, name="o")(o.reshape(b, s, heads * width))
        with jax.named_scope(scopes.NORM):
            o = norm(name="attn_out_norm")(o)
        x = x + o

        with jax.named_scope(scopes.NORM):
            y = norm(name="mlp_norm")(x)
        with jax.named_scope(scopes.MLP):
            y = nn.silu(dense(self.mlp_dim, name="gate")(y)) \
                * dense(self.mlp_dim, name="up")(y)
            y = dense(hidden, name="down")(y)
        with jax.named_scope(scopes.NORM):
            y = norm(name="mlp_out_norm")(y)
        return x + y


def head_losses(z, kernel, labels=None, dtype=jnp.bfloat16, tied=False,
                logit_scale=1.0):
    """fp32 logits of a state over the vocabulary (bf16 operands, fp32
    accumulation, under ``hvd_lm_head``), or, given the labels, the
    cross-entropy of each position (the log-sum-exp and the pick of the
    label under ``hvd_loss``, beside the matmul's scope and not inside
    it). ``kernel`` is (hidden, vocab), or with ``tied`` an embedding's
    table (vocab, hidden); the logits are the product times
    ``logit_scale`` (1: the product itself)."""
    with jax.named_scope(scopes.LM_HEAD):
        logits = jax.lax.dot_general(
            z.astype(dtype), kernel.astype(dtype),
            (((z.ndim - 1,), (1 if tied else 0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if logit_scale != 1.0:
            logits = logits * logit_scale
    if labels is None:
        return logits
    with jax.named_scope(scopes.LOSS):
        picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        return jax.nn.logsumexp(logits, -1) - picked


class _Head(nn.Module):
    """Untied vocabulary head: fp32 logits of a state, or, given the
    labels, the cross-entropy of each position (the logits then never
    leave the rematerialised call)."""

    vocab_size: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, z, labels=None):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (z.shape[-1], self.vocab_size), jnp.float32)
        return head_losses(z, kernel, labels, self.dtype)


class _ExitGate(nn.Module):
    """``w_g . z + b_g`` in fp32, before the sigmoid: a matvec a token,
    done on the vector unit so that it is exact fp32."""

    @nn.compact
    def __call__(self, z):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (z.shape[-1], 1), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (1,), jnp.float32)
        with jax.named_scope(scopes.LOOP_EXIT):
            return (z.astype(jnp.float32) * kernel[:, 0]).sum(-1) + bias[0]


class LoopLM(nn.Module):
    """``apply(tokens)`` -> ``(logits, gates)``: fp32 logits
    (passes, B, S, vocab) and fp32 gate logits (passes, B, S) of every
    exit. ``apply(tokens, labels)`` -> ``(ce, gates)``: each exit's
    cross-entropy (passes, B, S) in place of its logits, which is what
    training at a real size can hold."""

    vocab_size: int = 49152
    num_layers: int = 48
    hidden: int = 2048
    num_heads: int = 16
    head_dim: int = 128
    mlp_dim: int = 5632
    passes: int = 4
    rope_base: float = 1e6
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    def setup(self):
        self.tok_emb = nn.Embed(self.vocab_size, self.hidden,
                                param_dtype=jnp.float32)
        layer = nn.remat(LoopLayer)
        for i in range(self.num_layers):
            setattr(self, f"layer{i}", layer(
                self.num_heads, self.head_dim, self.mlp_dim, self.rope_base,
                self.norm_eps, self.dtype))
        self.final_norm = RMSNorm(self.norm_eps, self.dtype)
        self.lm_head = nn.remat(_Head)(self.vocab_size, self.dtype)
        self.exit_gate = _ExitGate()

    def one_pass(self, h):
        for i in range(self.num_layers):
            h = getattr(self, f"layer{i}")(h)
        with jax.named_scope(scopes.NORM):
            return self.final_norm(h)

    def __call__(self, tokens, labels=None):
        def step(model, h, labels):
            z = model.one_pass(h)
            return z, (model.lm_head(z, labels), model.exit_gate(z))

        passes = nn.scan(step, variable_broadcast="params",
                         split_rngs={"params": False},
                         in_axes=nn.broadcast, length=self.passes)
        with jax.named_scope(scopes.EMBED):
            h = self.tok_emb(tokens).astype(self.dtype)
        return passes(self, h, labels)[1]


def exit_log_distribution(gates):
    """log p_t over the leading (pass) axis from the gates' logits:
    ``p_t = s(a_t) prod_{j<t} (1 - s(a_j))``, the last pass taking what
    is left (its own gate is not used)."""
    log_stay = jax.nn.log_sigmoid(-gates)
    before = jnp.cumsum(log_stay, 0) - log_stay     # sum over j < t
    return jnp.concatenate(
        [jax.nn.log_sigmoid(gates[:-1]) + before[:-1], before[-1:]], 0)


def looplm_loss(model, params, tokens, beta, weights=None,
                with_stats=False):
    """The exit-weighted next-token loss of ``tokens`` (B, S + 1):
    per position ``sum_t p_t CE_t - beta H(p)``, then the mean over
    positions, weighted by ``weights`` (B, S) where given. With
    ``with_stats`` also ``{"exit_mass": (passes,), "exit_entropy": ()}``,
    the same mean of each pass's exit probability and of H(p)."""
    ce, gates = model.apply({"params": params}, tokens[:, :-1],
                            tokens[:, 1:])

    def mean(x):
        if weights is None:
            return x.mean((-2, -1))
        return (x * weights).sum((-2, -1)) / weights.sum()

    with jax.named_scope(scopes.LOOP_EXIT):
        log_p = exit_log_distribution(gates)
        p = jnp.exp(log_p)
        entropy = -(p * log_p).sum(0)
        loss = mean((p * ce).sum(0) - beta * entropy)
        if not with_stats:
            return loss
        return loss, {"exit_mass": mean(p), "exit_entropy": mean(entropy)}
