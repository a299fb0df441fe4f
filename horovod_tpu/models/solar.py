"""Decoder-only LM whose layer kind comes from a pattern: softmax
grouped-query attention in the layers ``gqa_layers`` names and gated
delta-rule linear attention (KDA) in the others, every layer followed by a
sparse mixture of SwiGLU experts with a shared expert (the Solar-Open2 /
Kimi-Linear shape).

    x <- x + Attn_l(RMSNorm(x));   x <- x + MoE_l(RMSNorm(x))

The model is written for ONE RANK OF A DEPLOYMENT that divides each layer
over several chips: it is told how many query, K/V and KDA heads it holds
and which experts (``held_experts = (first, count)`` of ``num_experts``),
and computes their part of each layer. The router keeps its published
width and its experts per token; a route to an expert held elsewhere adds
nothing here (``parallel/moe.py`` ``held_experts_layer``). Nothing stands
in for the absent chips.

- **GQA layer** (no positions): q over the held query heads, k and v over
  the held K/V heads, causal flash attention with K/V head = query head //
  group in the kernels' index maps (``ops/flash_attention.py``), an
  elementwise sigmoid gate on the attention output, then ``W_o``.
- **KDA layer**: q, k, v through a causal depthwise convolution of
  ``conv_size`` taps and SiLU; q and k L2-normalised a head, q scaled by
  head_dim^-0.5; per-channel decay ``log alpha = -exp(A) softplus(W_f^up
  W_f^down x + b)``; ``beta = 2 sigmoid(w_beta x)``; the chunked recurrence
  (``ops/linear_attention.py``); a per-head RMSNorm of the output times
  ``sigmoid(W_g^up W_g^down x)``, then ``W_o``.
- **MoE**: a router over ``num_experts`` (this model's scores them by a
  softmax; ``SparseExperts`` also takes the sigmoid router with a
  selection bias that ``models/lfm2.py`` runs), top ``top_k``, weights
  normalised over the k; the held experts as grouped matmuls under
  ``hvd_moe_experts``, routing under ``hvd_moe_route``; one shared SwiGLU
  expert on every token under ``hvd_moe_shared``.

Same TPU choices as ``models/looplm.py``, whose ``RMSNorm`` and untied
head (``hvd_lm_head``) it shares: bf16 compute / fp32 parameters, every
layer rematerialised (``nn.remat``), the cross-entropy computed inside the
head's rematerialised call. The held experts' load, routes and drops of a
step, summed over the layers, are published from inside the step where
``publish_stats`` asks for it (``moe.record_held_stats``).
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from ..common import scopes
from ..ops.flash_attention import flash_attention
from ..ops.linear_attention import kda_attention
from ..ops.pallas_kernels import _decide
from ..ops.short_conv import causal_conv
from ..parallel import moe
from .delta_rule import decay_bias_init, decay_rate_init, unit
from .looplm import RMSNorm, _Head


def _dense(dtype):
    return functools.partial(nn.Dense, use_bias=False, dtype=dtype,
                             param_dtype=jnp.float32)


class GatedGQA(nn.Module):
    """Causal softmax attention, ``num_heads`` query heads on
    ``num_kv_heads`` K/V heads, no positions, output gate."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, s, hidden = x.shape
        dense = _dense(self.dtype)
        wide, narrow = (n * self.head_dim
                        for n in (self.num_heads, self.num_kv_heads))
        with jax.named_scope(scopes.MIXER_PROJ):
            q = dense(wide, name="q")(x).reshape(b, s, self.num_heads, -1)
            k, v = (dense(narrow, name=n)(x).reshape(
                b, s, self.num_kv_heads, -1) for n in ("k", "v"))
        o = flash_attention(q, k, v, causal=True)
        with jax.named_scope(scopes.MIXER_PROJ):
            o = o.reshape(b, s, wide) * nn.sigmoid(
                dense(wide, name="gate")(x))
            return dense(hidden, name="o")(o)


# -- what the step tells XLA:TPU's scheduler ---------------------------------
#
# Two pieces of this model's step have no reader inside it, so the
# scheduler puts them where it likes, and it likes them late: the
# forward's loss (an output of the step: the forward's own logits matmul
# then runs at the very end) and the head's in-place update (which must
# follow the kernel's last reader, that late forward, and holds the
# backward's 768 MB of fp32 logits until then). While the recurrence was
# XLA code, its 512 MB temporaries in each layer's backward made the
# scheduler bring both forward; as two kernels it holds nothing of that
# size, and the step took 13.03 GiB where it had taken 11.57 (PERF.md,
# PR 34). The two functions below say what the XLA code said by accident.
# Their effect is the compiler's to give, so a test holds it
# (tests/test_tpu_compile.py: the whole step for a described v5e).

def _loss_before_the_backward(head, z, labels):
    """``head(z, labels)`` whose backward hands the state's cotangent on
    only once the forward's loss is there: the loss is computed in the
    forward, from the forward's logits, and the head's kernel is free to be
    updated as soon as its own gradient is."""
    def forward(mdl, z):
        ce, vjp = nn.vjp(lambda m, z: m(z, labels), mdl, z)
        return ce, (vjp, ce)

    def backward(residuals, ct):
        vjp, ce = residuals
        head_grads, dz = vjp(ct)
        dz, _ = jax.lax.optimization_barrier((dz, ce))
        return head_grads, dz

    return nn.custom_vjp(lambda mdl, z: mdl(z, labels), forward_fn=forward,
                         backward_fn=backward)(head, z)


# The XLA recurrence's largest temporary in a layer's backward at the
# expert cell's size, (2, 8, 64, 4, 16, 16, 128) fp32. Compiled for a
# described v5e, the cell's step reads the same 10.448 GiB from a quarter
# of it to twice it, 11.97 with a fifth or none, 10.72 with four times.
_BALLAST_BYTES = 512 * 2 ** 20


@jax.custom_vjp
def _as_heavy_as_the_xla_code(o):
    """The identity. On a TPU its backward passes the cotangent through a
    Pallas call that does nothing and declares, beside it, an output of
    ``_BALLAST_BYTES`` it never writes and nothing reads: memory the
    scheduler sees at this point of the backward, dead at once, at no time
    and no traffic."""
    return o


def _ballast_bwd(_, ct):
    if not _decide(None)[0]:
        return (ct,)
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    ct, _ = pl.pallas_call(
        lambda ct_ref, out_ref, ballast_ref: None,
        in_specs=[anywhere], out_specs=[anywhere, anywhere],
        out_shape=[jax.ShapeDtypeStruct(ct.shape, ct.dtype),
                   jax.ShapeDtypeStruct((_BALLAST_BYTES // 4096, 1024),
                                        jnp.float32)],
        input_output_aliases={0: 0}, name="solar_scheduler_ballast")(ct)
    return (ct,)


_as_heavy_as_the_xla_code.defvjp(lambda o: (o, None), _ballast_bwd)


class KDA(nn.Module):
    """Gated delta-rule linear attention over ``num_heads`` heads of
    ``head_dim`` for q, k and v alike."""

    num_heads: int
    head_dim: int
    conv_size: int = 4
    gate_rank: int = 128
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, s, hidden = x.shape
        heads, width = self.num_heads, self.head_dim
        dense = _dense(self.dtype)

        def mixed(name):
            taps = self.param("conv_" + name, nn.initializers.lecun_normal(
                in_axis=0, out_axis=()), (self.conv_size, heads * width),
                jnp.float32)
            y = nn.silu(causal_conv(dense(heads * width, name=name)(x), taps))
            return y.reshape(b, s, heads, width)

        # everything around the recurrence under the one name; the
        # recurrence itself under its own (``hvd_kda``), outside it
        with jax.named_scope(scopes.MIXER_PROJ):
            q = (unit(mixed("q")) * width ** -0.5).astype(self.dtype)
            k = unit(mixed("k")).astype(self.dtype)
            v = mixed("v").astype(self.dtype)

            rate = self.param("A_log", decay_rate_init, (heads,),
                              jnp.float32)
            bias = self.param("dt_bias", decay_bias_init, (heads * width,),
                              jnp.float32)
            f = dense(heads * width, name="f_up")(
                dense(self.gate_rank, name="f_down")(x))
            log_alpha = -jnp.exp(rate)[:, None] * jax.nn.softplus(
                (f.astype(jnp.float32) + bias).reshape(b, s, heads, width))
            beta = 2.0 * nn.sigmoid(
                dense(heads, name="beta")(x).astype(jnp.float32))

        o = _as_heavy_as_the_xla_code(
            kda_attention(q, k, v, log_alpha, beta))
        with jax.named_scope(scopes.MIXER_PROJ):
            o = RMSNorm(self.norm_eps, self.dtype, name="o_norm")(o)
            gate = dense(heads * width, name="g_up")(
                dense(self.gate_rank, name="g_down")(x))
            o = o.reshape(b, s, heads * width) * nn.sigmoid(gate)
            return dense(hidden, name="o")(o)


class SparseExperts(nn.Module):
    """The held routed experts' part of the layer plus the shared expert
    (none where ``shared_dim`` is 0). ``score`` is the router's score
    function (``moe.route_top_k``); with ``select_bias`` the top-k is
    taken of the scores plus a vector ``select_bias`` (num_experts,) that
    no gradient reaches, N(0, 0.02^2) at the start: a balancing term that
    a trainer moves outside the loss, which this layer does not do.
    ``whole_blocks`` is ``moe.held_experts_layer``'s: the routes' blocks
    worked whole, a step's cost the same whatever its routes. Returns
    ``(y, stats)``, the stats of ``moe.held_experts_layer``."""

    num_experts: int
    held_experts: Tuple[int, int]
    top_k: int
    expert_dim: int
    shared_dim: int
    routed_scale: float = 1.0
    dtype: Any = jnp.bfloat16
    score: str = "softmax"
    select_bias: bool = False
    whole_blocks: bool = False

    @nn.compact
    def __call__(self, x):
        b, s, hidden = x.shape
        count = self.held_experts[1]
        bank = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                            batch_axis=0)
        router = self.param("router", nn.initializers.lecun_normal(),
                            (hidden, self.num_experts), jnp.float32)
        banks = [self.param(name, bank, shape, jnp.float32)
                 for name, shape in (
                     ("experts_gate", (count, hidden, self.expert_dim)),
                     ("experts_up", (count, hidden, self.expert_dim)),
                     ("experts_down", (count, self.expert_dim, hidden)))]
        bias = None
        if self.select_bias:
            bias = jax.lax.stop_gradient(self.param(
                "select_bias", nn.initializers.normal(0.02),
                (self.num_experts,), jnp.float32))
        # ``init`` keeps nothing of the result: one block of the default
        # size, so that the init program traces no copies of it
        # (``moe.first_block_rungs``; a second of a cached set-up)
        block_rows = moe.default_block_rows(
            b * s, self.top_k, count, self.num_experts) \
            if self.is_initializing() else None
        y, stats = moe.held_experts_layer(
            x.reshape(b * s, hidden), router, *banks, self.num_experts,
            self.held_experts, self.top_k, self.routed_scale,
            block_rows=block_rows, score=self.score, select_bias=bias,
            whole_blocks=self.whole_blocks)
        if not self.shared_dim:
            return y.reshape(b, s, hidden), stats
        with jax.named_scope(scopes.MOE_SHARED):
            dense = _dense(self.dtype)
            shared = dense(hidden, name="shared_down")(
                nn.silu(dense(self.shared_dim, name="shared_gate")(x))
                * dense(self.shared_dim, name="shared_up")(x))
        return y.reshape(b, s, hidden) + shared, stats


class SolarLayer(nn.Module):
    """``x + Attn(norm(x))`` then ``x + MoE(norm(x))``. ``attn`` is the
    layer's attention class and ``attn_args`` / ``moe_args`` the
    constructor arguments of it and of ``SparseExperts``."""

    attn: Any
    attn_args: Tuple
    moe_args: Tuple
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        norm = functools.partial(RMSNorm, self.norm_eps, self.dtype)
        with jax.named_scope(scopes.NORM):
            y = norm(name="attn_norm")(x)
        x = x + self.attn(*self.attn_args, name="attn")(y)
        with jax.named_scope(scopes.NORM):
            y = norm(name="mlp_norm")(x)
        y, stats = SparseExperts(*self.moe_args, name="moe")(y)
        return x + y, stats


class SolarLM(nn.Module):
    """``apply(tokens)`` -> fp32 logits (B, S, vocab); ``apply(tokens,
    labels)`` -> the cross-entropy of each position (B, S), which is what
    training at a real size can hold. The head counts, the experts and the
    vocabulary are those HELD HERE; ``num_experts`` and ``top_k`` are the
    router's own."""

    vocab_size: int = 24576
    num_layers: int = 4
    hidden: int = 4096
    gqa_layers: Tuple[int, ...] = (0,)
    num_heads: int = 8
    num_kv_heads: int = 1
    head_dim: int = 128
    kda_heads: int = 8
    kda_head_dim: int = 128
    conv_size: int = 4
    gate_rank: int = 128
    num_experts: int = 320
    held_experts: Tuple[int, int] = (0, 8)
    top_k: int = 8
    expert_dim: int = 1280
    shared_dim: int = 1280
    routed_scale: float = 1.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # Set the hvd_tpu_moe_* gauges from inside the step (a host callback:
    # such a program is not kept in JAX's persistent compile cache).
    publish_stats: bool = False

    def setup(self):
        self.tok_emb = nn.Embed(self.vocab_size, self.hidden,
                                param_dtype=jnp.float32)
        layer = nn.remat(SolarLayer)
        gqa = (GatedGQA, (self.num_heads, self.num_kv_heads, self.head_dim,
                          self.dtype))
        kda = (KDA, (self.kda_heads, self.kda_head_dim, self.conv_size,
                     self.gate_rank, self.norm_eps, self.dtype))
        experts = (self.num_experts, tuple(self.held_experts), self.top_k,
                   self.expert_dim, self.shared_dim, self.routed_scale,
                   self.dtype)
        for i in range(self.num_layers):
            setattr(self, f"layer{i}", layer(
                *(gqa if i in self.gqa_layers else kda), experts,
                self.norm_eps, self.dtype))
        self.final_norm = RMSNorm(self.norm_eps, self.dtype)
        self.lm_head = nn.remat(_Head)(self.vocab_size, self.dtype)

    def __call__(self, tokens, labels=None):
        with jax.named_scope(scopes.EMBED):
            h = self.tok_emb(tokens).astype(self.dtype)
        total = None
        for i in range(self.num_layers):
            h, stats = getattr(self, f"layer{i}")(h)
            total = stats if total is None else jax.tree.map(
                jnp.add, total, stats)
        if self.publish_stats and not self.is_initializing():
            moe.record_held_stats(total, self.held_experts[0])
        with jax.named_scope(scopes.NORM):
            z = self.final_norm(h)
        if labels is None:
            return self.lm_head(z)
        return _loss_before_the_backward(self.lm_head, z, labels)


def solar_loss(model, params, tokens, weights=None):
    """Mean next-token cross-entropy of ``tokens`` (B, S + 1), weighted by
    ``weights`` (B, S) where given. No auxiliary loss."""
    ce = model.apply({"params": params}, tokens[:, :-1], tokens[:, 1:])
    if weights is None:
        return ce.mean()
    return (ce * weights).sum() / weights.sum()
