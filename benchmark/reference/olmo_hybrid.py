"""Plain reference for family ``olmo_hybrid``: a decoder whose token mixer
is a scalar-gated delta rule (Gated DeltaNet) or full softmax attention
without positions by a pattern (``layer_types``), a dense SwiGLU
feed-forward in every layer and Olmo's block, which norms a branch's
output, in straightforward ``jax.numpy`` and float32. No kernels, no
``hvd``, no flax: it reads the parameter tree the system initialised and
nothing else of the program.

The published model (``allenai/Olmo-Hybrid-7B``, ``config.json``,
``model_type`` ``olmo_hybrid``):

    h = Embed(tokens)
    h <- h + RMSNorm(Mix_l(h))
    h <- h + RMSNorm(W_down(silu(W_gate h) * W_up h))
    logits = RMSNorm(h) W_head

Gated DeltaNet mixer (``linear_num_key_heads`` = ``linear_num_value_heads``
H heads, ``linear_key_head_dim`` d_k, ``linear_value_head_dim`` d_v,
``linear_conv_kernel_dim`` taps; no bias):

    [q | k | v] = silu(conv(x W_qkv))             widths H d_k | H d_k | H d_v (zeros before the sequence)
    q_h = q_h / sqrt(sum q_h^2 + 1e-6) / sqrt(d_k);   k_h = k_h / sqrt(sum k_h^2 + 1e-6)
    beta_t = 2 sigmoid(x_t W_b)                   (the 2: linear_allow_neg_eigval)
    alpha_t = exp(-exp(A_log) softplus(x_t W_a + dt_bias))
    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - (alpha_t S_{t-1})^T k_t)^T      a head: (d_k x d_v), S_0 = 0
    o_t = S_t^T q_t
    out = (RMSNorm_head(o_t) * w * silu(x_t W_g)) W_o

THE RECURRENCE IS RUN AS WRITTEN, TOKEN BY TOKEN (``_recurrence``): a
``lax.scan`` over the tokens of a block inside a ``lax.scan`` over the
blocks of ``_TOKEN_BLOCK`` tokens, each block rematerialised, so that the
backward holds one state a block and one block's states (a state of 30
heads x 96 x 192 fp32 is 2.1 MiB; 8,192 of them would be 17 GiB). Nothing
of the program's chunked algorithm (pair matrices, a triangular solve, a
carry between chunks) is in it. The state's two products are
``dot_general``s and so take the caller's matmul precision.

Full attention: q = RMSNorm(x W_q), k = RMSNorm(x W_k), each norm over the
WHOLE projection (a scale vector as wide as the projection), then cut
into heads of ``hidden / heads``; NO positions (``rope_theta`` null);
causal softmax at ``head_dim ** -0.5``, the scores of a block of
``_QUERY_BLOCK`` queries at a time against the keys they can see, each
block rematerialised; ``W_o``.

The share of the deployment (the configuration's ``deployment``): the
parameter tree holds the vocabulary rows of ONE chip, and the logits, the
softmax and the loss are over that slice.

Departures from the published description, each a line of the
configuration's ``assumed``: what the config does not spell out is the
family's convention (Olmo 2/3's norm on a branch's output and QK norm
over the projection; the public Gated DeltaNet's L2 norm of q and k, its
norm-then-SiLU-gate on the output and its decay's parameters); the three
projections of q, k and v are one matrix here, its columns in that order,
and the depthwise convolution one array of taps over them.

The caller sets ``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp

_QUERY_BLOCK = 512
_TOKEN_BLOCK = 128
ATTENTION = "full_attention"


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + eps) * scale


def _conv(x, taps):
    """Depthwise, causal: ``y_t = sum_j taps[j] x_{t - (n - 1) + j}``,
    zeros before the sequence, written as the shifted products."""
    n, length = taps.shape[0], x.shape[1]
    padded = jnp.concatenate(
        [jnp.zeros_like(x[:, :1])] * (n - 1) + [x], 1)
    return sum(taps[j] * padded[:, j:j + length] for j in range(n))


def _recurrence(q, k, v, alpha, beta):
    """``S_t = alpha_t S_{t-1} + beta_t k_t (v_t - (alpha_t S_{t-1})^T
    k_t)^T``, ``o_t = S_t^T q_t``, token by token. q, k: (B, S, H, Dk); v:
    (B, S, H, Dv); alpha, beta: (B, S, H). Returns o (B, S, H, Dv)."""
    batch, length, heads, dk = k.shape

    def token(state, inputs):
        qt, kt, vt, at, bt = inputs     # (B, H, D) x 3, (B, H) x 2
        state = at[..., None, None] * state
        write = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", state, kt))
        state = state + kt[..., :, None] * write[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

    @jax.checkpoint
    def block(state, inputs):
        return jax.lax.scan(token, state, inputs)

    size = min(_TOKEN_BLOCK, length)
    while length % size:
        size -= 1

    def blocks(x):      # (B, S, ...) -> (S / size, size, B, ...)
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape(length // size, size, *x.shape[1:])

    state = jnp.zeros((batch, heads, dk, v.shape[-1]), jnp.float32)
    o = jax.lax.scan(block, state,
                     tuple(map(blocks, (q, k, v, alpha, beta))))[1]
    return jnp.moveaxis(o.reshape(length, batch, heads, -1), 0, 1)


def _gated_delta_net(x, p, config):
    batch, length, _ = x.shape
    heads = config["linear_num_key_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    qkv = jax.nn.silu(_conv(x @ p["qkv"]["kernel"], p["conv"]))
    q, k, v = jnp.split(qkv, (heads * dk, 2 * heads * dk), -1)
    q, k = (y.reshape(batch, length, heads, dk) for y in (q, k))
    q, k = (y / jnp.sqrt((y ** 2).sum(-1, keepdims=True) + 1e-6)
            for y in (q, k))
    beta = jax.nn.sigmoid(x @ p["b"]["kernel"])
    if config["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    alpha = jnp.exp(-jnp.exp(p["A_log"]) * jax.nn.softplus(
        x @ p["a"]["kernel"] + p["dt_bias"]))
    o = _recurrence(q * dk ** -0.5, k, v.reshape(batch, length, heads, dv),
                    alpha, beta)
    gate = (x @ p["gate"]["kernel"]).reshape(o.shape)
    y = _rms_norm(o, p["o_norm"], config["rms_norm_eps"]) * jax.nn.silu(gate)
    return y.reshape(batch, length, heads * dv) @ p["o"]["kernel"]


@jax.checkpoint
def _attend_block(q, k, v, start):
    """Queries ``start ..`` of a sequence against the keys up to their
    own position; k and v are cut to those keys by the caller."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    seen = (start + jnp.arange(q.shape[1]))[:, None] \
        >= jnp.arange(k.shape[1])[None, :]
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


def _attention(x, p, config):
    b, s, hidden = x.shape
    eps, heads = config["rms_norm_eps"], config["num_attention_heads"]
    # the QK norm over the whole projection, before the heads are cut
    q = _rms_norm(x @ p["q"]["kernel"], p["q_norm"]["scale"], eps)
    k = _rms_norm(x @ p["k"]["kernel"], p["k_norm"]["scale"], eps)
    q, k, v = (y.reshape(b, s, heads, -1)
               for y in (q, k, x @ p["v"]["kernel"]))
    block = min(_QUERY_BLOCK, s)
    outs = [_attend_block(q[:, start:start + block], k[:, :start + block],
                          v[:, :start + block], start)
            for start in range(0, s, block)]
    return jnp.concatenate(outs, 1).reshape(b, s, -1) @ p["o"]["kernel"]


def _swiglu(x, p):
    return (jax.nn.silu(x @ p["gate"]["kernel"]) * (x @ p["up"]["kernel"])) \
        @ p["down"]["kernel"]


def _layer(h, p, config, attention):
    """The norm on each branch's OUTPUT (Olmo 2/3's block)."""
    eps = config["rms_norm_eps"]
    mix = _attention if attention else _gated_delta_net
    h = h + _rms_norm(mix(h, p["mixer"], config), p["op_norm"]["scale"], eps)
    return h + _rms_norm(_swiglu(h, p["ffn"]), p["ffn_norm"]["scale"], eps)


class _Frozen:
    """The configuration as a static argument: hashed by identity."""

    def __init__(self, config):
        self._config = config

    def __getitem__(self, key):
        return self._config[key]


def states(params, tokens, config):
    """float32 (B, S, hidden): the normed state the head reads."""
    layer = jax.checkpoint(_layer, static_argnums=(2, 3))
    frozen = _Frozen(config)
    h = params["tok_emb"]["embedding"][tokens]
    for i in range(config["num_hidden_layers"]):
        h = layer(h, params[f"layer{i}"], frozen,
                  config["layer_types"][i] == ATTENTION)
    return _rms_norm(h, params["final_norm"]["scale"],
                     config["rms_norm_eps"])


def logits(params, tokens, config):
    """float32 (B, S, vocab). For small sizes."""
    return states(params, tokens, config) @ params["lm_head"]["kernel"]


@jax.checkpoint
def _cross_entropy(z, kernel, labels):
    logp = jax.nn.log_softmax(z @ kernel, -1)
    return -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]


def token_losses(params, batch, config):
    """float32 (B, S): the loss of the next token at every position."""
    tokens = batch["tokens"]
    return _cross_entropy(states(params, tokens[:, :-1], config),
                          params["lm_head"]["kernel"], tokens[:, 1:])
