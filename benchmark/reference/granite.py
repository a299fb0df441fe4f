"""Plain reference for family ``granite``: a decoder whose token mixer is
a Mamba-2 state-space layer or grouped-query attention without positions
by a pattern (``layer_types``), a dense SwiGLU feed-forward in every
layer and Granite's four multipliers, in straightforward ``jax.numpy``
and float32. No kernels, no ``hvd``, no flax: it reads the parameter
tree the system initialised and nothing else of the program.

The published model (``ibm-granite/granite-4.0-h-micro``,
``config.json``, ``model_type`` ``granitemoehybrid``, no experts):

    h = embedding_multiplier * Embed(tokens)
    h <- h + residual_multiplier * Mix_l(RMSNorm(h))
    h <- h + residual_multiplier * W_down(silu(W_gate y) * W_up y),  y = RMSNorm(h)
    logits = RMSNorm(h) Embed^T / logits_scaling

Mamba-2 mixer (``mamba_n_heads`` H heads of ``mamba_d_head`` P channels,
``mamba_n_groups`` G groups of B and C over a state of ``mamba_d_state``
N, ``mamba_d_conv`` taps; no projection bias, a convolution bias):

    [z | xBC | dt] = W_in u                       widths H P | H P + 2 G N | H
    xBC_t = silu(sum_j w_j xBC_{t - (taps - 1) + j} + b_conv)   (zeros before the sequence)
    [x | B | C] = xBC
    delta_t = softplus(dt_t + dt_bias);   a_t = exp(-delta_t exp(A_log))
    H_t = a_t H_{t-1} + delta_t x_t B_t^T         a head: (P x N), H_0 = 0
    y_t = H_t C_t + D x_t
    out = W_out (RMSNorm(y * silu(z)) * w)        the gate BEFORE the norm, a group at a time

THE RECURRENCE IS RUN AS WRITTEN, TOKEN BY TOKEN (``_recurrence``): a
``lax.scan`` over the tokens of a block inside a ``lax.scan`` over the
blocks of ``_TOKEN_BLOCK`` tokens, each block rematerialised, so that the
backward holds one state a block and one block's states (a state of 64
heads x 64 x 128 fp32 is 2 MiB; 8,192 of them would be 16 GiB). Nothing
of the program's chunked algorithm (pair matrices, chunk states, a carry
between chunks) is in it. ``H_t C_t`` is a ``dot_general`` and so takes
the caller's matmul precision.

Attention: q = W_q u as heads of ``hidden / heads``, k and v as
``num_key_value_heads`` of them; NO positions
(``position_embedding_type`` ``"nope"``) and no norm on q or k; causal
softmax at scale ``attention_multiplier`` (not ``head_dim ** -0.5``), q
head h on K/V head h // group, the scores of a block of
``_QUERY_BLOCK`` queries at a time against the keys they can see, each
block rematerialised; ``W_o``.

The share of the deployment (the configuration's ``deployment``): the
parameter tree holds the vocabulary rows of ONE chip, and the logits, the
softmax and the loss are over that slice.

Departures from the published description, each a line of the
configuration's ``assumed``: what the config does not spell out is the
public Mamba-2 convention (the order ``z | xBC | dt`` of ``W_in``'s
columns and ``x | B | C`` of the convolution's, the gate before the norm,
``dt`` unclamped); the published feed-forward's ``input_linear`` holds
gate and up in one matrix (first half the gate), here two; no biases but
the convolution's.

The caller sets ``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp

_QUERY_BLOCK = 512
_TOKEN_BLOCK = 128
ATTENTION = "attention"


def _rms_norm(x, p, eps):
    return x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + eps) * p["scale"]


def _conv(x, taps, bias):
    """Depthwise, causal: ``y_t = sum_j taps[j] x_{t - (n - 1) + j} +
    bias``, zeros before the sequence, written as the shifted products."""
    n, length = taps.shape[0], x.shape[1]
    padded = jnp.concatenate(
        [jnp.zeros_like(x[:, :1])] * (n - 1) + [x], 1)
    return sum(taps[j] * padded[:, j:j + length] for j in range(n)) + bias


def _recurrence(x, delta, a, b, c):
    """``H_t = exp(delta_t a) H_{t-1} + delta_t x_t B_t^T``, ``y_t = H_t
    C_t``, token by token. x: (B, S, H, P); delta: (B, S, H); a: (H,); b,
    c: (B, S, H, N). Returns y (B, S, H, P)."""
    batch, length, heads, width = x.shape

    def token(state, inputs):
        xt, dt, bt, ct = inputs         # (B, H, P), (B, H), (B, H, N) x 2
        state = jnp.exp(dt * a)[..., None, None] * state \
            + (dt[..., None] * xt)[..., None] * bt[..., None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct)

    @jax.checkpoint
    def block(state, inputs):
        return jax.lax.scan(token, state, inputs)

    size = min(_TOKEN_BLOCK, length)
    while length % size:
        size -= 1

    def blocks(v):      # (B, S, ...) -> (S / size, size, B, ...)
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape(length // size, size, *v.shape[1:])

    state = jnp.zeros((batch, heads, width, b.shape[-1]), jnp.float32)
    y = jax.lax.scan(block, state, tuple(map(blocks, (x, delta, b, c))))[1]
    return jnp.moveaxis(y.reshape(length, batch, heads, width), 0, 1)


def _mamba(u, p, config):
    batch, length, _ = u.shape
    heads, width = config["mamba_n_heads"], config["mamba_d_head"]
    groups, n = config["mamba_n_groups"], config["mamba_d_state"]
    inner = heads * width
    z, xbc, dt = jnp.split(u @ p["in_proj"]["kernel"],
                           (inner, 2 * inner + 2 * groups * n), -1)
    xbc = jax.nn.silu(_conv(xbc, p["conv"], p["conv_bias"]))
    x, b, c = jnp.split(xbc, (inner, inner + groups * n), -1)
    x = x.reshape(batch, length, heads, width)
    # head h reads the B and C of group h // (heads / groups)
    b, c = (jnp.repeat(v.reshape(batch, length, groups, n),
                       heads // groups, 2) for v in (b, c))
    delta = jax.nn.softplus(dt + p["dt_bias"])
    y = _recurrence(x, delta, -jnp.exp(p["A_log"]), b, c) \
        + p["D"][:, None] * x
    y = y.reshape(batch, length, inner) * jax.nn.silu(z)
    y = y.reshape(batch, length, groups, -1)
    y = y / jnp.sqrt((y ** 2).mean(-1, keepdims=True)
                     + config["rms_norm_eps"])
    y = y.reshape(batch, length, inner) * p["norm"]["scale"]
    return y @ p["out_proj"]["kernel"]


@jax.checkpoint
def _attend_block(q, k, v, start, scale):
    """Queries ``start ..`` of a sequence against the keys up to their
    own position; k and v are cut to those keys by the caller."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    seen = (start + jnp.arange(q.shape[1]))[:, None] \
        >= jnp.arange(k.shape[1])[None, :]
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


def _attention(u, p, config):
    b, s, hidden = u.shape
    width = hidden // config["num_attention_heads"]
    q, k, v = ((u @ p[n]["kernel"]).reshape(b, s, -1, width)
               for n in ("q", "k", "v"))
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, 2), jnp.repeat(v, group, 2)
    block = min(_QUERY_BLOCK, s)
    scale = jnp.float32(config["attention_multiplier"])
    outs = [_attend_block(q[:, start:start + block], k[:, :start + block],
                          v[:, :start + block], start, scale)
            for start in range(0, s, block)]
    return jnp.concatenate(outs, 1).reshape(b, s, -1) @ p["o"]["kernel"]


def _swiglu(x, p):
    return (jax.nn.silu(x @ p["gate"]["kernel"]) * (x @ p["up"]["kernel"])) \
        @ p["down"]["kernel"]


def _layer(h, p, config, attention):
    eps, r = config["rms_norm_eps"], config["residual_multiplier"]
    mix = _attention if attention else _mamba
    h = h + r * mix(_rms_norm(h, p["op_norm"], eps), p["mixer"], config)
    return h + r * _swiglu(_rms_norm(h, p["ffn_norm"], eps), p["ffn"])


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
    h = config["embedding_multiplier"] * params["tok_emb"]["embedding"][tokens]
    for i in range(config["num_hidden_layers"]):
        h = layer(h, params[f"layer{i}"], frozen,
                  config["layer_types"][i] == ATTENTION)
    return _rms_norm(h, params["final_norm"], config["rms_norm_eps"])


def logits(params, tokens, config):
    """float32 (B, S, vocab). For small sizes."""
    return states(params, tokens, config) \
        @ params["tok_emb"]["embedding"].T / config["logits_scaling"]


@jax.checkpoint
def _cross_entropy(z, table, labels, scaling):
    logp = jax.nn.log_softmax(z @ table.T / scaling, -1)
    return -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]


def token_losses(params, batch, config):
    """float32 (B, S): the loss of the next token at every position."""
    tokens = batch["tokens"]
    return _cross_entropy(states(params, tokens[:, :-1], config),
                          params["tok_emb"]["embedding"], tokens[:, 1:],
                          jnp.float32(config["logits_scaling"]))
