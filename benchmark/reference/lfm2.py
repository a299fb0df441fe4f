"""Plain reference for family ``lfm2``: a decoder whose token mixer is a
gated short convolution or rotary grouped-query attention by a pattern
(``layer_types``) and whose feed-forward is a dense SwiGLU in the first
``num_dense_layers`` layers and a sparse mixture of SwiGLU experts after,
in straightforward ``jax.numpy`` and float32. No kernels, no ``hvd``, no
flax: it reads the parameter tree the system initialised and nothing else
of the program.

The published model (``LiquidAI/LFM2-8B-A1B``, ``config.json``,
``model_type`` ``lfm2_moe``), layer l:

    h <- h + Mix_l(RMSNorm_op(h));   h <- h + FFN_l(RMSNorm_ffn(h))

Short convolution (``conv_L_cache`` 3 taps, no bias):

    [B, C, X] = split3(W_in u);  z = B * X
    c_t = w_0 z_{t-2} + w_1 z_{t-1} + w_2 z_t      (zeros before the sequence)
    out = W_out (C * c)

written as the three shifted products. Attention: q = W_q u as heads of
64, k = W_k u and v = W_v u as a quarter as many; RMSNorm over each
head's channels on q and on k; rotary positions over the whole head
width in half-split pairs (channel i with channel i + 32), angle t
theta^(-i / 32), written out here; causal softmax at scale 64^-0.5, q head
h on K/V head h // group, the scores of a block of ``_QUERY_BLOCK``
queries at a time against the keys they can see, each block
rematerialised (32 heads x 8192^2 fp32 scores are 8.6 GB a row whole);
``W_o``. Dense feed-forward ``W2(SiLU(W1 x) * W3 x)``. Expert layer:
``s = sigmoid(W_r x)``; the experts are the top ``num_experts_per_tok`` of
``s + b``; their weights the UNBIASED scores ``s_i / (sum_chosen s +
1e-6)`` times ``routed_scaling_factor``; a dense loop over the experts
held here, each on every token with the token's weight for it (0 where it
was not chosen). Output: a final RMSNorm and logits over the TIED
embedding.

The share of the deployment (the configuration's ``deployment``): the
parameter tree holds the experts and the vocabulary rows of ONE chip; the
reference computes that chip's part and leaves out, as the program does,
what the experts held elsewhere would have added. ``held_experts_first``
says which of the router's columns are the held experts'.

Departures from the published description, each a line of the
configuration's ``assumed``: the tied head (the family's; the config's
row has no key), the ``1e-6`` under the weights' division, the selection
bias ``b`` as a seeded constant that no gradient and no balancing update
moves, no auxiliary loss, no biases anywhere.

The caller sets ``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp

_QUERY_BLOCK = 512
ATTENTION = "full_attention"


def _rms_norm(x, p, eps):
    return x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + eps) * p["scale"]


def _short_conv(u, p, config):
    b, c, x = jnp.split(u @ p["in_proj"]["kernel"], 3, -1)
    z = b * x
    taps = p["conv"]
    before = jnp.zeros_like(z[:, :1])
    z1 = jnp.concatenate([before, z[:, :-1]], 1)            # z_{t-1}
    z2 = jnp.concatenate([before, before, z[:, :-2]], 1)    # z_{t-2}
    mixed = taps[0] * z2 + taps[1] * z1 + taps[2] * z
    return (c * mixed) @ p["out_proj"]["kernel"]


def _rotary(x, theta):
    """(B, S, H, D): channel i and channel i + D / 2 turned by the angle
    t theta^(-2 i / D)."""
    s, d = x.shape[1], x.shape[-1]
    half = d // 2
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@jax.checkpoint
def _attend_block(q, k, v, start):
    """Queries ``start ..`` of a sequence against the keys up to their
    own position; k and v are cut to those keys by the caller."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    seen = (start + jnp.arange(q.shape[1]))[:, None] \
        >= jnp.arange(k.shape[1])[None, :]
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


def _attention(u, p, config):
    b, s, hidden = u.shape
    width = hidden // config["num_attention_heads"]
    eps, theta = config["norm_eps"], float(config["rope_theta"])
    q, k, v = ((u @ p[n]["kernel"]).reshape(b, s, -1, width)
               for n in ("q", "k", "v"))
    q = _rotary(_rms_norm(q, p["q_norm"], eps), theta)
    k = _rotary(_rms_norm(k, p["k_norm"], eps), theta)
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, 2), jnp.repeat(v, group, 2)
    block = min(_QUERY_BLOCK, s)
    outs = [_attend_block(q[:, start:start + block], k[:, :start + block],
                          v[:, :start + block], start)
            for start in range(0, s, block)]
    return jnp.concatenate(outs, 1).reshape(b, s, -1) @ p["o"]["kernel"]


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _experts(x, p, config):
    scores = jax.nn.sigmoid(x @ p["router"])
    experts = jax.lax.top_k(scores + p["select_bias"],
                            config["num_experts_per_tok"])[1]
    chosen = jnp.take_along_axis(scores, experts, -1)
    weights = chosen / (chosen.sum(-1, keepdims=True) + 1e-6) \
        * config["routed_scaling_factor"]
    first = config["held_experts_first"]
    y = 0.0
    for e in range(p["experts_gate"].shape[0]):
        weight = (weights * (experts == first + e)).sum(-1)
        y = y + weight[..., None] * _swiglu(
            x, p["experts_gate"][e], p["experts_up"][e],
            p["experts_down"][e])
    return y


def _layer(h, p, config, attention, dense):
    eps = config["norm_eps"]
    mix = _attention if attention else _short_conv
    h = h + mix(_rms_norm(h, p["op_norm"], eps), p["mixer"], config)
    x = _rms_norm(h, p["ffn_norm"], eps)
    if dense:
        return h + _swiglu(x, *(p["ffn"][n]["kernel"]
                                for n in ("gate", "up", "down")))
    return h + _experts(x, p["ffn"], config)


class _Frozen:
    """The configuration as a static argument: hashed by identity."""

    def __init__(self, config):
        self._config = config

    def __getitem__(self, key):
        return self._config[key]


def states(params, tokens, config):
    """float32 (B, S, hidden): the normed state the head reads."""
    layer = jax.checkpoint(_layer, static_argnums=(2, 3, 4))
    frozen = _Frozen(config)
    h = params["tok_emb"]["embedding"][tokens]
    for i in range(config["num_hidden_layers"]):
        h = layer(h, params[f"layer{i}"], frozen,
                  config["layer_types"][i] == ATTENTION,
                  i < config["num_dense_layers"])
    return _rms_norm(h, params["final_norm"], config["norm_eps"])


def logits(params, tokens, config):
    """float32 (B, S, vocab). For small sizes."""
    return states(params, tokens, config) @ params["tok_emb"]["embedding"].T


@jax.checkpoint
def _cross_entropy(z, table, labels):
    logp = jax.nn.log_softmax(z @ table.T, -1)
    return -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]


def token_losses(params, batch, config):
    """float32 (B, S): the loss of the next token at every position."""
    tokens = batch["tokens"]
    return _cross_entropy(states(params, tokens[:, :-1], config),
                          params["tok_emb"]["embedding"], tokens[:, 1:])
