"""Plain reference for family ``solar_open2``: a decoder whose layers are
softmax grouped-query attention (``gqa_layers``) or gated delta-rule
linear attention (KDA), each followed by a sparse mixture of SwiGLU
experts with a shared expert, in straightforward ``jax.numpy`` and
float32. No kernels, no ``hvd``, no flax: it reads the parameter tree the
system initialised and nothing else of the program.

The published model (``upstage/Solar-Open2-250B``, ``config.json``; the
``linear_attn_config`` keys follow Kimi-Linear's):

    x <- x + Attn_l(RMSNorm(x));   x <- x + MoE_l(RMSNorm(x))

KDA, per head, token by token, with S the head's Dk x Dv state:

    q = L2norm(SiLU(conv4(W_q x))) Dk^-0.5,  k = L2norm(SiLU(conv4(W_k x)))
    v = SiLU(conv4(W_v x))
    alpha_t = exp(-exp(A) softplus(W_f^up W_f^down x_t + b))
    beta_t = 2 sigmoid(w_beta x_t)
    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t
    out = W_o [RMSNorm_head(o_t) * sigmoid(W_g^up W_g^down x_t)]

run as exactly that recurrence (``_recurrence``: a ``lax.scan`` over the
tokens inside a scan over stretches of ``_STRETCH`` tokens, each stretch
rematerialised, so that the backward keeps S / 64 states and not S).
GQA: causal softmax attention, no positions, q head h on K/V head
h // group, the scores of a block of ``_QUERY_BLOCK`` queries at a time,
times ``sigmoid(W_gate x)`` elementwise, then ``W_o``. MoE: softmax over
all ``router_width`` scores, the top ``num_experts_per_tok``, their scores
normalised to sum to 1, times ``routed_scaling_factor``; a dense loop over
the experts held here, each on every token with the token's weight for it
(0 where it was not chosen); plus the shared expert.

The share of the deployment (the configuration's ``deployment``): the
parameter tree holds the query, K/V and KDA heads, the experts and the
vocabulary rows of ONE chip; the reference computes that chip's part and
leaves out, as the program does, what the experts held elsewhere would
have added. ``held_experts_first`` says which of the router's columns are
the held experts'.

Departures from the published description, each a line of the
configuration's ``assumed``: the score function (softmax before the
top-k), no router bias, no auxiliary loss, the low-rank width of the two
KDA gates (``head_dim``), an elementwise GQA output gate, the
initialisation of ``A`` and ``b``, no biases anywhere.

The caller sets ``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp

_STRETCH = 64
_QUERY_BLOCK = 512


def _rms_norm(x, p, eps):
    return x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + eps) * p["scale"]


def _conv(x, taps):
    """Causal depthwise convolution: y_t = sum_j taps[j] x_{t-(n-1)+j}."""
    n, s = taps.shape[0], x.shape[1]
    x = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
    return sum(x[:, j:j + s] * taps[j] for j in range(n))


def _recurrence(q, k, v, alpha, beta):
    """o (B, S, H, Dv) of the delta rule token by token; operands
    (B, S, H, D), ``beta`` (B, S, H)."""
    b, s, h, dk = k.shape
    pad = -s % _STRETCH

    def token(state, xs):
        qt, kt, vt, at, bt = xs
        state = state * at[..., None]
        state = state + kt[..., None] * (bt[..., None] * (
            vt - jnp.einsum("bhkv,bhk->bhv", state, kt)))[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

    @jax.checkpoint
    def stretch(state, xs):
        return jax.lax.scan(token, state, xs)

    def by_stretch(x):          # (B, S, ...) -> (S / n, n, B, ...)
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((-1, _STRETCH) + x.shape[1:])

    state = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    o = jax.lax.scan(stretch, state,
                     tuple(map(by_stretch, (q, k, v, alpha, beta))))[1]
    return jnp.moveaxis(o.reshape((-1,) + o.shape[2:]), 0, 1)[:, :s]


def _kda(x, p, config):
    b, s, _ = x.shape
    width = config["linear_attn_config"]["head_dim"]

    def mixed(name):
        y = jax.nn.silu(_conv(x @ p[name]["kernel"], p["conv_" + name]))
        return y.reshape(b, s, -1, width)

    def unit(y):
        return y / jnp.sqrt((y * y).sum(-1, keepdims=True) + 1e-6)

    q, k, v = unit(mixed("q")) * width ** -0.5, unit(mixed("k")), mixed("v")
    f = (x @ p["f_down"]["kernel"]) @ p["f_up"]["kernel"] + p["dt_bias"]
    alpha = jnp.exp(-jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        f.reshape(b, s, -1, width)))
    beta = 2.0 * jax.nn.sigmoid(x @ p["beta"]["kernel"])
    o = _rms_norm(_recurrence(q, k, v, alpha, beta), p["o_norm"],
                  config["rms_norm_eps"])
    gate = jax.nn.sigmoid((x @ p["g_down"]["kernel"]) @ p["g_up"]["kernel"])
    return (o.reshape(b, s, -1) * gate) @ p["o"]["kernel"]


def _gqa(x, p, config):
    b, s, _ = x.shape
    width = config["head_dim"]
    q, k, v = ((x @ p[n]["kernel"]).reshape(b, s, -1, width)
               for n in ("q", "k", "v"))
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, 2), jnp.repeat(v, group, 2)
    block = min(_QUERY_BLOCK, s)
    outs = []
    for start in range(0, s, block):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, start:start + block],
                            k) * width ** -0.5
        seen = (start + jnp.arange(scores.shape[2]))[:, None] \
            >= jnp.arange(s)[None, :]
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd",
                               jax.nn.softmax(scores, -1), v))
    o = jnp.concatenate(outs, 1).reshape(b, s, -1)
    return (o * jax.nn.sigmoid(x @ p["gate"]["kernel"])) @ p["o"]["kernel"]


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _moe(x, p, config):
    scores = jax.nn.softmax(x @ p["router"], -1)
    chosen, experts = jax.lax.top_k(scores, config["num_experts_per_tok"])
    weights = chosen / chosen.sum(-1, keepdims=True) \
        * config["routed_scaling_factor"]
    y = _swiglu(x, p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
                p["shared_down"]["kernel"])
    first = config["held_experts_first"]
    for e in range(p["experts_gate"].shape[0]):
        weight = (weights * (experts == first + e)).sum(-1)
        y = y + weight[..., None] * _swiglu(
            x, p["experts_gate"][e], p["experts_up"][e],
            p["experts_down"][e])
    return y


def _layer(x, p, config, softmax):
    eps = config["rms_norm_eps"]
    attn = _gqa if softmax else _kda
    x = x + attn(_rms_norm(x, p["attn_norm"], eps), p["attn"], config)
    return x + _moe(_rms_norm(x, p["mlp_norm"], eps), p["moe"], config)


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
        h = layer(h, params[f"layer{i}"], frozen, i in config["gqa_layers"])
    return _rms_norm(h, params["final_norm"], config["rms_norm_eps"])


def logits(params, tokens, config):
    """float32 (B, S, vocab). For small sizes."""
    return states(params, tokens, config) @ params["lm_head"]["kernel"]


@jax.checkpoint
def _cross_entropy(z, head, labels):
    logp = jax.nn.log_softmax(z @ head, -1)
    return -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]


def token_losses(params, batch, config):
    """float32 (B, S): the loss of the next token at every position."""
    tokens = batch["tokens"]
    return _cross_entropy(states(params, tokens[:, :-1], config),
                          params["lm_head"]["kernel"], tokens[:, 1:])
